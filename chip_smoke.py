#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls
(``tpujob run JOB.yaml`` -> supervisor -> reconciler -> replica, and
``tpujob serve-request`` for the serving job), at the full width of the
models, with random weights made from a seed:

- kernels: the Pallas flash-attention forward and both backward kernels at
  the trainer's head shape (32 query / 8 KV heads of 128, blocks of 1024)
  against the dense ``jax.numpy`` reference the repo's tests use;
- train: Llama-3-8B widths (d_model 4096, 32/8 heads of 128, d_ff 14336,
  vocabulary 128,256), depth cut to 4 layers, bf16 params + adafactor +
  'dots' remat, seq 4096, batch 1; warm-up + timed steps, one blocking
  checkpoint on the last step;
- serve: the ``1b`` preset at full width and depth, int8 weights + int8 KV,
  L=4096, 8 slots, chunk 128, block 64; four requests of mixed lengths;
- on a host with four or more chips, the same trainer as one process on
  four devices (fsdp=4) and as four one-chip processes (after the
  ``smoke_dist`` collective ring).

This process never imports JAX: a parent that has touched JAX holds the
chip and its children could not open it. Children run one after another.
It asserts facts the replicas reported (status records, ``--json`` lines,
responses, the stored job object), not exit codes.

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every phase passed on an accelerator. With no accelerator the probe fails
in seconds and nothing else runs. ``--rehearse-cpu`` runs the same phases
and assertions at tiny size on the CPU for the tier-1 test; it prints
``REHEARSAL platform=cpu`` and never prints the result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
STATE = OUT / "state"
CLI = [sys.executable, "-m", "pytorch_operator_tpu.client.cli", "--state-dir", str(STATE)]

PROBE = """
import json, jax, jaxlib
from importlib import metadata
d = jax.devices()
print(json.dumps({
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": metadata.version("libtpu"),
}))
"""

# Flash attention against the test oracle, run in a child like every
# other phase. The kernel runs compiled on the chip and interpreted on the
# CPU (ops/flash_attention.py decides from the backend, not from here).
KERNEL_CHECK = """
import importlib, json, sys
import jax, jax.numpy as jnp
from pytorch_operator_tpu.runtime.backend import device_report, setup_backend
setup_backend()
fa = importlib.import_module("pytorch_operator_tpu.ops.flash_attention")
S, H, KH, D = (int(a) for a in sys.argv[1:5])
q, k, v = (
    jax.random.normal(key, (1, S, h, D), jnp.bfloat16)
    for key, h in zip(jax.random.split(jax.random.key(0), 3), (H, KH, KH))
)
def out_and_grads(attend):
    loss = lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()
    return (attend(q, k, v), *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
got = out_and_grads(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
want = out_and_grads(lambda q, k, v: fa._dense_reference(q, k, v, causal=True))
err = [
    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
          / jnp.max(jnp.abs(b.astype(jnp.float32))))
    for a, b in zip(got, want)
]
print(json.dumps({"rel_err": dict(zip(("out", "dq", "dk", "dv"), err)), **device_report()}))
"""

# The chip sizes (ISSUE 21 §1) and the rehearsal's: same flags, same
# phases, same assertions; only the numbers differ.
CHIP = dict(
    platform="tpu",
    kernel=(1024, 32, 8, 128),  # S, H, KH, D
    resource="tpu_chips",
    gang=4,
    vocab=128_256,
    d_model=4096,
    train=["--config", "8b", "--seq-len", "4096"],
    layers=4,
    fsdp_layers=8,  # four chips hold a deeper cut
    serve=["--config", "1b", "--max-decode-len", "4096", "--slots", "8",
           "--chunk", "128", "--block", "64"],
    requests=[(100, 33), (260, 64), (700, 96), (1500, 128)],
)
REHEARSAL = dict(
    platform="cpu",
    kernel=(128, 4, 2, 16),
    resource="cpu_devices",
    gang=2,
    vocab=256,
    d_model=64,
    train=["--config", "tiny", "--attn-impl", "flash", "--xent", "chunked",
           "--seq-len", "128"],
    layers=2,
    fsdp_layers=2,
    serve=["--config", "tiny", "--max-decode-len", "256", "--slots", "2",
           "--chunk", "16", "--block", "4"],
    requests=[(10, 5), (30, 9), (70, 12), (150, 16)],
)
# lr 1e-2 is adafactor's usual rate with parameter scaling; a relative
# update of 3e-4 (the flag's default) is below bfloat16's resolution.
TRAIN_COMMON = ["--param-dtype", "bfloat16", "--optimizer", "adafactor",
                "--lr", "1e-2", "--remat", "--remat-policy", "dots", "--json"]
WARMUP, STEPS = 2, 18


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def job_yaml(name: str, module: str, args: list, resource: str, chips: int,
             workers: int = 0) -> Path:
    template = (
        f"{{module: {module}, args: {json.dumps(args)}, "
        f"resources: {{{resource}: {chips}}}}}"
    )
    specs = f"    Master: {{replicas: 1, template: {template}}}\n"
    if workers:
        specs += f"    Worker: {{replicas: {workers}, template: {template}}}\n"
    path = OUT / "jobs" / f"{name}.yaml"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(
        "api_version: tpujob.dev/v1\nkind: TPUJob\n"
        f"metadata: {{name: {name}}}\nspec:\n  replica_specs:\n{specs}"
        # A failure fails the job at once: a second life must not pass.
        "  run_policy: {backoff_limit: 0}\n"
    )
    return path


def stop(proc: subprocess.Popen) -> None:
    """End a ``tpujob run`` and everything under it. SIGINT lets its
    ``finally`` shut the supervisor down, which kills the replicas; they
    live in their own sessions, so whatever outlives that is swept from
    the runner's records."""
    if proc.poll() is not None:
        return  # it ended by itself, and its supervisor shut down with it
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    for rec in (STATE / "replicas").glob("*.json"):
        try:
            os.killpg(json.loads(rec.read_text())["pid"], signal.SIGKILL)
        except (OSError, ValueError, KeyError, TypeError):
            pass  # already gone, which is the goal


def run_job(path: Path, timeout: float) -> subprocess.Popen:
    return subprocess.Popen(
        CLI + ["run", str(path), "--timeout", str(timeout)], cwd=ROOT
    )


def finish_job(proc: subprocess.Popen, name: str, timeout: float) -> None:
    """Wait for ``tpujob run`` and assert what the stored job says."""
    try:
        proc.wait(timeout=timeout + 60)
    except subprocess.TimeoutExpired:
        pass
    finally:
        stop(proc)
    out = subprocess.run(
        CLI + ["describe", name, "--json"], cwd=ROOT, capture_output=True,
        text=True,
    )
    check(out.returncode == 0, f"job {name} is in the store")
    status = json.loads(out.stdout)["status"]
    done = {c["type"] for c in status["conditions"] if c["status"]}
    if "Succeeded" not in done:
        for log in sorted((STATE / "logs").glob(f"default_{name}-*.log")):
            tail = log.read_text(errors="replace")[-3000:]
            print(f"---- {log.name} (tail)\n{tail}\n----", flush=True)
    check("Succeeded" in done, f"job {name} phase is Succeeded (has {sorted(done)})")
    check(status["restart_count"] == 0, f"job {name} restarts == 0")


def json_line(name: str) -> dict:
    """The ``--json`` result the Master printed: last JSON line of its log."""
    log = STATE / "logs" / f"default_{name}-master-0.log"
    for line in reversed(log.read_text(errors="replace").splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(f"no JSON result line in {log}")


def device_records(name: str) -> list:
    """One ``device`` status record per replica of the job."""
    recs = []
    for f in sorted((STATE / "status" / f"default_{name}").glob("*.jsonl")):
        for line in f.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("event") == "device":
                recs.append(rec)
    return recs


def check_device(rec: dict, size: dict, world: int, local: int) -> None:
    check(rec["platform"] == size["platform"], f"platform == {size['platform']!r}")
    check(bool(rec["device_kind"]), f"device_kind {rec['device_kind']!r} is non-empty")
    check(rec["device_count"] == world, f"device count {rec['device_count']} == {world}")
    check(
        rec["local_device_count"] == local,
        f"local device count {rec['local_device_count']} == {local}",
    )


def check_trained(res: dict, size: dict) -> None:
    end = WARMUP + STEPS
    check(res["start_step"] == 0, "training started at step 0 (no stale checkpoint)")
    check(res["end_step"] == end, f"end_step {res['end_step']} == {end}")
    check(res["d_model"] == size["d_model"], f"d_model == {size['d_model']} (full width)")
    check(res["n_layers"] >= 2, f"n_layers {res['n_layers']} >= 2 (the layer scan compiles)")
    first, final = res["first_loss"], res["final_loss"]
    # Random weights give the head unit-variance logits, so the first loss
    # is ln V + 1/2 by arithmetic — a reference for the whole forward pass.
    # The tokens are uniform over V, so a few steps cannot go far below ln V
    # at 128,256 (82k tokens seen); what they must not do is rise or blow
    # up. The tier-1 rehearsal asserts that the tiny model does learn.
    init = math.log(size["vocab"]) + 0.5
    check(abs(first - init) < 0.25, f"first loss {first} is ln V + 1/2 = {init:.3f} +- 0.25")
    check(
        math.isfinite(final) and final < first + 0.1,
        f"final loss {final} is finite and not above the first step's {first}",
    )


def cache_entries() -> tuple:
    from pytorch_operator_tpu.runtime.backend import compile_cache_dir

    d = Path(compile_cache_dir())
    return d, (sum(1 for _ in d.iterdir()) if d.is_dir() else 0)


def phase(title: str):
    print(f"== {title}", flush=True)
    return time.time(), cache_entries()[1]


def phase_done(title: str, t0: float, before: int, extra: str = "") -> None:
    d, after = cache_entries()
    print(
        f"== {title}: passed, wall {time.time() - t0:.1f}s{extra}; "
        f"compile cache {d}: {before} -> {after} entries",
        flush=True,
    )


def kernel_phase(size: dict) -> None:
    title = "kernels flash attention vs dense reference (S, H, KH, D) = %s" % (size["kernel"],)
    t0, before = phase(title)
    out = subprocess.run(
        [sys.executable, "-c", KERNEL_CHECK, *map(str, size["kernel"])], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": size["platform"]},
    )
    if out.returncode != 0:
        print(out.stderr[-3000:], flush=True)
    check(out.returncode == 0, "the three kernels compiled and ran")
    res = json.loads(out.stdout.strip().splitlines()[-1])
    check(res["platform"] == size["platform"], f"platform == {size['platform']!r}")
    for name, err in res["rel_err"].items():
        check(err < 2e-2, f"{name}: max error / max reference = {err:.2e} < 2e-2")
    phase_done(title, t0, before)


def train_phase(size: dict, name: str, layers: int, chips: int, workers: int = 0,
                checkpoint: bool = True) -> dict:
    world = chips * (1 + workers)
    title = f"train {name}: {1 + workers} process(es) x {chips} device(s)"
    t0, before = phase(title)
    args = size["train"] + TRAIN_COMMON + [
        "--layers", str(layers), "--batch-size", str(world),
        "--warmup", str(WARMUP), "--steps", str(STEPS),
    ]
    if world > 1:
        args += ["--mesh", f"fsdp={world}"]
    if checkpoint:
        args += ["--checkpoint-every", str(WARMUP + STEPS)]
    path = job_yaml(name, "pytorch_operator_tpu.workloads.llama_train", args,
                    size["resource"], chips, workers)
    finish_job(run_job(path, 450), name, 450)
    res = json_line(name)
    check_trained(res, size)
    recs = device_records(name)
    check(len(recs) == 1 + workers, f"{len(recs)} device record(s) == {1 + workers} replica(s)")
    for rec in recs:
        check_device(rec, size, world, chips)
    ids = [i for rec in recs for i in rec["local_device_ids"]]
    check(len(set(ids)) == world, f"local devices {ids} are {world} distinct devices")
    in_use = [b for rec in recs for b in rec["bytes_in_use"]]
    if size["platform"] == "tpu":
        check(
            min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
            f"bytes_in_use {in_use} non-zero on every device, within 2x of each other",
        )
    if checkpoint:
        from pytorch_operator_tpu.checkpoint.integrity import verify_step

        root = STATE / "checkpoints" / f"default_{name}"
        check(
            verify_step(root, res["end_step"]) is True,
            f"checkpoint {res['end_step']} is committed and matches its sidecar",
        )
    peak = [b for rec in recs for b in rec["peak_bytes_in_use"]]
    phase_done(
        title, t0, before,
        f", first step (compile) {res['first_step_s']}s, {res['params_m']}M params, "
        f"loss {res['first_loss']} -> {res['final_loss']}, peak_bytes_in_use {peak}",
    )
    return res


def serve_phase(size: dict) -> None:
    name = "smoke-serve"
    title = f"serve {name}: 1 process x 1 device"
    t0, before = phase(title)
    spool = OUT / "spool"
    requests = size["requests"]
    args = size["serve"] + [
        "--quantize", "int8", "--kv-quantize", "int8", "--spool", str(spool),
        "--max-requests", str(len(requests)), "--json",
    ]
    path = job_yaml(name, "pytorch_operator_tpu.workloads.serve", args,
                    size["resource"], 1)
    job = run_job(path, 450)
    clients = []
    try:
        # The serve job owns its spool; it appears once the weights are up.
        deadline = time.time() + 300
        while not (spool / "requests").is_dir():
            if job.poll() is not None or time.time() > deadline:
                raise SmokeFailure("the serve job's spool did not come up")
            time.sleep(0.5)
        for p, n in requests:
            clients.append(subprocess.Popen(
                CLI + ["serve-request", "--spool", str(spool), "--prompt-len", str(p),
                       "--max-new-tokens", str(n), "--timeout", "400"],
                cwd=ROOT, stdout=subprocess.PIPE, text=True,
            ))
        ttfts = []
        for (p, n), client in zip(requests, clients):
            out, _ = client.communicate(timeout=420)
            check(client.returncode == 0, f"serve-request prompt {p} returned a response")
            resp = json.loads(out.strip().splitlines()[-1])
            toks = resp["tokens"]
            check(
                len(toks) == n and all(0 <= t < size["vocab"] for t in toks),
                f"prompt {p}: {len(toks)} tokens == max_new_tokens {n}, all in the vocabulary",
            )
            check(resp["prompt_len"] == p, f"prompt {p}: prompt_len echoed")
            check(resp["ttft_ms"] > 0, f"prompt {p}: ttft_ms {resp['ttft_ms']} > 0")
            ttfts.append(resp["ttft_ms"])
    finally:
        for client in clients:
            if client.poll() is None:
                client.kill()
                client.wait()
        finish_job(job, name, 60)
    stats = json_line(name)
    check(stats["served"] == len(requests), f"served {stats['served']} == {len(requests)}")
    check(stats["rejected"] == 0, "rejected == 0")
    recs = device_records(name)
    check(len(recs) == 1, "one device record")
    check_device(recs[0], size, 1, 1)
    phase_done(
        title, t0, before,
        f", ttft_ms per request {ttfts} (compilation included), "
        f"peak_bytes_in_use {recs[0]['peak_bytes_in_use']}",
    )


def gang_smoke_dist(size: dict) -> None:
    n = size["gang"]
    name = "smoke-dist"
    title = f"{name}: {n} processes x 1 device"
    t0, before = phase(title)
    path = job_yaml(name, "pytorch_operator_tpu.workloads.smoke_dist", [],
                    size["resource"], 1, workers=n - 1)
    finish_job(run_job(path, 300), name, 300)
    recs = device_records(name)
    check(len(recs) == n, f"{len(recs)} device records == {n} replicas")
    for rec in recs:
        check_device(rec, size, n, 1)
    ids = [i for rec in recs for i in rec["local_device_ids"]]
    check(len(set(ids)) == n, f"local devices {ids} are {n} distinct devices")
    phase_done(title, t0, before)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny sizes on the CPU (tier-1 test); never prints the result line",
    )
    rehearsal = ap.parse_args().rehearse_cpu
    size = REHEARSAL if rehearsal else CHIP
    if not (ROOT / "pytorch_operator_tpu").is_dir():
        print("chip_smoke: the pytorch_operator_tpu package is not beside this script")
        return 2
    sys.path.insert(0, str(ROOT))  # the package's JAX-free helpers, from any cwd
    if rehearsal:
        print("REHEARSAL platform=cpu", flush=True)

    # The probe pins the platform, so no accelerator is an error here, in
    # seconds, before any model is built; its exit frees the chip.
    probe = subprocess.run(
        [sys.executable, "-c", PROBE], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": size["platform"]},
    )
    if probe.returncode != 0:
        print(probe.stderr[-2000:], file=sys.stderr)
        print(f"chip_smoke: probe found no {size['platform']} device")
        return 1
    dev = json.loads(probe.stdout.strip().splitlines()[-1])
    print(
        f"probe: platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
        f"libtpu={dev['libtpu']}",
        flush=True,
    )
    if dev["platform"] != size["platform"]:
        print(f"chip_smoke: probe landed on {dev['platform']}")
        return 1

    shutil.rmtree(OUT, ignore_errors=True)  # fresh state, never .tpujob/
    try:
        kernel_phase(size)
        train_phase(size, "smoke-train", size["layers"], chips=1)
        serve_phase(size)
        n = size["gang"]
        if rehearsal or dev["count"] >= n:
            train_phase(size, "smoke-fsdp", size["fsdp_layers"], chips=n,
                        checkpoint=False)
            gang_smoke_dist(size)
            train_phase(size, "smoke-gang", size["layers"], chips=1,
                        workers=n - 1, checkpoint=False)
        else:
            print(f"SKIPPED four-chip legs: {dev['count']} chip(s)", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}")
        return 1
    finally:
        # Gigabytes, and the output directory is copied back from the chip.
        shutil.rmtree(STATE / "checkpoints", ignore_errors=True)
    if "jax" in sys.modules:
        print("chip_smoke: FAILED: the parent imported JAX")
        return 1
    if rehearsal:
        print("REHEARSAL passed (no result line: this was not a chip)")
        return 0
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
