#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Drives the cell through the system's normal path — a job file, ``tpujob
run``, the supervisor, the runner, a replica per chip — with the cell's
model, traffic and seed, measures for S seconds after everything is warm,
checks what the timed path produced against the plain reference, and
prints one JSON object as the last line of its standard output.

This process never imports JAX: the replica holds the chip. Everything
that belongs to one configuration, one traffic mix or one per-layer metric
is a file found by its name in BENCHMARK.json (``configs/``, ``traffic/``,
``limits/``, ``layer_metrics/``), and what knows the shape of a model's block
by the configuration file's ``bench.family`` (``families/<family>/``, see
``family.py``); adding a cell, of a new family too, adds files, not code.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import metrics as M  # noqa: E402
from benchmark import traffic as T  # noqa: E402

DRAIN_S = 45.0        # after the window: answers still owed are waited for this long
STARTUP_S = 1100.0    # a first run compiles
TRACE_S = 4.0         # seconds of the window a traced run records
CHECK_SAMPLE = 4      # finished requests the reference follows, the longest among them
POLL_S = 0.02         # the load generator looks for its answers this often
STALL_S = 0.05        # a sleep of the generator that overruns by this much: the machine stood still
PROGRAM_SEED_MOD = 2147483629  # the program's seeds are signed 32-bit


class BenchFailure(Exception):
    """The run cannot give a result; exit non-zero and print none."""


class SupplyRanOut(BenchFailure):
    """A closed loop asked for more requests than its traffic file holds: the
    benchmark's fault, not the replica's. A window that was not fed to its end
    has no honest rate, so this too is a run without a result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---- the cell's files ----


def load_cell(workload: str, bench: Path = BENCH) -> dict:
    manifest = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config_path = bench.parent / cfg_entry["file"]
    limits_path = bench / "limits" / f"{workload}.json"
    return {
        "manifest": manifest,
        "cell": cell,
        "config_path": config_path,
        "config": json.loads(config_path.read_text()),
        "traffic": T.load(cell["traffic"], bench / "traffic"),
        "limits": json.loads(limits_path.read_text()) if limits_path.is_file() else {},
    }


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


# ---- the job ----


def write_job(state: Path, name: str, module: str, args: list, resource: str,
              chips: int, env: dict) -> Path:
    """One replica per chip: a Master and ``chips - 1`` Workers of one chip
    each, which the supervisor admits as a gang."""
    template = {"module": module, "args": [str(a) for a in args],
                "resources": {resource: 1}, "env": env}
    specs = {"Master": {"replicas": 1, "template": template}}
    if chips > 1:
        specs["Worker"] = {"replicas": chips - 1, "template": template}
    job = {
        "api_version": "tpujob.dev/v1", "kind": "TPUJob", "metadata": {"name": name},
        # A failure fails the job at once: a second life must not pass.
        "spec": {"replica_specs": specs, "run_policy": {"backoff_limit": 0}},
    }
    path = state / "job.yaml"
    path.write_text(json.dumps(job))  # JSON is YAML
    return path


def start_job(state: Path, job: Path, env: dict) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-m", "pytorch_operator_tpu.client.cli", "--state-dir",
         str(state / "tpujob"), "run", str(job), "--timeout", str(STARTUP_S + 600)],
        cwd=ROOT, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def stop_job(proc: subprocess.Popen, state: Path) -> None:
    """End ``tpujob run`` and everything under it, and wait for each."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    for rec in (state / "tpujob" / "replicas").glob("*.json"):
        try:
            os.killpg(json.loads(rec.read_text())["pid"], signal.SIGKILL)
        except (OSError, ValueError, KeyError, TypeError):
            pass  # already gone


def status_records(state: Path, name: str) -> list:
    recs = []
    for f in sorted((state / "tpujob" / "status" / f"default_{name}").glob("*.jsonl")):
        for line in f.read_text().splitlines():
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return recs


def log_tails(state: Path) -> str:
    out = []
    for log in sorted((state / "tpujob" / "logs").glob("*.log")):
        out.append(f"---- {log.name}\n{log.read_text(errors='replace')[-2500:]}")
    return "\n".join(out)


# ---- serving: the load generator ----


def drive_serve(spool: Path, job: subprocess.Popen, mix: dict, schedule: list,
                seconds: float, warm: dict) -> dict:
    """Offer the schedule to the spool and collect the answers. One thread:
    it sleeps to the next due time, writes the request whole (temporary
    name, then rename), and in between looks, by name, for the answers it
    waits for. Not a scan of ``responses/``: the directory grows to some
    hundreds of files while the server renames into it, and scanning it every
    5 ms cost the saturated cell 0.3% of its rate and the harness half of its
    CPU time (PERF.md, PR 26)."""
    requests_dir, responses_dir = spool / "requests", spool / "responses"
    deadline = time.time() + STARTUP_S
    while not requests_dir.is_dir():
        if job.poll() is not None or time.time() > deadline:
            raise BenchFailure("the serving job's spool did not come up")
        time.sleep(0.05)
    answers: dict = {}
    seen_at: dict = {}
    waiting: set = set()
    stalls: list = []

    def nap(limit: float) -> None:
        """Sleep, and keep by how much the sleep overran where that is
        ``STALL_S`` or more: a process that only sleeps sees every pause of
        the whole machine, and the server stands still in the same pauses
        (PERF.md, PR 26)."""
        t = time.time()
        time.sleep(limit)
        over = time.time() - t - limit
        if over >= STALL_S:
            stalls.append(over)

    def send(rec: dict, submit_time: float) -> None:
        body = {"id": rec["id"], "prompt": rec["prompt"], "prompt_len": None,
                "max_new_tokens": rec["max_new_tokens"], "submit_time": submit_time}
        tmp = requests_dir / f".{rec['id']}.tmp"
        tmp.write_text(json.dumps(body))
        os.rename(tmp, requests_dir / f"{rec['id']}.json")
        rec["submit_time"] = submit_time
        waiting.add(rec["id"])

    def collect() -> None:
        for rid in list(waiting):
            try:
                text = (responses_dir / f"{rid}.json").read_text()  # the server renames it into place whole
            except FileNotFoundError:
                continue
            answers[rid] = json.loads(text)
            seen_at[rid] = time.time()
            waiting.discard(rid)

    def wait_for(ids, until: float) -> None:
        while time.time() < until and not all(i in answers for i in ids):
            if job.poll() is not None:
                raise BenchFailure("the serving job ended before its answers were read")
            collect()
            time.sleep(POLL_S)

    # Warm-up: one request through both of the engine's programs.
    send(warm, time.time())
    wait_for([warm["id"]], time.time() + STARTUP_S)
    if "tokens" not in answers.get(warm["id"], {}):
        raise BenchFailure(f"warm-up request was not answered: {answers.get(warm['id'])}")

    t0 = time.time()
    end = t0 + seconds
    lateness = []
    sent = []
    if mix["loop"] == "open":
        for rec in schedule:
            due = t0 + rec["due"]
            while time.time() < due:
                collect()
                nap(min(POLL_S, max(0.0, due - time.time())))
            send(rec, due)
            lateness.append(time.time() - due)
            sent.append(rec)
    else:
        supply = iter(schedule)
        in_flight: set = set()
        while time.time() < end:
            while len(in_flight) < int(mix["clients"]):
                rec = next(supply, None)
                if rec is None:
                    raise SupplyRanOut(
                        f"closed loop: traffic file {mix.get('name')!r} holds a supply of {len(schedule)} requests and "
                        f"the last was drawn {time.time() - t0:.1f} s into a window of {seconds:g} s, with "
                        f"{sum(r['id'] in answers for r in sent)} answers read: the program outran the traffic file. "
                        "Raise its `supply` (to twice what a window sends, traffic.py) in a `benchmark` PR")
                send(rec, time.time())
                sent.append(rec)
                in_flight.add(rec["id"])
            collect()
            in_flight -= set(answers)
            nap(POLL_S)
    wait_for([r["id"] for r in sent], end + DRAIN_S)
    return {"t0": t0, "end": end, "sent": sent, "answers": answers, "seen_at": seen_at,
            "lateness": lateness, "stalls": stalls}


def judge_answers(load: dict) -> dict:
    """A request is failed unless it was answered in full, without error,
    by the drain limit."""
    good, failed = [], []
    for rec in load["sent"]:
        ans = load["answers"].get(rec["id"])
        ok = (ans is not None and "error" not in ans and ans.get("prompt_len") == rec["prompt_len"]
              and len(ans.get("tokens", [])) == rec["max_new_tokens"])
        (good if ok else failed).append(rec["id"])
    return {"good": good, "failed": failed}


# ---- the check ----


def run_reference(state: Path, env: dict, bench: Path, control: bool = False) -> dict:
    """The plain reference, in a process of its own once the program's
    replicas have ended (the chip is free, and ``memory_peak_bytes`` stays
    the program's)."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.reference_run", str(state / "check_in.json"),
         str(state / "check_out.json"), "--bench", str(bench), *(["--control"] if control else [])],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise BenchFailure(f"the reference failed:\n{proc.stderr[-3000:]}")
    return json.loads((state / "check_out.json").read_text())


def compare(numbers: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; all must hold."""
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise BenchFailure(f"no limit for compared number {name!r} in the cell's limits file")
        limit = limits[name]["limit"]
        held = value is not None and value <= limit
        say(f"compared {name} = {value} limit {limit} {'ok' if held else 'NOT CORRECT'}")
        ok = ok and held
    return ok


def pick_sample(good: list, sent: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    import random

    by_id = {r["id"]: r for r in sent}
    done = [by_id[i] for i in good]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + r["max_new_tokens"])
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[: n - 1]


# ---- one run ----


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Path = BENCH, platform: str = "tpu", module: str | None = None,
             control: bool = False) -> dict:
    """Everything after the arguments. ``platform='cpu'`` and ``module``
    exist for the benchmark's own tests, which drive a tiny cell's whole
    run without a chip, and ``control`` for the tools that read the limits
    (the reference also computes in the next precision down); the command
    line cannot reach them."""
    t_start = time.time()
    if not (ROOT / "pytorch_operator_tpu").is_dir():
        raise BenchFailure("the system under test (pytorch_operator_tpu/) is not in this checkout")
    spec = load_cell(workload, bench)
    cell, config, mix = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])
    role = config["bench"]["role"]
    state = ROOT / ".benchrun" / workload
    shutil.rmtree(state, ignore_errors=True)
    state.mkdir(parents=True)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".xla_cache"))
    env.pop("BENCH_RUN", None)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    pseed = seed % PROGRAM_SEED_MOD
    trace_s = min(TRACE_S, seconds / 2) if trace else 0.0
    name = "bench"
    args = ["--bench-config", spec["config_path"], "--bench-dir", bench, "--bench-state", state,
            "--bench-seconds", seconds, "--bench-trace-s", trace_s, *config["bench"]["args"]]
    load = None
    if role == "serve":
        schedule = T.schedule(mix, seed, seconds, int(config["vocab_size"]))
        args += ["--spool", state / "spool", "--seed", pseed, "--idle-timeout", 4,
                 "--report-every", 1000, "--json"]
    else:
        per_chip = int(mix["batch_per_chip"])
        args += ["--seq-len", mix["seq_len"], "--batch-size", per_chip * chips,
                 "--prefetch", mix.get("prefetch", 0), "--json"]
        if chips > 1:
            args += ["--mesh", f"fsdp={chips}"]
    job = write_job(state, name, module or config["bench"]["module"], args,
                    "tpu_chips" if platform == "tpu" else "cpu_devices", chips,
                    {"TPUJOB_SEED": str(pseed)})
    t_submit = time.time()
    proc = start_job(state, job, env)
    try:
        if role == "serve":
            engine = config["bench"]["engine"]
            warm = {"id": "w0", "prompt_len": 2 * engine["chunk"] + 7,
                    "max_new_tokens": engine["block"] + 6}
            warm["prompt"] = T.prompt_tokens(seed, 10**6, warm["prompt_len"], int(config["vocab_size"]))
            load = drive_serve(state / "spool", proc, mix, schedule, seconds, warm)
            t_window = load["t0"]
        try:
            proc.wait(timeout=STARTUP_S + seconds if role == "train" else 240)
        except subprocess.TimeoutExpired:
            raise BenchFailure("the job did not end") from None
    except SupplyRanOut:
        raise  # the traffic file's fault: the replica's log has nothing to say about it
    except BenchFailure as e:
        raise BenchFailure(f"{e} (no accelerator, or the replica failed):\n{log_tails(state)}") from None
    finally:
        stop_job(proc, state)

    records = status_records(state, name)
    devices = [r for r in records if r.get("event") == "device"]
    if not devices:
        raise BenchFailure(f"the job reported no device (no accelerator, or it failed):\n{log_tails(state)}")
    dev = devices[-1]
    if platform == "tpu" and dev["platform"] == "cpu":
        raise BenchFailure("the replica computed on the CPU: no accelerator")
    if dev["device_count"] < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX found {dev['device_count']}")
    reports = [json.loads(p.read_text()) for p in sorted(state.glob("replica-*.json"))]
    if len(reports) != chips:
        raise BenchFailure(f"{len(reports)} of {chips} replicas reported:\n{log_tails(state)}")
    replicas = [json.loads(p.read_text()) for p in sorted((state / "tpujob" / "replicas").glob("*.json"))]
    finals = [r for r in records if r.get("event") == "metrics"]
    ctx = {
        "cell": cell, "config": config, "traffic": mix, "bench": bench, "chips": chips, "seconds": seconds,
        "device": dev, "records": records, "reports": reports, "replicas": replicas, "t_submit": t_submit,
        "final": finals[-1] if finals else {},
    }

    # ---- end-to-end numbers, and what the check compares ----
    e2e: dict = {}
    if role == "serve":
        verdict = judge_answers(load)
        submitted = {r["id"]: r["submit_time"] for r in load["sent"]}
        good = [{**load["answers"][i], "submit_time": submitted[i]} for i in verdict["good"]]
        ctx.update(answers=good, load=load)
        attempted, failed = len(load["sent"]), len(verdict["failed"])
        if load["lateness"]:
            say(f"generator lateness ms: mean {1e3 * M.mean(load['lateness']):.3f} max {1e3 * max(load['lateness']):.3f}, "
                f"over 10 ms {sum(x > 0.01 for x in load['lateness'])} of {len(load['lateness'])} requests")
        # A window in which the whole machine stood still reads as a slow server.
        say(f"generator sleeps that overran by {1e3 * STALL_S:g} ms or more: {len(load['stalls'])}, "
            f"{sum(load['stalls']):.3f} s in all, the longest {max(load['stalls'], default=0.0):.3f} s")
        if mix["loop"] == "closed":  # how near the window came to the traffic file's end (traffic.py: the rule)
            say(f"generator sent {attempted} of the traffic file's supply of {len(schedule)} requests: "
                f"{100.0 * attempted / len(schedule):.1f}%")
        ttft = [a["ttft_ms"] for a in good]
        e2e["ttft_mean_ms"] = M.mean(ttft)
        say(f"time to first token ms over {len(ttft)} requests: mean {e2e['ttft_mean_ms']} "
            f"p50 {M.percentile(ttft, 50)} p90 {M.percentile(ttft, 90)}")
        e2e["tpot_p50_ms"] = M.percentile([a["tpot_ms"] for a in good if a["tpot_ms"] is not None], 50)
        # Work done inside the window, credited from the engine's record of
        # each request; whole answers seen by its end go on an earlier line
        # (that count moves in steps of one admission round).
        e2e["serve_tokens_per_s"] = M.tokens_per_s(M.tokens_processed(good, load["t0"], load["end"]), seconds)
        in_window = [a for a in good if load["seen_at"][a["id"]] <= load["end"]]
        say(f"answers seen whole inside the window: {len(in_window)} of {attempted}, "
            f"{sum(a['prompt_len'] + len(a['tokens']) for a in in_window)} tokens")
        sample = pick_sample(verdict["good"], load["sent"], seed, CHECK_SAMPLE)
        check_in = {"kind": "serve", "config": config, "seed": pseed, "pad_to": mix["check_pad_to"],
                    "width": max(a for _, a in mix["lengths"]),
                    "requests": [{"prompt": r["prompt"], "tokens": load["answers"][r["id"]]["tokens"]}
                                 for r in sample]}
    else:
        program = json.loads((state / "check_program.json").read_text())
        t_window = program["window_start"]
        attempted = int(program["window_steps"])
        losses_finite = all(map(_finite, program["losses"] + [ctx["final"].get("final_loss")]))
        failed = 0 if losses_finite else attempted
        e2e["train_tokens_per_s_chip"] = ctx["final"]["tokens_per_sec"] / chips
        ctx["program_check"] = program
        check_in = {"kind": "train", "config": config, "seed": pseed, "steps": len(program["losses"]),
                    "batch": int(mix["batch_per_chip"]) * chips, "seq_len": int(mix["seq_len"]),
                    "lr": config["bench"]["lr"]}
    e2e["setup_s"] = t_window - t_start
    ctx["e2e"] = e2e

    (state / "check_in.json").write_text(json.dumps(check_in))
    ref = run_reference(state, env, bench, control)
    numbers = check_numbers(role, ref, ctx)
    if control:
        say("control " + json.dumps(control_numbers(role, ref)))
    limits = spec["limits"].get("limits", {})
    correct = compare(numbers, limits) and failed == 0
    compared = {name: {"value": value, "limit": limits[name]["limit"]} for name, value in numbers.items()}
    if role == "serve":
        say(f"compared requests answered in full = {attempted - failed} of {attempted} limit {attempted} "
            f"{'ok' if failed == 0 else 'NOT CORRECT'}")
        compared["requests_answered_in_full"] = {"value": attempted - failed, "limit": attempted}

    # ---- the result line ----
    group = "per_layer" if trace else "end_to_end"
    out_metrics = {}
    for m in metrics_of(spec["manifest"], group, workload):
        value = read_layer_metric(m["name"], ctx, bench) if trace else e2e.get(m["name"])
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # Two moments the allocator saw on the fullest chip, and the larger: its
    # peak of live arrays, and the arrays live at the run's end beside the
    # most it ever set aside for a running program (this libtpu keeps a
    # program's temporaries out of ``peak_bytes_in_use``; PERF.md section 4).
    live, running = (max(r["peak_bytes_in_use"] for r in reports),
                     max(r["bytes_in_use"] + r["peak_bytes_reserved"] for r in reports))
    say(f"allocator: peak of live arrays {live} bytes; live at the end + peak reserved for programs {running} bytes")
    device = {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["device_count"],
              "memory_peak_bytes": max(live, running)}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if trace:
        traces = [r.get("trace") or {} for r in reports]
        if not all(t.get("busy_s") for t in traces):
            raise BenchFailure("the traced run recorded no operation on the device")
        device["busy_s"] = M.mean(t["busy_s"] for t in traces)
        device["window_s"] = M.mean(t["window_s"] for t in traces)
        say(f"collective operations s per chip in the traced window: {M.mean(t['collective_s'] for t in traces)}")
        result["breakdown"] = {"device_ops": traces[0]["device_ops"], "idle_gaps": traces[0]["idle_gaps"]}
    result["compared"] = compared  # last on the line: what a record of a run that is not correct keeps
    return result


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and x == x and abs(x) != float("inf")


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """Over the leaves, |program's norm - reference's norm| against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    median = M.percentile(list(reference.values()), 50)
    return max(abs(program[k] - v) / max(v, median) for k, v in reference.items())


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def train_numbers(got: dict, ref: dict) -> dict:
    """What a training cell compares: each step's loss gap, and the worst
    leaf's gap of the first gradient's norm and of the parameters' change."""
    numbers = {f"loss_gap_step{i + 1}": abs(g - r) for i, (g, r) in enumerate(zip(got["losses"], ref["losses"]))}
    for name in ("grad_norm", "delta_norm"):
        numbers[f"{name}_gap_worst_leaf"] = worst_leaf_gap(flatten(got[name]), flatten(ref[name]))
    return numbers


def check_numbers(role: str, ref: dict, ctx: dict) -> dict:
    if role == "serve":
        say(f"reference over {ref['requests']} requests, {ref['positions']} served tokens: "
            f"{ref['agree']} are the reference's own first choice; reference took {ref['seconds']:.1f}s")
        return {"served_logit_gap_max": ref["gap_max"]}
    program = ctx["program_check"]
    say(f"reference followed {len(ref['losses'])} steps in {ref['seconds']:.1f}s; "
        f"losses program {program['losses']} reference {ref['losses']}")
    return train_numbers(program, ref)


def control_numbers(role: str, ref: dict) -> dict:
    """The same numbers with the lower-precision reference in the program's
    place (serving: the gap of the token that it puts first)."""
    if role == "serve":
        return {"served_logit_gap_max": ref["control_gap_max"], "sound": ref["gap_max"]}
    return train_numbers(ref["control"], ref)


def read_layer_metric(name: str, ctx: dict, bench: Path = BENCH):
    """A per-layer metric is a small reader of its own,
    ``layer_metrics/<name>.py`` with ``read(ctx)``; one that finds nothing
    to read returns None and the metric is left out of the line."""
    path = bench / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"per-layer metric {name!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except BenchFailure as e:
        print(f"benchmark: no result: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark: the harness imported JAX", file=sys.stderr)
        return 1
    # Each number compared beside its limit: the last lines of standard error, and last in the result's line.
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
