"""Persistent serving job: spool-fed continuous batching under the
supervisor.

Reference analog: SURVEY §1's spec -> supervisor -> workload chain —
the operator's long-running reconciled workload — applied to inference.
Where ``workloads/generate.py`` decodes ONE fixed batch and exits (the
benchmark shape), this runs indefinitely: clients drop requests into a
spool directory (serving/spool.py — this environment's Service
substrate), the engine (serving/engine.py) admits them into cache slots
between decode dispatches (it picks each one's length, so the loop below
polls, harvests and answers as often as that), finished requests free
their slot for the next arrival, and responses carry the per-request
latency record (TTFT, per-token). Progress/metrics flow through the same rendezvous surface
training workloads use, so ``tpujob describe`` shows a serving job's
live throughput exactly like a training job's.

The train -> checkpoint -> serve journey: point ``--restore`` at a
training job's checkpoint directory (params-only partial restore;
optimizer state never touches host memory).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys
import threading
import time

from .. import faults, obs
from ..obs.trace import serve_span
from ..runtime import rendezvous
from .trainer import maybe_profile


def run(
    *,
    config: str = "tiny",
    spool_dir: str,
    slots: int = 8,
    chunk: int = 64,
    block: int = 16,
    max_decode_len: int = 2048,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token: int | None = None,
    quantize: str | None = None,
    kv_quantize: str | None = None,
    init_host: bool = False,
    restore: str | None = None,
    max_requests: int = 0,
    idle_timeout: float = 0.0,
    poll_interval: float = 0.05,
    report_every: float = 5.0,
    transport: str = "spool",
    seed: int = 0,
    profile_dir: str | None = None,
    log=print,
) -> dict:
    """The serving loop. ``max_requests``/``idle_timeout`` bound the run
    for tests and benches; both 0 means serve forever (the production
    daemon shape — the supervisor owns the lifecycle).

    The loop's phases are spans (``serve.poll``, ``serve.submit``,
    ``serve.respond``, ``serve.idle`` around the engine's own) and laps
    of the engine's one clock (``ServingEngine.host_lap``). While the
    engine is busy, everything the loop does between two of its steps
    lies in one span, ``serve.boundary``: the loop's side of the stretch
    in which the device waits for the host, as one name; with
    ``profile_dir`` the whole loop runs under a ``jax.profiler`` trace,
    where those spans land beside the device's operations."""
    import jax
    import numpy as np

    from ..models.serving import preset
    from ..serving import Request, ServingEngine
    from ..serving.engine import SPAN_CAT
    from ..serving.shmring import EngineTransport
    from .generate import load_params

    # The preset states the model's family; everything a model decides
    # (its cache, its forwards, how its weights are made) comes with it.
    cfg = preset(
        config,
        decode=True,
        max_decode_len=max_decode_len,
        quantize=quantize,
        kv_quantize=kv_quantize,
    )
    # Every served family's decode step holds Pallas kernels (the write,
    # ops/cache_write.py; over a plain slab the walk too,
    # ops/cache_attention.py), whose library takes about a second of Python
    # to import: brought in on a thread beside the backend's start and the
    # weights, which wait on the device and the compile cache, not in front
    # of the first dispatch.
    threading.Thread(
        target=importlib.import_module, args=("jax.experimental.pallas.tpu",), daemon=True
    ).start()
    log(
        f"[serve] config={config} slots={slots} chunk={chunk} "
        f"block={block} L={max_decode_len} spool={spool_dir} "
        f"({jax.devices()[0].platform})"
    )
    params, _, n_params, weight_bytes, restored_step = load_params(
        cfg, config=config, restore=restore, quantize=quantize,
        init_host=init_host, seed=seed, log=log, tag="serve",
    )
    engine = ServingEngine(
        cfg, params, slots=slots, chunk=chunk, block=block,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token=eos_token, seed=seed,
    )
    # The transport wraps the durable file spool and — when the job's
    # ``spec.serving.transport`` is shmring — attaches the router's
    # shared-memory ring pair once it appears (serving/shmring.py).
    spool = EngineTransport(spool_dir, transport)
    recovered = spool.recover()
    if recovered:
        # A previous life of this job (the supervisor's restart policy)
        # died with claims in flight; they're requests again now.
        log(f"[serve] recovered {recovered} claimed request(s) from a "
            "previous life")
    rendezvous.report_first_step(0)

    served = 0
    rejected = 0
    last_activity = time.time()
    last_report = 0.0
    # The open ``serve.boundary``, if any: from one ``engine.step``'s
    # return to just before the next, across the loop's iterations.
    boundary = contextlib.ExitStack()

    def to_request(rec: dict) -> Request:
        if rec.get("prompt") is not None:
            prompt = np.asarray(rec["prompt"], np.int32)
        else:
            # Synthetic prompt of the requested length (no tokenizer in
            # this environment); deterministic per request id ACROSS
            # processes (crc32, not str hash — PYTHONHASHSEED randomizes
            # the latter, which would break claimed-request replay after
            # an engine restart).
            import zlib

            seed_ = zlib.crc32(rec["id"].encode())
            prompt = np.random.default_rng(seed_).integers(
                0, cfg.vocab_size, (int(rec["prompt_len"]),)
            ).astype(np.int32)
        return Request(
            id=rec["id"],
            prompt=prompt,
            max_new_tokens=int(rec["max_new_tokens"]),
            submit_time=float(rec["submit_time"]),
        )

    def finish(res) -> None:
        nonlocal served, last_activity
        t_resp = time.time()
        with obs.span("serve.respond", SPAN_CAT, rid=res.id):
            spool.respond(
                res.id,
                {
                    "id": res.id,
                    "tokens": res.tokens,
                    "prompt_len": res.prompt_len,
                    "ttft_ms": round(1000 * res.ttft_s, 3),
                    "admit_wait_ms": round(1000 * res.admit_wait_s, 3),
                    # ttft_ms in its three parts: waiting to be claimed
                    # from the spool, waiting for a slot, being prefilled.
                    "claim_wait_ms": round(1000 * res.claim_wait_s, 3),
                    "slot_wait_ms": round(1000 * res.slot_wait_s, 3),
                    "prefill_ms": round(1000 * res.prefill_s, 3),
                    "tpot_ms": (
                        round(1000 * res.tpot_s, 3)
                        if res.tpot_s is not None
                        else None
                    ),
                },
            )
        last_activity = time.time()
        # The request's last hop (slot_wait and decode are the engine's).
        serve_span("respond", t_resp, last_activity - t_resp, rid=res.id)
        served += 1

    def submit_polled(polled) -> None:
        nonlocal rejected, last_activity
        for rec in polled:
            try:
                engine.submit(to_request(rec))
                last_activity = time.time()
            except (ValueError, KeyError, TypeError) as e:
                rejected += 1
                rid = rec.get("id")
                if rid is None:
                    # Nobody to answer. The spool names a single-file
                    # request by its file (serving/spool.py), so only a
                    # foreign batch frame can come without an id.
                    log(f"[serve] dropped a request with no id: {e}")
                else:
                    spool.respond(rid, {"id": rid, "error": str(e)})

    def step_and_respond() -> None:
        nonlocal rejected
        boundary.close()
        fault = None
        try:
            results = engine.step()
        except faults.InjectedFault as e:
            fault, results = e, []
        boundary.enter_context(obs.span("serve.boundary", SPAN_CAT))
        if fault is not None:
            # Failure-path hardening: a faulted iteration must not
            # strand its in-flight requests (a client would block
            # its full timeout on a response nothing will write).
            # Abort the occupied slots and answer each with an
            # error — exactly-once responses, queued requests
            # untouched, the engine keeps serving.
            aborted = engine.abort_in_flight()
            for rid in aborted:
                spool.respond(rid, {"id": rid, "error": f"engine fault: {fault}"})
            rejected += len(aborted)
            log(
                f"[serve] engine step fault ({fault}); aborted "
                f"{len(aborted)} in-flight request(s) with error "
                "responses"
            )
        for res in results:
            finish(res)
        engine.host_lap("respond")

    def report() -> None:
        s = engine.stats()
        rendezvous.report_metrics(
            served,
            serve_requests=served,
            serve_pending=spool.pending_count(),
            serve_decode_tokens_per_sec=s["decode_tokens_per_sec"],
            serve_ttft_ms_p50=s["ttft_ms_p50"],
            serve_tpot_ms_p50=s["tpot_ms_p50"],
        )
        # Serve-plane load beat: the router's least-loaded dispatch
        # and the queue_growth/batch_size_collapse detectors read
        # this replica-side occupancy stream (serving/router.py).
        rendezvous.report_serve(
            served,
            slots=slots,
            slots_free=engine.slots_free,
            queued=engine.queued,
            pending=spool.pending_count(),
            ttft_ms_p50=s["ttft_ms_p50"],
            ttft_ms_p99=s["ttft_ms_p99"],
            tpot_ms_p50=s["tpot_ms_p50"],
            tpot_ms_p99=s["tpot_ms_p99"],
            # Decode-dispatch phase for the router's batch-fill
            # tie-break: a busy engine frees its next slot one
            # dispatch away, as long as the steps it last ran.
            block_ms=(
                (s["tpot_ms_p50"] or 0.0) * engine.last_steps
                if engine.busy
                else 0.0
            ),
        )
        # The LIVE operator surface (`tpujob describe` Training
        # block + per-job gauges) folds only progress records —
        # report through it like training workloads do, with
        # served requests as the step counter. (The beat also writes
        # the buffered spans out: rendezvous.report_progress.)
        rendezvous.report_progress(
            served,
            throughput=s["decode_tokens_per_sec"] or 0.0,
            unit="tok/s",
        )
        engine.host_lap("report")

    with maybe_profile(profile_dir, log), boundary:
        while True:
            # Admission feed: claim enough to keep the slots fed one
            # iteration ahead (ring tier first, then the file spool).
            with obs.span("serve.poll", SPAN_CAT):
                polled, _ = spool.poll_requests(2 * slots - engine.queued)
            # A poll that finds an idle engine nothing is part of being idle.
            engine.host_lap("poll" if polled or engine.busy else "idle")
            if polled:
                with obs.span("serve.submit", SPAN_CAT, n=len(polled)):
                    submit_polled(polled)
                engine.host_lap("submit")
            if engine.busy:
                step_and_respond()
            else:
                boundary.close()
                with obs.span("serve.idle", SPAN_CAT):
                    time.sleep(poll_interval)
                engine.host_lap("idle")
            now = time.time()
            if now - last_report > report_every:
                last_report = now
                report()
            if max_requests and served >= max_requests and not engine.busy:
                break
            if (
                idle_timeout
                and not engine.busy
                and now - last_activity > idle_timeout
            ):
                log(f"[serve] idle for {idle_timeout}s, exiting")
                break

    stats = engine.stats()
    stats.update(
        served=served,
        rejected=rejected,
        params_m=round(n_params / 1e6, 1),
        config=config,
        transport=transport,
        ring_recvs=spool.ring_recvs,
        ring_sends=spool.ring_sends,
        **rendezvous.report_device(),
    )
    spool.close()
    if weight_bytes is not None:
        stats["weight_mb"] = round(weight_bytes / 1e6, 2)
    if restored_step is not None:
        stats["restored_step"] = restored_step
    n_dev = jax.device_count()
    if stats["decode_tokens_per_sec"]:
        stats["decode_tokens_per_sec_per_chip"] = round(
            stats["decode_tokens_per_sec"] / n_dev, 1
        )
    rendezvous.report_metrics(served, **{
        k: v for k, v in stats.items()
        if isinstance(v, (int, float)) and v is not None
    })
    log(f"[serve] done: {json.dumps(stats)}")
    return stats


def main(argv=None) -> int:
    from ..models.serving import families

    import os

    p = argparse.ArgumentParser()
    p.add_argument("--config", choices=sorted(families()), default="tiny")
    p.add_argument(
        "--spool",
        default=os.environ.get("TPUJOB_SPOOL_DIR") or None,
        help="spool directory (requests/ claimed/ responses/) — the "
        "serving job's request surface; defaults to the "
        "supervisor-injected TPUJOB_SPOOL_DIR (spec.serving jobs get a "
        "private per-replica spool the router dispatches into)",
    )
    p.add_argument("--slots", type=int, default=8,
                   help="concurrent cache slots (the serving batch)")
    p.add_argument("--chunk", type=int, default=64,
                   help="prefill chunk length: what a short prompt pads to, "
                   "and what a window layer's ring allows for; a model that "
                   "takes a wider chunk runs a long prompt's body through "
                   "chunks of 512 (serving/engine.py:chunk_schedule)")
    p.add_argument("--block", type=int, default=16,
                   help="the most decode steps one dispatch may run; the "
                   "engine picks each dispatch's length (to the next slot "
                   "that frees, a short quantum while one is free) and "
                   "admits between dispatches")
    p.add_argument("--max-decode-len", type=int, default=2048)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos-token", type=int, default=None)
    p.add_argument("--quantize", choices=["int8"], default=None)
    p.add_argument("--kv-quantize", choices=["int8"], default=None)
    p.add_argument("--init-host", action="store_true")
    p.add_argument("--restore", default=None, metavar="CKPT_DIR")
    p.add_argument(
        "--max-requests", type=int, default=0,
        help="exit after serving N requests (0 = serve forever)",
    )
    p.add_argument(
        "--idle-timeout", type=float, default=0.0,
        help="exit after this many idle seconds (0 = serve forever)",
    )
    p.add_argument(
        "--report-every", type=float, default=5.0,
        help="seconds between progress/metrics reports to the "
        "supervisor surface",
    )
    p.add_argument(
        "--transport",
        choices=("spool", "shmring"),
        default=os.environ.get("TPUJOB_SERVE_TRANSPORT") or "spool",
        help="router transport tier; defaults to the supervisor-"
        "injected TPUJOB_SERVE_TRANSPORT (spec.serving.transport)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the serving loop here (the "
        "loop's and the engine's spans land in it beside the device's "
        "operations; read it with python -m pytorch_operator_tpu.profiling)",
    )
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    if not args.spool:
        p.error(
            "--spool is required (no TPUJOB_SPOOL_DIR in the environment)"
        )

    world = rendezvous.initialize_from_env()
    stats = run(
        config=args.config,
        spool_dir=args.spool,
        slots=args.slots,
        chunk=args.chunk,
        block=args.block,
        max_decode_len=args.max_decode_len,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        eos_token=args.eos_token,
        quantize=args.quantize,
        kv_quantize=args.kv_quantize,
        init_host=args.init_host,
        restore=args.restore,
        max_requests=args.max_requests,
        idle_timeout=args.idle_timeout,
        report_every=args.report_every,
        transport=args.transport,
        seed=args.seed,
        profile_dir=args.profile_dir,
        log=lambda msg: print(msg, flush=True),
    )
    if args.json and world.process_id == 0:
        print(json.dumps(stats), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
