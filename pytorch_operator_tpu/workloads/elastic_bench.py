"""Elastic benchmark: resize-in-place vs whole-world restart recovery.

The elastic tentpole's claim is quantitative: when a replica dies and
the survivors still satisfy ``min_replicas``, shrinking the world in
place (survivors adopt the resize record, re-rank, resume from the
verified checkpoint) must beat tearing the whole gang down and
respawning it. This bench pins that claim with real subprocess gangs.

Each cell runs one gang (1 Master + G Workers — ``--gangs`` counts the
WORKER replicas, the elastic dimension) of the jax-free
``exit_with`` step-loop workload (checkpoint every step, progress
heartbeat every step) under a real Supervisor, waits for steady
stepping, SIGKILLs the highest-index worker, and measures recovery
from the kill to the moment EVERY surviving (or respawned) member has
taken its first post-recovery step:

- ``resize``  — ``min_replicas=1``: the reconciler classifies the
  death as survivable, commits a resize record, and the survivors
  adopt it in place. Recovery is marked per-member by a
  ``resize_join`` status record.
- ``restart`` — ``min_replicas=G``: losing one worker falls below
  the floor, so the SAME death drives the whole-world restart path.
  Recovery is marked per-member by a fresh-incarnation
  ``first_step`` record.

Both modes use identical specs except the ``min_replicas`` floor, so
the delta is purely resize-vs-restart mechanics. Per cell the artifact
records recovery wall-clock, step loss (steps re-trained relative to
the pre-death frontier), the post-resize rank assignment (pinned
unique AND dense in [0, world)), and the count of post-kill cold
starts (pinned 0 for resize cells — shrink must not respawn anyone).

Emitted artifact (``--out``): per-cell numbers plus the
acceptance block — resize recovery strictly faster than restart
recovery for every gang size, and zero duplicate ranks ever observed.

Usage:
    python -m pytorch_operator_tpu.workloads.elastic_bench \
        [--gangs 2,4,8] [--pre-steps 5] [--step-time 0.02] \
        [--timeout 120] [--out elastic.json]
    tpujob bench-elastic ...
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional


def _daemon_pass(sup) -> None:
    # The tpujob-supervisor loop body, minus the sleep.
    sup.store.rescan()
    sup.process_deletion_markers()
    sup.process_scale_markers()
    sup.process_suspend_markers()
    sup.process_apply_markers()
    sup.sync_once()


def _pump(sup, pred, timeout: float, poll: float = 0.05):
    """Drive daemon passes until ``pred()`` returns truthy or timeout.
    Returns the predicate's value (None on timeout)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _daemon_pass(sup)
        got = pred()
        if got:
            return got
        time.sleep(poll)
    return None


def _records(sdir: Optional[Path]) -> Dict[str, List[dict]]:
    """Per-replica status records, file order preserved (the order the
    replica emitted them, which is what the marker scan relies on)."""
    out: Dict[str, List[dict]] = {}
    if sdir is None:
        return out
    try:
        files = sorted(sdir.glob("*.jsonl"))
    except OSError:
        return out
    for f in files:
        recs = []
        try:
            lines = f.read_text().splitlines()
        except OSError:
            continue
        for line in lines:
            try:
                recs.append(json.loads(line))
            except ValueError:
                continue
        out[f.name[: -len(".jsonl")]] = recs
    return out


def _warmed(sdir, members: List[str], pre_steps: int) -> bool:
    """Every member has reported at least ``pre_steps`` progress
    steps (so the pre-death frontier and checkpoints exist)."""
    recs = _records(sdir)
    for m in members:
        steps = [
            r.get("step", 0)
            for r in recs.get(m, [])
            if r.get("event") == "progress"
        ]
        if not steps or max(steps) < pre_steps:
            return False
    return True


def _first_recovery_step(recs: List[dict], t_kill: float):
    """The replica's first progress record AFTER its post-kill recovery
    marker (``resize_join`` = adopted the shrunk world in place;
    ``first_step`` = a fresh incarnation came up). Returns
    (ts, step, marker_event) or None while still recovering."""
    marker = None
    for r in recs:
        ev = r.get("event")
        ts = float(r.get("ts", 0.0))
        if marker is None:
            if ts > t_kill and ev in ("resize_join", "first_step"):
                marker = ev
        elif ev == "progress":
            return ts, int(r.get("step", 0)), marker
    return None


def _gang_recovered(sdir, members: List[str], t_kill: float):
    """None until EVERY expected member has stepped post-recovery;
    then ``{member: (ts, step, marker)}`` — the world is only back
    when its slowest member is back."""
    recs = _records(sdir)
    out = {}
    for m in members:
        got = _first_recovery_step(recs.get(m, []), t_kill)
        if got is None:
            return None
        out[m] = got
    return out


def _gang_job(name: str, workers: int, *, min_replicas: int,
              step_time: float):
    from ..api.types import (
        ElasticPolicy,
        ObjectMeta,
        ProcessTemplate,
        ReplicaSpec,
        ReplicaType,
        Resources,
        RestartPolicy,
        RunPolicy,
        TPUJob,
        TPUJobSpec,
    )

    def tmpl():
        return ProcessTemplate(
            module="pytorch_operator_tpu.workloads.exit_with",
            args=["--steps", "100000", "--step-time", str(step_time)],
            resources=Resources(cpu_devices=1),
        )

    return TPUJob(
        metadata=ObjectMeta(name=name),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.MASTER: ReplicaSpec(
                    replicas=1,
                    restart_policy=RestartPolicy.ON_FAILURE,
                    template=tmpl(),
                ),
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=workers,
                    restart_policy=RestartPolicy.ON_FAILURE,
                    template=tmpl(),
                ),
            },
            run_policy=RunPolicy(backoff_limit=8),
            elastic_policy=ElasticPolicy(min_replicas, workers, 8),
        ),
    )


def run_cell(gang: int, mode: str, *, pre_steps: float, step_time: float,
             timeout: float) -> dict:
    """One (gang size, mode) measurement in its own state dir."""
    from ..api.types import ReplicaType
    from ..controller import Supervisor
    from ..controller.progress import job_status_dir
    from ..controller.runner import replica_name

    workers = gang
    min_replicas = 1 if mode == "resize" else workers
    members = ["master-0"] + [f"worker-{i}" for i in range(workers)]
    victim_member = f"worker-{workers - 1}"
    survivors = [m for m in members if m != victim_member]
    expected = survivors if mode == "resize" else members

    with tempfile.TemporaryDirectory(
        prefix=f"elastic-bench-{gang}-{mode}-"
    ) as td:
        state = Path(td)
        sup = Supervisor(state_dir=state, poll_interval=0.05)
        key = None
        try:
            key = sup.submit(
                _gang_job(
                    f"bench-{mode}-{gang}",
                    workers,
                    min_replicas=min_replicas,
                    step_time=step_time,
                )
            )
            sdir = job_status_dir(state / "status", key)
            if not _pump(
                sup, lambda: _warmed(sdir, members, pre_steps), timeout
            ):
                raise RuntimeError(
                    f"gang={gang} mode={mode}: warm-up timed out"
                )

            pre = _records(sdir)
            pre_max = max(
                r.get("step", 0)
                for recs in pre.values()
                for r in recs
                if r.get("event") == "progress"
            )
            victim = replica_name(key, ReplicaType.WORKER, workers - 1)
            t_kill = time.time()
            sup.runner.inject_kill(victim)

            got = _pump(
                sup, lambda: _gang_recovered(sdir, expected, t_kill), timeout
            )
            if got is None:
                raise RuntimeError(
                    f"gang={gang} mode={mode}: recovery timed out"
                )
            recovery_s = max(ts for ts, _, _ in got.values()) - t_kill
            resume_step = min(step for _, step, _ in got.values())
            cold_starts = sum(
                1 for _, _, marker in got.values() if marker == "first_step"
            )

            # Post-resize rank audit from the adopters' own reports:
            # the newest generation's ranks must be unique and dense.
            ranks = None
            ranks_ok = None
            if mode == "resize":
                joins = [
                    r
                    for m in expected
                    for r in _records(sdir).get(m, [])
                    if r.get("event") == "resize_join"
                    and float(r.get("ts", 0.0)) > t_kill
                ]
                if joins:
                    top = max(int(j.get("generation", 0)) for j in joins)
                    newest = [
                        j for j in joins
                        if int(j.get("generation", 0)) == top
                    ]
                    ranks = sorted(int(j.get("rank", -1)) for j in newest)
                    worlds = {int(j.get("world_size", 0)) for j in newest}
                    ranks_ok = (
                        len(worlds) == 1
                        and ranks == list(range(worlds.pop()))
                    )
                else:
                    ranks_ok = False

            return {
                "gang": gang,
                "mode": mode,
                "recovery_s": round(recovery_s, 4),
                "pre_max_step": int(pre_max),
                "resume_step": int(resume_step),
                "step_loss": max(0, int(pre_max) - int(resume_step) + 1),
                "post_kill_cold_starts": cold_starts,
                "ranks": ranks,
                "ranks_unique_dense": ranks_ok,
            }
        finally:
            if key is not None:
                try:
                    sup.delete_job(key, purge_artifacts=True)
                except Exception:
                    # invariant: waived — bench teardown under a tmpdir; the artifact JSON already captured the result
                    pass
            sup.shutdown()


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="resize-in-place vs whole-world-restart recovery bench"
    )
    p.add_argument("--gangs", default="2,4,8",
                   help="comma-separated WORKER replica counts per gang "
                        "(each gang also has one master)")
    p.add_argument("--pre-steps", type=int, default=5,
                   help="steps every member must reach before the kill")
    p.add_argument("--step-time", type=float, default=0.02,
                   help="per-step sleep of the workload (s)")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="per-phase (warm-up / recovery) timeout (s)")
    p.add_argument("--out", default=None,
                   help="write the JSON artifact here")
    args = p.parse_args(argv)

    gangs = [int(g) for g in args.gangs.split(",") if g.strip()]
    cells = []
    for gang in gangs:
        if gang < 2:
            raise SystemExit(
                "--gangs entries must be >= 2 (a 1-worker gang has no "
                "survivable worker death — shrinking needs a survivor)"
            )
        for mode in ("resize", "restart"):
            t0 = time.monotonic()
            cell = run_cell(
                gang,
                mode,
                pre_steps=args.pre_steps,
                step_time=args.step_time,
                timeout=args.timeout,
            )
            cell["cell_wall_s"] = round(time.monotonic() - t0, 2)
            cells.append(cell)
            print(
                f"[elastic-bench] gang={gang} mode={mode}: "
                f"recovery={cell['recovery_s']:.3f}s "
                f"step_loss={cell['step_loss']} "
                f"cold_starts={cell['post_kill_cold_starts']}",
                flush=True,
            )

    by = {(c["gang"], c["mode"]): c for c in cells}
    resize_faster = all(
        by[(g, "resize")]["recovery_s"] < by[(g, "restart")]["recovery_s"]
        for g in gangs
    )
    no_dup_ranks = all(
        c["ranks_unique_dense"] is not False for c in cells
    )
    shrink_never_respawns = all(
        c["post_kill_cold_starts"] == 0
        for c in cells
        if c["mode"] == "resize"
    )
    out = {
        "bench": "elastic",
        "config": {
            "gangs": gangs,
            "pre_steps": args.pre_steps,
            "step_time": args.step_time,
        },
        "cells": cells,
        "acceptance": {
            "resize_faster_every_cell": resize_faster,
            "zero_duplicate_ranks": no_dup_ranks,
            "shrink_never_respawns": shrink_never_respawns,
        },
    }
    text = json.dumps(out, indent=2, sort_keys=True)
    if args.out:
        Path(args.out).write_text(text + "\n")
        print(f"[elastic-bench] wrote {args.out}")
    else:
        print(text)
    ok = resize_faster and no_dup_ranks and shrink_never_respawns
    print(f"[elastic-bench] acceptance: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
