"""Readers that several per-layer metric files share. A reader takes the
run's context (answers, status records, replica reports with the reduced
trace, the cell's files) and returns a number, or None where it finds
nothing to read; the harness then leaves the metric out of the line."""

from __future__ import annotations

from benchmark import family
from benchmark import metrics as M


def _traces(ctx):
    traces = [r.get("trace") for r in ctx["reports"]]
    return traces if traces and all(t and t.get("busy_s") for t in traces) else None


def device_idle_pct(ctx):
    traces = _traces(ctx)
    if not traces:
        return None
    return 100.0 * (1.0 - M.mean(t["busy_s"] for t in traces) / M.mean(t["window_s"] for t in traces))


def program_share_pct(program):
    def read(ctx):
        traces = _traces(ctx)
        if not traces:
            return None
        return 100.0 * M.mean(t["program_s"].get(program, 0.0) for t in traces) / M.mean(
            t["busy_s"] for t in traces)
    return read


def program_run_ms_p50(program):
    def read(ctx):
        traces = _traces(ctx)
        runs = [x for t in traces or [] for x in t["program_run_s"].get(program, [])]
        return 1e3 * M.percentile(runs, 50) if runs else None
    return read


def answers_stat(field, stat):
    def read(ctx):
        xs = [a[field] for a in ctx.get("answers", []) if a.get(field) is not None]
        if not xs:
            return None
        print(f"{field} {stat}: over {len(xs)} requests", flush=True)
        return M.mean(xs) if stat == "mean" else M.percentile(xs, float(stat))
    return read


def generator_late_ms(ctx):
    """How late the benchmark's own load generator sent, mean over the
    window's requests (sent - due, open loop): a stalled generator shows here,
    and is not read as a slow server."""
    late = (ctx.get("load") or {}).get("lateness")
    return 1e3 * M.mean(late) if late else None  # the maximum is on run.py's own "generator lateness" line


def generator_stall_ms_per_s(ctx):
    """Milliseconds by which the load generator's sleeps overran (each by
    ``run.STALL_S`` or more), per second of the window: pauses of the whole
    machine, in which the server stands still too. 0.0 where none did."""
    stalls = (ctx.get("load") or {}).get("stalls")
    if stalls is None or not ctx.get("seconds"):
        return None
    return 1e3 * sum(stalls) / ctx["seconds"]


def generator_supply_used_pct(ctx):
    """The share of a closed loop's supply (the traffic file's ``supply``) that
    the generator sent, window and all: at 100 the run fails
    (``run.SupplyRanOut``), and the file's rule keeps it at 50 or under when it
    is written (``traffic.schedule``). None for an open loop, which has none."""
    sent, supply = (ctx.get("load") or {}).get("sent"), (ctx.get("traffic") or {}).get("supply")
    if sent is None or not supply:
        return None
    return 100.0 * len(sent) / int(supply)


def engine_decode_tok_s(ctx):
    return ctx["final"].get("decode_tokens_per_sec")


def launch_s(ctx):
    """Submit -> the supervisor's record of the last replica spawned."""
    made = [r["created_at"] for r in ctx["replicas"] if r.get("created_at")]
    return max(made) - ctx["t_submit"] if made else None


def first_step_s(ctx):
    """Replica spawned -> its ``first_step`` record: import, backend,
    rendezvous, weights and, for the trainer, compile or cache hit and the
    first execution (the engine's programs load at the warm-up request)."""
    made = [r["created_at"] for r in ctx["replicas"] if r.get("created_at")]
    first = [r["ts"] for r in ctx["records"] if r.get("event") == "first_step"]
    return max(first) - max(made) if first and made else None


def alloc_peak_gb(ctx):
    return max(r["peak_bytes_in_use"] for r in ctx["reports"]) / 1e9


def train_mfu_pct(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s_chip")
    if rate is None:
        return None
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    return M.mfu_pct(flops.train_flops_per_token(ctx["config"], int(ctx["traffic"]["seq_len"])), rate,
                     ctx["device"]["device_kind"])
