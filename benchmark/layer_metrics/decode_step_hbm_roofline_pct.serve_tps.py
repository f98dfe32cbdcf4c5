"""decode_step_hbm_roofline_pct.serve_tps: the least bytes the traced window's decode steps had to move (the family's flops.decode_step_bytes_min(model, slots, mean_positions): every weight once with the tied embedding once as the head, the one slab's live positions once a reader, each ring's live positions, each row's scan state read and written) over the device time of decode_block there, over the published HBM bandwidth. What decode_hbm_roofline_pct.serve_tps is for the families with experts, whose reader returns None without expert counters. Bound by bytes, not operations."""
import inspect

from benchmark import family
from benchmark import metrics as M
from benchmark.scope_reduce import traced_decode_steps


def read(ctx):
    final, steps_traced = ctx.get("final", {}), traced_decode_steps(ctx)
    traces = [r.get("trace") or {} for r in ctx["reports"]]
    seconds = M.mean(t.get("program_s", {}).get("decode_block", 0.0) for t in traces)
    if not steps_traced or not seconds or not all(final.get(k) for k in ("decode_steps", "decode_tokens", "decode_live_positions")):
        return None  # no trace, or a program without these counters
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if "experts_touched" in inspect.signature(flops.decode_step_bytes_min).parameters:
        return None  # a family with experts: decode_hbm_roofline_pct.serve_tps reads it
    rows = final["decode_tokens"] / final["decode_steps"]
    live = final["decode_live_positions"] / final["decode_tokens"]
    step_bytes = flops.decode_step_bytes_min(ctx["config"], slots=rows, mean_positions=live)
    rate = step_bytes * steps_traced / seconds
    print(f"decode step: at least {step_bytes / 1e9:.4f} GB ({rows:.2f} rows, {live:.1f} live positions a row); "
          f"{steps_traced:g} steps in {seconds:.6f} s of decode_block in the traced window = {rate / 1e9:.2f} GB/s, "
          f"{1e3 * seconds / steps_traced:.3f} ms a step", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
