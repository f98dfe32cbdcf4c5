"""decode_hbm_roofline_pct.serve_tps: the least bytes the traced window's decode steps had to read (the family's flops.decode_step_bytes_min: every weight of attention, the dense layer, the routers and the head; only the experts the counters say got a token; only the live positions of each cache kind) over the device time of decode_block there, over the published HBM bandwidth. Bound by bytes, not operations."""
from benchmark import family
from benchmark import metrics as M
from benchmark.scope_reduce import traced_decode_steps


def read(ctx):
    final, steps_traced = ctx.get("final", {}), traced_decode_steps(ctx)
    need = ("decode_steps", "decode_tokens", "decode_live_positions", "decode_moe_experts_touched")
    traces = [r.get("trace") or {} for r in ctx["reports"]]
    seconds = M.mean(t.get("program_s", {}).get("decode_block", 0.0) for t in traces)
    if not steps_traced or not seconds or not all(final.get(k) for k in need):
        return None  # no trace, or a program without these counters
    steps = final["decode_steps"]
    rows = final["decode_tokens"] / steps
    live = final["decode_live_positions"] / final["decode_tokens"]
    touched = final["decode_moe_experts_touched"] / steps
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    step_bytes = flops.decode_step_bytes_min(ctx["config"], slots=rows, mean_positions=live, experts_touched=touched)
    rate = step_bytes * steps_traced / seconds
    print(f"decode step: at least {step_bytes / 1e9:.4f} GB ({rows:.2f} rows, {live:.1f} live positions a row, "
          f"{touched:.2f} experts touched); {steps_traced:g} steps in {seconds:.6f} s of decode_block in the traced "
          f"window = {rate / 1e9:.2f} GB/s", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
