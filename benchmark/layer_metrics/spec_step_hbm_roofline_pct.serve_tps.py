"""spec_step_hbm_roofline_pct.serve_tps: the least bytes the traced window's VERIFYING decode steps had to read (the family's flops.spec_step_bytes_min: every weight of the main stack's layers, of the multi-token-prediction block and of the head's slice once; only the held experts the counters say got a token; each occupied row's live positions of the two slabs and the four rings) over the device time of decode_block there, over the published HBM bandwidth. Rows are row-steps over steps, NOT tokens over steps: a step yields one or two tokens a row and reads the same. Bound by bytes, not operations."""
from benchmark import family
from benchmark import metrics as M
from benchmark.scope_reduce import traced_decode_steps


def read(ctx):
    final, steps_traced = ctx.get("final", {}), traced_decode_steps(ctx)
    need = ("decode_steps", "decode_row_steps", "decode_live_positions", "decode_moe_experts_touched", "mtp_drafts")
    traces = [r.get("trace") or {} for r in ctx["reports"]]
    seconds = M.mean(t.get("program_s", {}).get("decode_block", 0.0) for t in traces)
    if not steps_traced or not seconds or not all(final.get(k) for k in need):
        return None  # no trace, or a program that does not draft
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if not hasattr(flops, "spec_step_bytes_min"):
        return None
    steps = final["decode_steps"]
    rows = final["decode_row_steps"] / steps
    live = final["decode_live_positions"] / final["decode_row_steps"]  # counted at every row-step, from the device's tallies
    touched = final["decode_moe_experts_touched"] / steps
    step_bytes = flops.spec_step_bytes_min(ctx["config"], slots=rows, mean_positions=live, experts_touched=touched)
    rate = step_bytes * steps_traced / seconds
    print(f"verifying step: at least {step_bytes / 1e9:.4f} GB ({rows:.2f} rows, {live:.1f} live positions a row-step, "
          f"{touched:.2f} experts touched); {steps_traced:g} steps in {seconds:.6f} s of decode_block in the traced window "
          f"= {rate / 1e9:.2f} GB/s, {1e3 * seconds / steps_traced:.3f} ms a step", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
