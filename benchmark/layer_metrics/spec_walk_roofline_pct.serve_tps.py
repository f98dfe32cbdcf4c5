"""spec_walk_roofline_pct.serve_tps: the least bytes the traced window's verifying steps had to read of the two slabs they walk (the family's flops.walk_step_bytes_min: each occupied row's live positions of the full layer's slab and of the block's, keys and values once for the row's two queries) over the device time of the walk's kernel (cache_attention_decode) inside decode_block there, over the published HBM bandwidth. The kernel reads whole blocks of 512 positions, so rows a few hundred deep keep it under what the bytes allow."""
from benchmark import family
from benchmark import metrics as M
from benchmark.mtp_reduce import decode_walk_s
from benchmark.scope_reduce import traced_decode_steps


def read(ctx):
    final = ctx.get("final", {})
    seconds, events = decode_walk_s(ctx)
    steps_traced = traced_decode_steps(ctx) if seconds else None
    if not steps_traced or not all(final.get(k) for k in ("decode_steps", "decode_row_steps", "decode_live_positions", "mtp_drafts")):
        return None  # no trace, a program without the kernel in its decode step, or one that does not draft
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if not hasattr(flops, "walk_step_bytes_min"):
        return None
    rows = final["decode_row_steps"] / final["decode_steps"]
    live = final["decode_live_positions"] / final["decode_row_steps"]
    step_bytes = flops.walk_step_bytes_min(ctx["config"], slots=rows, mean_positions=live)
    rate = step_bytes * steps_traced / seconds
    attended = final.get("decode_attended_positions", 0) / final["decode_live_positions"]
    print(f"the walks of a verifying step: at least {step_bytes / 1e6:.2f} MB ({rows:.2f} rows, {live:.1f} live positions a "
          f"row-step; the kernel read {attended:.3f} x that in whole blocks); {steps_traced:g} steps, {events:g} kernel runs "
          f"in {seconds:.6f} s inside decode_block = {rate / 1e9:.2f} GB/s, {1e3 * seconds / steps_traced:.4f} ms a step",
          flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
