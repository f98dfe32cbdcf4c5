"""shared_kv_roofline_pct.serve_tps: the least bytes the traced window's decode steps had to read of the ONE full-attention slab (the family's flops.shared_kv_step_bytes_min: each occupied row's live positions, keys and values once, times the layers that attend it) over the device time of the attn_full and attn_cross scopes inside decode_block there, over the published HBM bandwidth. Bound by bytes, not operations; those scopes' projections (their weights are not counted) keep it under what the walk alone reaches."""
from benchmark import family
from benchmark import metrics as M
from benchmark.scope_reduce import traced_decode_steps
from benchmark.xdec_reduce import decode_scope_s


def read(ctx):
    final, seconds = ctx.get("final", {}), decode_scope_s(ctx, "attn_full", "attn_cross")
    steps_traced = traced_decode_steps(ctx) if seconds else None
    if not steps_traced or not all(final.get(k) for k in ("decode_steps", "decode_tokens", "decode_live_positions")):
        return None  # no trace, or a program without the scopes
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if not hasattr(flops, "shared_kv_step_bytes_min"):
        return None  # a family without a shared slab
    rows = final["decode_tokens"] / final["decode_steps"]
    live = final["decode_live_positions"] / final["decode_tokens"]
    step_bytes = flops.shared_kv_step_bytes_min(ctx["config"], slots=rows, mean_positions=live)
    rate = step_bytes * steps_traced / seconds
    print(f"the shared slab's part of a decode step: at least {step_bytes / 1e9:.4f} GB ({rows:.2f} rows, {live:.1f} live "
          f"positions a row, {final.get('cache_full_readers')} readers); {steps_traced:g} steps in {seconds:.6f} s of the "
          f"attn_full + attn_cross scopes inside decode_block = {rate / 1e9:.2f} GB/s", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
