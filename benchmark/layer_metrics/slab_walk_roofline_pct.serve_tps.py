"""slab_walk_roofline_pct.serve_tps: the least bytes the traced window's decode steps had to read of the slabs they walk (the family's flops.walk_step_bytes_min: each occupied row's live positions of each attention layer's slab, keys and values once) over the device time of the walk's kernel (cache_attention_decode) inside decode_block there (prefill_reduce), over the published HBM bandwidth: the kernel's share of its roofline at depth. Rows a step and live positions a row are the TRACED WINDOW'S OWN: the means over the window's paired dispatches (dispatch_reduce: each engine.decode_fence span's rows, steps and live; a row that takes every step of its dispatch has one more position live at each), times the window's steps on the device (scope_reduce's count: a run the pairing left out is counted at the paired runs' means, as prefill_mfu_pct counts a chunk run at the spans' mean n_real); the whole run's means, which the drain and 50 s of other waves move, are printed beside them. The kernel reads whole blocks, and a row that holds no request or is part-way through its prompt one block a step. What spec_walk_roofline_pct.serve_tps is for a model that drafts."""
from benchmark import dispatch_reduce, family
from benchmark import metrics as M
from benchmark.prefill_reduce import reduction
from benchmark.scope_reduce import traced_decode_steps


def read(ctx):
    final, red = ctx.get("final", {}), reduction(ctx)
    seconds, events = red.get("decode_walk_s"), red.get("decode_walk_events")
    steps_traced = traced_decode_steps(ctx) if seconds else None
    paired = dispatch_reduce.reduction(ctx) if steps_traced else {}
    if not steps_traced or final.get("mtp_drafts") or not all(paired.get(k) for k in ("steps", "row_steps", "live_steps")):
        return None  # no trace, a program without the kernel in its decode step or whose fences say nothing, or one that drafts
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if not hasattr(flops, "walk_step_bytes_min"):
        return None
    rows, live = paired["row_steps"] / paired["steps"], paired["live_steps"] / paired["row_steps"]
    step_bytes = flops.walk_step_bytes_min(ctx["config"], slots=rows, mean_positions=live)
    rate = step_bytes * steps_traced / seconds
    whole = ""
    if all(final.get(k) for k in ("decode_steps", "decode_tokens", "decode_live_positions")):
        whole = (f" (the whole run's means: {final['decode_tokens'] / final['decode_steps']:.2f} rows, "
                 f"{final['decode_live_positions'] / final['decode_tokens']:.1f} live positions a row)")
    print(f"the walks of a decode step: at least {step_bytes / 1e6:.2f} MB ({rows:.2f} rows, {live:.1f} live positions a "
          f"row over the window's {paired['steps']} paired steps{whole}; the kernel read {paired['attended'] / paired['live_steps']:.3f} "
          f"x that in whole blocks); {steps_traced:g} steps, {events:g} kernel runs in {seconds:.6f} s inside decode_block = "
          f"{rate / 1e9:.2f} GB/s, {1e3 * seconds / steps_traced:.4f} ms a step", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
