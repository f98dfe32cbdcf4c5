"""prefill_mfu_pct.serve_tps: the operations the traced window's prompt tokens required (the family's flops.forward_flops_per_token(model, position, head=False) at the chunks' mean position, over the n_real of the window's engine.prefill_dispatch spans a chunk times the chunk program's runs on the device; the head's product once a head run) over the device time of the jit_prefill_chunk... programs there, over the chip's published bfloat16 peak: the chunk's share of its roofline. Pads count as time, not as operations."""
from benchmark import family
from benchmark import metrics as M
from benchmark.prefill_reduce import prefill_tokens, reduction


def read(ctx):
    red = reduction(ctx)
    carried = prefill_tokens(red)
    if not carried or not red.get("prefill_s"):
        return None  # no trace, or a program whose chunk spans do not say what they carried
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    if not hasattr(flops, "forward_flops_per_token") or not hasattr(flops, "head_flops"):
        return None
    tokens, position, heads = carried
    per_token = flops.forward_flops_per_token(ctx["config"], position, head=False)
    done = tokens * per_token + heads * flops.head_flops(ctx["config"])
    spans = red["spans"]
    print(f"prefill: {red['chunk_runs']:g} runs of the chunk's program and {heads:g} of the head's in {red['prefill_s']:.6f} s "
          f"of device time; the window's {spans['chunks']} chunk spans carried {spans['n_real']} prompt tokens "
          f"({spans['n_real'] / spans['chunks']:.1f} a chunk, mean position {position:.0f}, {spans['resumed']} of them "
          f"resumed a prompt, {spans['heads']} queued a head): {tokens:.0f} tokens x {per_token / 1e9:.3f} GFLOP = "
          f"{done / red['prefill_s'] / 1e12:.2f} TFLOP/s, {tokens / red['prefill_s']:.0f} prompt tokens/s of device time",
          flush=True)
    return 100.0 * done / red["prefill_s"] / M.peaks(ctx["device"]["device_kind"])["bf16_flops"]
