"""ssm_state_roofline_pct.serve_tps: the least bytes the state-space layers had to move in the traced window's decode steps (the family's flops.ssm_step_bytes_min: the Mamba layers' weights once a step, and each occupied row's convolution tail and float32 scan state read and written) over the device time of the ssm scope inside decode_block there, over the published HBM bandwidth. Bound by bytes, not operations."""
from benchmark import family
from benchmark import metrics as M
from benchmark.scope_reduce import traced_decode_steps
from benchmark.ssm_reduce import decode_scope_s


def read(ctx):
    final, seconds = ctx.get("final", {}), decode_scope_s(ctx, "ssm")
    steps_traced = traced_decode_steps(ctx) if seconds else None
    if not steps_traced or not all(final.get(k) for k in ("decode_steps", "decode_tokens")):
        return None  # no trace, or a program without the scope
    rows = final["decode_tokens"] / final["decode_steps"]
    flops = family.of(ctx["config"], "flops", ctx["bench"])
    step_bytes = flops.ssm_step_bytes_min(ctx["config"], slots=rows)
    rate = step_bytes * steps_traced / seconds
    print(f"state-space part of a decode step: at least {step_bytes / 1e9:.4f} GB ({rows:.2f} rows); {steps_traced:g} "
          f"steps in {seconds:.6f} s of the ssm scope inside decode_block = {rate / 1e9:.2f} GB/s", flush=True)
    return 100.0 * rate / M.peaks(ctx["device"]["device_kind"])["hbm_bytes_per_s"]
