"""prefill_scan_share_pct.serve_tps: the ssm_scan scope's part (the sequential recurrence of the chunk's scan, a Mamba layer at a time) of the device time of the jit_prefill_chunk... programs in the traced window (prefill_reduce): what the recurrence costs a long prompt."""
import json

from benchmark.prefill_reduce import reduction


def read(ctx):
    red = reduction(ctx)
    seconds = red.get("prefill_scope_s", {}).get("ssm_scan", 0.0)
    if not red.get("prefill_s") or seconds <= 0.0:
        return None  # no trace, or a program without the scope in its prefill
    print(f"device s of the prefill programs by scope: {json.dumps({k: round(v, 6) for k, v in red['prefill_scope_s'].items()})} "
          f"of {red['prefill_s']:.6f} (busy {red['busy_s']:.6f})", flush=True)
    return 100.0 * seconds / red["prefill_s"]
