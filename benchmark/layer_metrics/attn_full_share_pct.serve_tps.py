"""attn_full_share_pct.serve_tps: the attn_full scope's part of the device's busy time in the traced window (scope_reduce)."""
from benchmark.scope_reduce import scope_share_pct

read = scope_share_pct("attn_full")
