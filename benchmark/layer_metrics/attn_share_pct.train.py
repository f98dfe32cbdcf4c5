"""attn_share_pct.train: the attn scope's part of the device's busy time in the traced window (span_reduce)."""
from benchmark.span_readers import scope_share_pct

read = scope_share_pct("attn")
