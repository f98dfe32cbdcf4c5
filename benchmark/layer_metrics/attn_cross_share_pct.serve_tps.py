"""attn_cross_share_pct.serve_tps: the attn_cross scope's part (the layers that attend another layer's keys and values: query projection, the slab's walk, the difference of the two softmaxes, output projection) of the device's busy time in the traced window (xdec_reduce)."""
from benchmark.xdec_reduce import scope_share_pct

read = scope_share_pct("attn_cross")
