"""mtp_share_pct.serve_tps: the mtp scope's part (the multi-token-prediction block: its joining product, its attention and slab, its experts, its head's product) of the device's busy time in the traced window (mtp_reduce); the block's scopes carry their own names, so the main stack's scope shares do not count it."""
from benchmark.mtp_reduce import scope_share_pct

read = scope_share_pct("mtp")
