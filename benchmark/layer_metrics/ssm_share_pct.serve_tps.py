"""ssm_share_pct.serve_tps: the ssm scope's part (the Mamba-2 layers: projections, convolution, scan, gated norm) of the device's busy time in the traced window (ssm_reduce)."""
from benchmark.ssm_reduce import scope_share_pct

read = scope_share_pct("ssm")
