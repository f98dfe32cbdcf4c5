"""prefill_share_pct.serve_tps: the prefill_chunk program's part of the device's busy time, clipped to the traced window."""
from benchmark.layer_readers import program_share_pct

read = program_share_pct("prefill_chunk")
