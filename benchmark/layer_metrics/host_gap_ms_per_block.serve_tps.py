"""host_gap_ms_per_block.serve_tps: the engine's host seconds between a fence's return and the next dispatch while it had work, over its decode blocks; segments on an earlier line."""
from benchmark.span_readers import host_gap_ms_per_block as read
