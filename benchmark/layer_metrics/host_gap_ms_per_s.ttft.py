"""host_gap_ms_per_s.ttft: the engine's host milliseconds between a fence's return and the next dispatch while it had work, per second of the window; segments on an earlier line."""
from benchmark.span_readers import host_gap_ms_per_s as read
