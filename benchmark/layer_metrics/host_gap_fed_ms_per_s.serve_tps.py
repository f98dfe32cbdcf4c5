"""host_gap_fed_ms_per_s.serve_tps: the engine's host gap as it stood at the newest arrival over the seconds of that record (1,000 x fed_host_gap_s / fed_s of the final record): the window without the drain."""
from benchmark.dispatch_reduce import host_gap_fed_ms_per_s as read
