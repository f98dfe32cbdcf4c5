"""generator_stall_ms_per_s.serve_tps: milliseconds by which the load generator's sleeps overran (50 ms or more each), per second of the window: the whole machine stood still, the server with it; the rate loses that share of the window."""
from benchmark.layer_readers import generator_stall_ms_per_s as read
