"""generator_stall_ms_per_s.ttft: milliseconds by which the load generator's sleeps overran (50 ms or more each), per second of the window: the whole machine stood still, the server with it; a request due in one waits it out."""
from benchmark.layer_readers import generator_stall_ms_per_s as read
