"""admit_wait_mean_ms: mean of the engine's admit_wait_ms over the answered requests."""
from benchmark.layer_readers import answers_stat

read = answers_stat("admit_wait_ms", "mean")
