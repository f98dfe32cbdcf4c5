"""slot_wait_mean_ms: mean over the answered requests of the response's slot_wait_ms (claimed -> admitted to a slot)."""
from benchmark.layer_readers import answers_stat

read = answers_stat("slot_wait_ms", "mean")
