"""claim_wait_mean_ms: mean over the answered requests of the response's claim_wait_ms (client's submit -> claimed from the spool)."""
from benchmark.layer_readers import answers_stat

read = answers_stat("claim_wait_ms", "mean")
