"""prefill_mean_ms: mean over the answered requests of the response's prefill_ms (admitted -> first token sampled)."""
from benchmark.layer_readers import answers_stat

read = answers_stat("prefill_ms", "mean")
