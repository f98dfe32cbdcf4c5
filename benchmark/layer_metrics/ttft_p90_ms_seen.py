"""ttft_p90_ms_seen: 90th percentile of the engine's ttft_ms; a record, never judged."""
from benchmark.layer_readers import answers_stat

read = answers_stat("ttft_ms", "90")
