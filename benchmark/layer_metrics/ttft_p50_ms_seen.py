"""ttft_p50_ms_seen: median of the engine's ttft_ms; a record, never judged."""
from benchmark.layer_readers import answers_stat

read = answers_stat("ttft_ms", "50")
