"""tpot_p90_ms_seen: 90th percentile of the engine's tpot_ms; a record, never judged."""
from benchmark.layer_readers import answers_stat

read = answers_stat("tpot_ms", "90")
