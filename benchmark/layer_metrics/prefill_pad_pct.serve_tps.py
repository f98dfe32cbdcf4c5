"""prefill_pad_pct.serve_tps: pad positions of each prompt's last chunk over the positions the prefill program ran (prompt + pad), from the engine's counters in the final record."""
from benchmark.span_readers import final_value

read = final_value("prefill_pad_pct")
