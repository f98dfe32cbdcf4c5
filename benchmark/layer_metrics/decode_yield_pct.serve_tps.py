"""decode_yield_pct.serve_tps: tokens accepted over the steps the occupied rows ran (rows x block), from the engine's counters in the final record."""
from benchmark.span_readers import final_value

read = final_value("decode_yield_pct")
