"""slot_occupancy_pct.tpot: rows that held a request over rows the decode blocks ran (slots x blocks), from the engine's counters in the final record."""
from benchmark.span_readers import final_value

read = final_value("slot_occupancy_pct")
