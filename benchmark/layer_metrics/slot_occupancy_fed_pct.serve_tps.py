"""slot_occupancy_fed_pct.serve_tps: rows that held a request over rows the decode blocks ran, from the engine's record as it stood at the newest arrival (fed_slot_occupancy_pct of the final record): the window without the drain."""
from benchmark.span_readers import final_value

read = final_value("fed_slot_occupancy_pct")
