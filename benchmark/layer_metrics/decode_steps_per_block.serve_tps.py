"""decode_steps_per_block.serve_tps: decode steps run over the decode dispatches made (the mean length the engine chose for a dispatch; steps past a row's budget yield nothing), from the engine's counters in the final record."""
from benchmark.span_readers import final_value

read = final_value("decode_steps_per_block")
