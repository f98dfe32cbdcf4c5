"""decode_step_ms.tpot: device time of the decode_block runs of the traced window that were paired with the engine's dispatch spans, over the steps those spans say (benchmark/dispatch_reduce.py); the window's counts from the spans on earlier lines."""
from benchmark.dispatch_reduce import decode_step_ms as read
