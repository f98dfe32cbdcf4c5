"""fence_tail_ms.serve_tps: mean over the traced window's paired decode dispatches of the return of engine.decode_fence less the end of the dispatch's decode_block run on the device (benchmark/dispatch_reduce.py)."""
from benchmark.dispatch_reduce import fence_tail_ms as read
