"""boundary_ms.serve_tps: mean duration of serve.boundary (the serve loop's side of a boundary between two engine steps, engine busy) in the traced window; its children's parts and its self time on the line before."""
from benchmark.dispatch_reduce import boundary_ms as read
