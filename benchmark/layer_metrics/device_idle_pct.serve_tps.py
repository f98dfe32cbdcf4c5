"""device_idle_pct.serve_tps: 1 - union of device operation intervals over the traced window."""
from benchmark.layer_readers import device_idle_pct as read


