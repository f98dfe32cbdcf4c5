"""device_idle_pct.train: 1 - union of device operation intervals over the traced window."""
from benchmark.layer_readers import device_idle_pct as read


