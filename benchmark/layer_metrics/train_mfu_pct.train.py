"""train_mfu_pct.train: required operations per token x tokens/s/chip over the published bf16 peak of the reported device_kind."""
from benchmark.layer_readers import train_mfu_pct as read


