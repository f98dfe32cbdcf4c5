"""alloc_peak_gb: the allocator's peak_bytes_in_use on the fullest chip (without a program's temporaries)."""
from benchmark.layer_readers import alloc_peak_gb as read


