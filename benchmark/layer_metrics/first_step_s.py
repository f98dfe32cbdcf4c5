"""first_step_s: the replica's first status record -> its first_step record (weights, compile or cache hit, first execution)."""
from benchmark.layer_readers import first_step_s as read


