"""launch_s: submit -> the replica's first status record (CLI, supervisor, runner, env, import)."""
from benchmark.layer_readers import launch_s as read


