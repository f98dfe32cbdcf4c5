"""step_ms_p50.train: median device time of the train step's program in the traced window."""
from benchmark.layer_readers import program_run_ms_p50

read = program_run_ms_p50("train_step")
