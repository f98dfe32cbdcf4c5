"""expert_load_max_over_mean.serve_tps: the busiest held expert's tokens over the mean of the held experts (moe_expert_tokens summed over the expert layers, the model's device counters in the final record)."""
from benchmark.span_readers import final_value

read = final_value("expert_load_max_over_mean")
