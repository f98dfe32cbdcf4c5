"""expert_local_hit_pct.serve_tps: of the experts the window's tokens selected, the share held on this chip (100 x moe_local_pairs / (k x moe_tokens), the model's device counters in the final record); 100 x held / router width where the router routes over the whole layer."""
from benchmark.span_readers import final_value

read = final_value("expert_local_hit_pct")
