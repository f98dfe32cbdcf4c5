"""engine_decode_tok_s.serve_tps: engine.stats() decode_tokens_per_sec over the window."""
from benchmark.layer_readers import engine_decode_tok_s as read


