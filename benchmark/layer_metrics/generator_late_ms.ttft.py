"""generator_late_ms.ttft: mean over the window's requests of how late the benchmark's own load generator sent each (sent - due); a time to first token is counted from due, so a stall here reads as the server's."""
from benchmark.layer_readers import generator_late_ms as read
