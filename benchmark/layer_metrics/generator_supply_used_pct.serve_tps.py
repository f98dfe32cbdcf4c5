"""generator_supply_used_pct.serve_tps: 100 x the requests the closed loop's generator sent over the traffic file's ``supply``: how near the window came to running out of requests, where the run fails (a cell is written to stand at 50 or under: traffic.py)."""
from benchmark.layer_readers import generator_supply_used_pct as read
