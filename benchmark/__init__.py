"""The tpujob benchmark: BENCHMARK.json's harness, data and yardstick."""
