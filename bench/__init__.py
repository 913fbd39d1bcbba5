"""The on-chip benchmark of the served CAM search (see BENCHMARK.json)."""
