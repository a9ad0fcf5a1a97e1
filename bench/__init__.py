"""On-chip benchmark of the relational counting system (see BENCHMARK.json)."""
