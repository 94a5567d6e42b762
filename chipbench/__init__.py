"""On-chip benchmark of the SpGEMM serving path (see BENCHMARK.json)."""
