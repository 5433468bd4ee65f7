"""The benchmark of the streaming HDBSCAN service (see BENCHMARK.json)."""
