"""On-chip benchmark of the served LazyBatching path (see PERF.md)."""
