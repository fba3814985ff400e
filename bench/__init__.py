"""On-chip benchmark of the clustering engine and IVF search (see PERF.md)."""
