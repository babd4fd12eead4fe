"""Chip benchmark of the Speed-ANN serving stack (see PERF.md at the root)."""
