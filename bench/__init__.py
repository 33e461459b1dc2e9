"""Chip benchmark of the registry-replay serving path (see PERF.md)."""
