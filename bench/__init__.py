"""Chip benchmark of the pool's served control plane (see PERF.md)."""
