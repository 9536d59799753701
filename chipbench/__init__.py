"""Chip benchmark of the served BaS join query (see PERF.md)."""
