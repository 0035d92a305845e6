"""Chip benchmark of the SaP solver: see run.py and PERF.md."""
