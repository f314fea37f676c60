"""Chip benchmark of the CacheX probe stack (see run.py)."""
