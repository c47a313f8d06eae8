"""Chip benchmark of the LifeRaft cross-match service (see run.py)."""
