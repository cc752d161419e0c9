"""Chip benchmark of the dropout train step (see run.py)."""
