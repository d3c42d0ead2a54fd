"""Chip benchmark of the continuous-depth LM training path (see run.py)."""
