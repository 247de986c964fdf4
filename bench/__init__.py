"""Closed-loop serving benchmark of the gRouting engine on TPU (see run.py)."""
