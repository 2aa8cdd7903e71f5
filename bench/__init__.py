"""Chip benchmark of the plan path (see ``run.py``)."""
