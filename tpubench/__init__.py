"""On-chip benchmark of the store's read paths; see ``run.py``."""
