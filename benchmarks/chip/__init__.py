"""On-chip benchmark of the trust tier (see run.py)."""
