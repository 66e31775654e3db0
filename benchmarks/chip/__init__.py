"""On-chip benchmark of the distributed data structures (see run.py)."""
