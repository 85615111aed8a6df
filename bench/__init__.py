"""On-chip benchmark of KBest's served path (see bench/run.py)."""
