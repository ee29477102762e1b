"""On-chip benchmark of the training system: ``python3 bench/run.py``."""
