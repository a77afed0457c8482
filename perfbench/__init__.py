"""On-chip benchmark of the served path (see `perfbench/run.py`)."""
