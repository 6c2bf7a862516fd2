"""On-chip benchmark of the served query path (see ``bench/run.py``)."""
