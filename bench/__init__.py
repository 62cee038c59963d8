"""On-chip benchmark of the fleet tuning service (see ``bench/run.py``)."""
