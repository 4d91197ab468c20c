"""On-chip benchmark of the edge/cloud serving path (see ``run.py``)."""
