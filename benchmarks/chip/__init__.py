"""On-chip benchmark of the Gossip-PGA trainer (see ``run.py``)."""
