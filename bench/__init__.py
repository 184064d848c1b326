"""Chip benchmark of the bit-plane serving path (see ``bench/run.py``)."""
