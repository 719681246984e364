"""Chip benchmark of Asteroid's planned training path (``run.py``)."""
