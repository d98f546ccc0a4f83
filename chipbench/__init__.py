"""Chip benchmark of Marvel-JAX: one command, driven by the data files
under this directory (see ``run.py``)."""
