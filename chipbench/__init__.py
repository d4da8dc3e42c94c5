"""Chip benchmark of the placement engine: cells of one deployment under
one traffic mix, run on a TPU by ``python chipbench/run.py``."""
