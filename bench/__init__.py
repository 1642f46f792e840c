"""The chip benchmark: see ``bench/run.py``."""
