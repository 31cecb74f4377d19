"""The chip benchmark of LOG.io's training feed: ``python bench/run.py``."""
