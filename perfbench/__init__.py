"""The chip benchmark of the sameAs store: ``python3 perfbench/run.py``."""
