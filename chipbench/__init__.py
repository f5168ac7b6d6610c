"""On-chip benchmark harness: see ``run.py`` and ``harness.py``."""
