"""On-chip benchmark of the transfer service: see ``bench/harness.py``."""
