"""On-chip benchmark of the federated detector: see ``bench/run.py``."""
