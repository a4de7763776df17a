"""The chip benchmark of the Froid engine: ``python3 bench/run.py --help``."""
