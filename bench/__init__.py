"""The serving benchmark: ``python3 bench/run.py --help``."""
