"""The chip benchmark of the failover training loop (`python3 bench/run.py`)."""
