"""The chip benchmark: one command (run.py), everything else found by name."""
