"""The chip benchmark: cells of (model configuration x traffic mix) run
one at a time by ``bench/run.py``.  Nothing here is imported by the
program; the program is the system under test."""
