"""Harness library of the chip benchmark (see ``bench/run.py``)."""
