"""On-chip benchmark of the Morpheus simulator (see ``harness.py``)."""
