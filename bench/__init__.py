"""The chip benchmark of the triangle-count system (see ``bench/run.py``)."""
