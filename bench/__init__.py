"""The chip benchmark of the tracking platform (see ``bench/run.py``)."""
