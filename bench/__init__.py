"""The chip benchmark of the tuning service (``python bench/run.py``)."""
