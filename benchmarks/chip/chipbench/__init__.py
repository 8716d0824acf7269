"""The chip benchmark of the LIFL aggregation service (see run.py)."""
