"""End-to-end metric `setup_s`; see bench/readers.py."""
from bench.readers import setup_s as read  # noqa: F401
