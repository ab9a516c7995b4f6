"""End-to-end metric `graphs_per_s`; see bench/readers.py."""
from bench.readers import graphs_per_s as read  # noqa: F401
