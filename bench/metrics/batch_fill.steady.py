"""Per-layer metric `batch_fill.steady`; see bench/readers.py."""
from bench.readers import batch_fill as read  # noqa: F401
