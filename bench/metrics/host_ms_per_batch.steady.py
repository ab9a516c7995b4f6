"""Per-layer metric `host_ms_per_batch.steady`; see bench/readers.py."""
from bench.readers import host_ms_per_batch as read  # noqa: F401
