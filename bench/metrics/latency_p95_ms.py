"""End-to-end metric `latency_p95_ms`; see bench/readers.py."""
from bench.readers import latency_p95_ms as read  # noqa: F401
