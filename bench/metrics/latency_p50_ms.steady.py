"""Per-layer metric `latency_p50_ms.steady`; see bench/readers.py."""
from bench.readers import latency_p50_ms as read  # noqa: F401
