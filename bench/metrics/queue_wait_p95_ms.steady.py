"""Per-layer metric `queue_wait_p95_ms.steady`; see bench/attribution.py."""
from bench.attribution import queue_wait_p95_ms as read  # noqa: F401
