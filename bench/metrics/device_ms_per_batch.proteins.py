"""Per-layer metric `device_ms_per_batch.proteins`; see bench/readers.py."""
from bench.readers import device_ms_per_batch as read  # noqa: F401
