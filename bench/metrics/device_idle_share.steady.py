"""Per-layer metric `device_idle_share.steady`; see bench/readers.py."""
from bench.readers import device_idle_share as read  # noqa: F401
