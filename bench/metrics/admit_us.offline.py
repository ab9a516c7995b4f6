"""Per-layer metric `admit_us.offline`; see bench/readers.py."""
from bench.readers import admit_us as read  # noqa: F401
