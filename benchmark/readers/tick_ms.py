"""A percentile of the host time around each router.step() in the
benchmark's loop, ms."""
from ..harness import percentile


def read(record, q: float = 50.0):
    return percentile([1e3 * t for t in record.get("tick_s", ())], q)
