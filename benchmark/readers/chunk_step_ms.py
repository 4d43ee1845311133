"""Median over the loop's sync-to-sync chunks of (host seconds of the
chunk / steps in it), in ms: the statistic that does NOT see one stall,
beside the rate that does."""
from ..harness import percentile


def read(record, q: float = 50.0):
    per_step = [1e3 * s / n for s, n in record.get("chunks", ()) if n]
    return percentile(per_step, q)
