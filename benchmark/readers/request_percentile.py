"""A percentile over the window's requests of one per-request time (ms):
  ttft_ms        first token seen - time the request was DUE; a request
                 with no first token counts as the longest wait seen
  tpot_ms        (last token - first token) / (tokens - 1)
  queue_wait_ms  due -> start of the router.step() that gave it a slot
Over ALL requests the record counts as measured."""
from ..harness import percentile


def read(record, field: str, q: float):
    requests = record.get("requests", ())
    values = [r[field] for r in requests if r.get(field) is not None]
    if field == "ttft_ms":
        missing = sum(1 for r in requests if r.get(field) is None)
        if missing:
            worst = max(values + [1e3 * record["window_s"]])
            values += [worst] * missing
    return percentile(values, q)
