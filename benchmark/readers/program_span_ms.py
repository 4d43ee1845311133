"""A statistic of the duration (ms) of one of the PROGRAM's own spans —
`paddle_tpu.profiler.RecordEvent`, kept in the program's bounded ring and
asked for here — over the spans taken while the profiler session of a
`--trace 1` run was recording (`in_trace`), so they are the spans that sit
in the device trace too. `q` is a percentile, or "mean" for the mean over
all of them (a phase that runs on some ticks only weighs in by how often it
runs, where a median would not see it). With `minus`, each span's SELF
time: its duration less the part of its interval that spans of those names,
on the same thread and inside it, cover (their union, so nested ones count
once) — children on another thread (a `concurrent=True` router's workers)
are not subtracted. A program that keeps no such spans, or none of that
name, is nothing to read."""
from bisect import bisect_left

from paddle_tpu.profiler import get_profiler_spans

from ..harness import percentile


def traced_spans() -> list:
    """The program's `Span` records (fields name, start, dur_s, depth, tid,
    counts, in_trace) with in_trace true; [] where the records have no
    such field (a parent commit's)."""
    return [s for s in get_profiler_spans() if getattr(s, "in_trace", False)]


def _covered(children: list, starts: list, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] under the union of the (start, end) `children`
    (sorted, `starts` their starts) that lie inside it."""
    total, edge = 0.0, lo
    for start, end in children[bisect_left(starts, lo):]:
        if start >= hi:
            break
        if end <= hi and end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def read(record, name: str, q, minus=()):
    spans = traced_spans()
    inside = {}
    for s in spans:
        if s.name in minus:
            inside.setdefault(s.tid, []).append((s.start, s.start + s.dur_s))
    for rows in inside.values():
        rows.sort()
    starts = {tid: [r[0] for r in rows] for tid, rows in inside.items()}
    values = []
    for s in spans:
        if s.name != name:
            continue
        dur = s.dur_s
        if s.tid in inside:
            dur -= _covered(inside[s.tid], starts[s.tid], s.start,
                            s.start + s.dur_s)
        values.append(1e3 * dur)
    if q == "mean":
        return sum(values) / len(values) if values else None
    return percentile(values, q)
