"""The share of the program's traced time that one of its spans took, in
per cent: 100 x the summed duration of the spans named `name` / (the end of
the last span named `over` - the start of the first), over the spans taken
while the traced run's profiler session was recording — a phase's share of
the serving loop's wall time from the program's own spans (prefill that
nothing overlaps with decode over the router's ticks), where a median of
the phase's duration says nothing of how often it runs. `over` traced and
no `name` inside is 0: the phase did not run. No `over` traced — a program
that keeps no such spans, a run without a session — is nothing to read."""
from .program_span_ms import traced_spans


def read(record, name: str, over: str):
    spans = traced_spans()
    frame = [s for s in spans if s.name == over]
    if not frame:
        return None
    start = min(s.start for s in frame)
    end = max(s.start + s.dur_s for s in frame)
    if end <= start:
        return None
    inside = sum(s.dur_s for s in spans if s.name == name
                 and s.start >= start and s.start + s.dur_s <= end)
    return 100.0 * inside / (end - start)
