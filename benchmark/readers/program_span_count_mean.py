"""The MEAN of one count the program put on its own spans
(`RecordEvent(name, **counts)`) over the spans of that name taken while the
traced run's profiler session was recording — with `where`, over those
alone on which that other count is above 0 (a decode tick's wait since the
tick before, on the ticks that carried a row over from it). A mean, so
what happens on one span in eight weighs in by how often it happens. A
program whose spans carry no such count, or no span that qualifies, is
nothing to read: never 0."""
from .program_span_ms import traced_spans


def read(record, name: str, count: str, where: str = None):
    values = [s.counts[count] for s in traced_spans()
              if s.name == name and s.counts and count in s.counts
              and (where is None or s.counts.get(where, 0) > 0)]
    return sum(values) / len(values) if values else None
