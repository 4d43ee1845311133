"""A ratio of two COUNTS the program put on its own spans
(`RecordEvent(name, **counts)`), in per cent: 100 x sum of `num` / sum of
`den` over the spans of that name taken while the traced run's profiler
session was recording — or, with `complement`, 100 less that (the share
that is NOT `num`: padded tokens of a bucket, empty slots of a tick). No
such span, or a denominator of 0, is nothing to read: never 0."""
from .program_span_ms import traced_spans


def read(record, name: str, num: str, den: str, complement: bool = False):
    top = bottom = 0.0
    for s in traced_spans():
        if s.name == name and s.counts and num in s.counts \
                and den in s.counts:
            top += s.counts[num]
            bottom += s.counts[den]
    if not bottom:
        return None
    share = 100.0 * top / bottom
    return 100.0 - share if complement else share
