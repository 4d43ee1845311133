"""A ratio of SUMS of counts the program put on its own spans
(`RecordEvent(name, **counts)`), in per cent: 100 x the sum of the `num`
counts / the sum of the `den` counts over the spans of that name taken
while the traced run's profiler session was recording — program_span_ratio
for a quantity that is several counts a side (cache positions admitted by
each kind of layer over what one uniform pool would admit). With
`scale_by`, times that field of the record: a busiest expert's rows over
the MEAN rows an expert got is max x experts / total. A program whose
spans carry no such counts, or a denominator of 0, is nothing to read:
never 0."""
from .program_span_ms import traced_spans


def read(record, name: str, num: list, den: list, scale_by: str = None):
    top = bottom = 0.0
    for s in traced_spans():
        counts = s.counts or {}
        if s.name == name and all(k in counts for k in (*num, *den)):
            top += sum(counts[k] for k in num)
            bottom += sum(counts[k] for k in den)
    if not bottom or (scale_by and not record.get(scale_by)):
        return None
    return 100.0 * top / bottom * (record[scale_by] if scale_by else 1.0)
