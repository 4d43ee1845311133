"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the idle gaps by what the host was doing.

`load` turns an `.xplane.pb` (jax.profiler.ProfileData) into plain data —
`[{"name": plane, "lines": [{"name": line, "events": [(name, start_ns,
duration_ns), ...]}]}]` — and `reduce` works on that alone, so the
arithmetic is checked on a synthetic trace (tests/benchmark) and is the
same for every PR.

Busy is the UNION of the intervals in which an operation ran on a device
(operations nest — a `while` covers its body — so durations are never
summed for it); an operation's own time is its duration minus its
children's. The traced window is the benchmark's `bench.traced` host span
where the trace holds it, else the span from the first device event to the
last. Idle gaps are attributed to the benchmark's host span that overlaps
each gap most; gaps no span touches go to "(no span)".
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
NO_SPAN = "(no span)"


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line; keep the
    result's name and its type: `%fusion.4 = bf16[8,16]{1,0} fusion(...)`
    -> `fusion.4 bf16[8,16]`."""
    lhs, _, rhs = name.partition(" = ")
    result = rhs.split("{")[0].split(" ")[0].lstrip("(")
    return f"{lhs.lstrip('%')} {result}".strip()[:80]


def load(path: str) -> list:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [(short_name(e.name), float(e.start_ns),
                              float(e.duration_ns)) for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _merge(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events) -> dict:
    """name -> summed own time (duration less nested children), ns."""
    totals, stack = {}, []          # stack of [name, end, child_time, dur]

    def close(item):
        name, _end, child, dur = item
        totals[name] = totals.get(name, 0.0) + max(dur - child, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0.0, dur])
    while stack:
        close(stack.pop())
    return totals


def _device_events(plane: dict) -> list:
    lines = [l for l in plane["lines"] if l["name"] == OP_LINE] \
        or plane["lines"]
    return [e for l in lines for e in l["events"] if e[2] > 0]


def _host_spans(planes, names) -> list:
    spans = [(s, s + d, n) for p in planes if p["name"].startswith("/host:")
             for l in p["lines"] for (n, s, d) in l["events"] if n in names]
    return sorted(spans)


def _attribute(gaps, spans) -> dict:
    """name -> idle ns: each gap goes whole to the span overlapping it most."""
    out, first = {}, 0
    for g0, g1 in gaps:
        while first < len(spans) and spans[first][1] <= g0:
            first += 1
        best, best_overlap, i = NO_SPAN, 0.0, first
        while i < len(spans) and spans[i][0] < g1:
            overlap = min(g1, spans[i][1]) - max(g0, spans[i][0])
            if overlap > best_overlap:
                best, best_overlap = spans[i][2], overlap
            i += 1
        out[best] = out.get(best, 0.0) + (g1 - g0)
    return out


def reduce(planes: list, span_names=(), top: int = 10):
    """-> {"busy_s", "window_s", "chips", "device_ops": [[name, s]...],
    "idle_gaps": [[span, s]...]} or None where no operation ran on a
    device. busy_s is averaged over the chips; the breakdown lists are
    those of the chip with the most busy time."""
    devices = [(p["name"], _device_events(p)) for p in planes
               if DEVICE_PLANE.match(p["name"])]
    devices = [(n, ev) for n, ev in devices if ev]
    if not devices:
        return None
    marks = _host_spans(planes, {WINDOW_SPAN})
    if marks:
        w0, w1 = marks[0][0], marks[0][1]
    else:
        w0 = min(s for _, ev in devices for (_, s, _) in ev)
        w1 = max(s + d for _, ev in devices for (_, s, d) in ev)
    spans = _host_spans(planes, set(span_names))
    per_chip = []
    for _name, events in devices:
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                   for n, s, d in events if s < w1 and s + d > w0]
        busy = _merge((s, s + d) for _, s, d in clipped)
        busy_ns = sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        per_chip.append((busy_ns, clipped, gaps))
    if not any(b for b, _, _ in per_chip):
        return None
    busy_ns, events, gaps = max(per_chip, key=lambda c: c[0])

    def ranked(totals):
        rows = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / 1e9] for name, ns in rows]

    return {
        "busy_s": sum(b for b, _, _ in per_chip) / len(per_chip) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": len(per_chip),
        "device_ops": ranked(_self_times(events)),
        "idle_gaps": ranked(_attribute(gaps, spans)),
    }
