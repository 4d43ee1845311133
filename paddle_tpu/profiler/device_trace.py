"""paddle_tpu.profiler.device_trace — the device half of a profiler trace,
read beside the program's own spans on one clock.

Reference analog: python/paddle/profiler/profiler_statistic.py (the
DeviceView / KernelView tables of `Profiler.summary()`: device time per
kernel and per op, beside the host's) and paddle/fluid/platform/profiler/
event_node.cc (host and device events joined into one tree by time).

A profiler session (`Profiler(trace_dir=...)`, `jax.profiler.start_trace`)
writes one `.xplane.pb`: an `XSpace` whose `/device:TPU:n` planes hold the
line `XLA Modules` (one event per run of a compiled program) and the line
`XLA Ops` (the operations inside it, nested, each with its scope path in
the op metadata's `tf_op` stat), and whose `/host:` planes hold every
`RecordEvent` of the program as a `TraceAnnotation`. This module READS
that file — it changes nothing on any hot path — and gives, as plain dicts
(`device_view`) and as a printed table (`format_view`):

1. `programs`: per program of the `XLA Modules` line, its runs, their
   median / min / max device ms and the program span most of them ran
   under;
2. per program and per run, own device time by SCOPE — the named scopes
   in `tf_op`, a Pallas kernel under its own name —, an operation's own
   time being its duration less its children's;
3. `clock`: the device line's offset against the host line. A decode
   tick's program cannot start before `serving.decode_dispatch` opens nor
   end after `serving.decode_pull` closes, so over the traced ticks the
   offset lies in [max(dispatch_start - module_start),
   min(pull_end - module_end)]; the midpoint is applied, half the width is
   its error, and with it the launch latency (dispatch opening -> program
   start) and the return latency (program end -> pull closing);
4. `idle`: the device's idle gaps by the INNERMOST program span that
   overlaps each — a gap that spans several spans is split at their edges
   — and `spans`, the host's own time by the same rule (a span's self time
   is what none of its children cover). The idle window is first to last
   device event, or the caller's `window`: the name of a host span it put
   around what it measures.

`python -m paddle_tpu.profiler.device_trace <xplane.pb or dir> [window
span]` prints it.
The raw `XSpace` is parsed with tensorflow's generated `xplane_pb2`, loaded
from its file (importing the `tensorflow` package for it takes seconds);
without it `jax.profiler.ProfileData` gives everything but the scopes.
"""
from __future__ import annotations

import bisect
import glob
import importlib.util
import os
import re
import statistics
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "serving."          # the program's spans on the host lines
TICK, DISPATCH, PULL = ("serving.decode_tick", "serving.decode_dispatch",
                        "serving.decode_pull")
OUTSIDE = "(outside the program)"
NO_SCOPE = "(no scope)"
# tf_op components that are the tracing machinery's, not a named scope
_WRAPPERS = re.compile(r"^(\w+)\((.*)\)$")
_FUNCTION_WRAPPERS = {"jit", "pjit", "xla_call", "named"}
_STRUCTURE = {"while", "body", "cond", "closed_call", "core_call",
              "checkpoint", "remat", "rematted_computation",
              "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
              "shard_map", "branch_0_fun", "branch_1_fun"}


# ------------------------------------------------------------------ loading
def find_trace(path_or_dir: str) -> str:
    """The `.xplane.pb` itself, or the newest one under a trace
    directory (`<dir>/plugins/profile/<time>/<host>.xplane.pb`)."""
    if os.path.isfile(path_or_dir):
        return path_or_dir
    files = glob.glob(os.path.join(path_or_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path_or_dir!r}")
    return max(files, key=os.path.getmtime)


def _xplane_pb2():
    """tensorflow's generated module for xplane.proto, or None. Loaded
    from its file where the package is not imported yet: the module needs
    protobuf alone, `import tensorflow` takes seconds."""
    name = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    if name in sys.modules:
        return sys.modules[name]
    if "tensorflow" in sys.modules:
        return importlib.import_module(name)
    root = importlib.util.find_spec("tensorflow")
    if root is None or not root.submodule_search_locations:
        return None
    path = os.path.join(root.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    try:
        spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    except (ImportError, OSError):
        return None
    return module


def _planes_from_xspace(space) -> list:
    planes = []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        tf_ops = {}
        for mid, meta in plane.event_metadata.items():
            for stat in meta.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    tf_ops[mid] = stat.str_value or stat_names.get(
                        stat.ref_value, "")
        lines = []
        for line in plane.lines:
            t0 = line.timestamp_ns
            lines.append({"name": line.name, "events": [
                (plane.event_metadata[e.metadata_id].name,
                 t0 + e.offset_ps / 1e3, e.duration_ps / 1e3,
                 tf_ops.get(e.metadata_id, "")) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load(path: str) -> list:
    """The trace as plain data: `[{"name": plane, "lines": [{"name": line,
    "events": [(name, start_ns, duration_ns, tf_op), ...]}]}]`, `tf_op`
    the op metadata's scope path ("" where the trace or its reader has
    none)."""
    pb2 = _xplane_pb2()
    if pb2 is not None:
        space = pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        return _planes_from_xspace(space)
    from jax.profiler import ProfileData
    return [{"name": plane.name, "lines": [
        {"name": line.name, "events": [
            (e.name, float(e.start_ns), float(e.duration_ns), "")
            for e in line.events]} for line in plane.lines]}
        for plane in ProfileData.from_file(path).planes]


# ------------------------------------------------------------------- pieces
def scope_of(tf_op: str, op_name: str = "") -> str:
    """The named scopes of a `tf_op` path, joined by `/`:
    `jit(f)/while/body/closed_call/attention/kv_update/scatter:` ->
    `attention/kv_update` — the function wrappers, the control-flow
    structure and the final primitive dropped, `jvp(...)` / `transpose(...)`
    around a scope opened. A Pallas kernel (primitive `pallas_call`) ends
    in its own name, the HLO instruction's, where `pallas_call(name=)` has
    not put it there already."""
    # a fusion of several source ops lists them all, `a/b/op;c/d/op`: the
    # first is the fusion's root
    parts = [p for p in tf_op.split(";")[0].rstrip(":").split("/") if p]
    if not parts:
        return NO_SCOPE
    primitive, scopes = parts[-1], []
    for part in parts[:-1]:
        while (m := _WRAPPERS.match(part)):
            part = "" if m.group(1) in _FUNCTION_WRAPPERS else m.group(2)
        if part and part not in _STRUCTURE:
            scopes.append(part)
    if primitive == "pallas_call":
        kernel = re.sub(r"\.\d+$", "", op_name.split(" = ")[0].lstrip("%"))
        if kernel and scopes[-1:] != [kernel]:
            scopes.append(kernel)
    return "/".join(scopes) or NO_SCOPE


def _own_times(ops: list) -> list:
    """[(start, own_ns, name, tf_op)] of nested (name, start, dur, tf_op)
    events: own = duration less the children's."""
    out, stack = [], []               # stack of [end, index into out]
    for name, start, dur, tf_op in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        stack.append([start + dur, len(out)])
        out.append([start, dur, name, tf_op])
    return [(s, max(own, 0.0), n, t) for s, own, n, t in out]


def _merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def innermost_segments(spans: list) -> list:
    """Disjoint, sorted `(start, end, name)` covering the union of the
    `(start, end, name)` spans, each piece named by the innermost span
    over it: of the spans that cover it, the one that started last."""
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    ordered = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, active, nxt = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(ordered) and ordered[nxt][0] <= lo:
            active.append(ordered[nxt])
            nxt += 1
        active = [s for s in active if s[1] > lo]
        if not active:
            continue
        name = active[-1][2]
        if out and out[-1][2] == name and out[-1][1] == lo:
            out[-1][1] = hi
        else:
            out.append([lo, hi, name])
    return [tuple(s) for s in out]


def _by_segment(intervals: list, segments: list) -> dict:
    """name -> ns of the sorted disjoint `intervals` under each named
    segment; what no segment covers goes to OUTSIDE."""
    out = {}
    starts = [s[0] for s in segments]
    for a, b in intervals:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        if b - a - covered > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (b - a - covered)
    return out


def _ms_stats(values_ms: list) -> dict:
    return {"n": len(values_ms), "median_ms": statistics.median(values_ms),
            "min_ms": min(values_ms), "max_ms": max(values_ms),
            "total_ms": sum(values_ms)}


def _host_spans(planes: list, mark: str = None) -> list:
    """The program's spans on the host lines, and the caller's `mark`."""
    return sorted(
        (start, start + dur, name)
        for p in planes if p["name"].startswith(HOST_PLANE)
        for line in p["lines"] for name, start, dur, _ in line["events"]
        if name.startswith(SPAN_PREFIX) or name == mark)


def _clock(modules: list, spans: list):
    """The device line against the host line, from the decode ticks that
    hold exactly one program run (by its midpoint, on the raw clocks: the
    offset is far below half a program)."""
    ticks = [s for s in spans if s[2] == TICK]
    if not ticks or not modules:
        return None
    tick_starts = [t[0] for t in ticks]

    def inside(name: str) -> dict:
        """tick index -> the one span of that name inside the tick."""
        found = {}
        for s, e, n in spans:
            if n != name:
                continue
            i = bisect.bisect_right(tick_starts, s) - 1
            if i >= 0 and e <= ticks[i][1]:
                found.setdefault(i, []).append((s, e))
        return {i: v[0] for i, v in found.items() if len(v) == 1}

    dispatches, pulls = inside(DISPATCH), inside(PULL)
    held = {}
    for name, start, dur, _ in modules:
        i = bisect.bisect_right(tick_starts, start + dur / 2) - 1
        if i >= 0 and start + dur / 2 < ticks[i][1]:
            held.setdefault(i, []).append((start, start + dur, name))
    rows = [(dispatches[i][0], pulls[i][1], m[0][0], m[0][1], m[0][2])
            for i, m in sorted(held.items())
            if len(m) == 1 and i in dispatches and i in pulls]
    if not rows:
        return None
    # the decode program is the one most ticks hold; a stray small program
    # whose early clock puts it inside a tick says nothing of this one's
    program = statistics.mode(r[4] for r in rows)
    rows = [r for r in rows if r[4] == program]
    lo = max(d0 - m0 for d0, _, m0, _, _ in rows)
    hi = min(p1 - m1 for _, p1, _, m1, _ in rows)
    offset = (lo + hi) / 2
    return {
        "ticks": len(rows),
        "program": program,
        "offset_low_ms": lo / 1e6, "offset_high_ms": hi / 1e6,
        "offset_ms": offset / 1e6, "error_ms": abs(hi - lo) / 2e6,
        "consistent": lo <= hi,
        "launch_ms_median": statistics.median(
            m0 + offset - d0 for d0, _, m0, _, _ in rows) / 1e6,
        "program_ms_median": statistics.median(
            m1 - m0 for _, _, m0, m1, _ in rows) / 1e6,
        "return_ms_median": statistics.median(
            p1 - m1 - offset for _, p1, _, m1, _ in rows) / 1e6,
        "dispatch_to_pull_ms_median": statistics.median(
            p1 - d0 for d0, p1, _, _, _ in rows) / 1e6,
    }


def _span_table(spans: list, segments: list) -> dict:
    """name -> {n, total_ms, mean_ms, self_ms}: a span's self time is
    the time it is the innermost."""
    table = {}
    for s, e, name in spans:
        row = table.setdefault(name, {"n": 0, "total_ms": 0.0,
                                      "self_ms": 0.0})
        row["n"] += 1
        row["total_ms"] += (e - s) / 1e6
    for s, e, name in segments:
        table[name]["self_ms"] += (e - s) / 1e6
    for row in table.values():
        row["mean_ms"] = row["total_ms"] / row["n"]
    return table


def _programs(modules: list, ops: list) -> dict:
    """name -> its runs with own device ms by scope, their stats, and the
    mean by scope a run. A module's name keeps its fingerprint,
    `jit_prefill(5127...)`: two prompt buckets are two programs."""
    starts = [m[1] for m in modules]
    runs = [{"program": m[0], "start_ns": m[1], "ms": m[2] / 1e6,
             "by_scope": {}} for m in modules]
    for start, own, name, tf_op in _own_times(ops):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= modules[i][1] + modules[i][2]:
            continue
        scope = scope_of(tf_op, name)
        by = runs[i]["by_scope"]
        by[scope] = by.get(scope, 0.0) + own / 1e6
    programs = {}
    for run in runs:
        programs.setdefault(run["program"], {"runs": []})["runs"].append(run)
    for prog in programs.values():
        prog.update(_ms_stats([r["ms"] for r in prog["runs"]]))
        mean = prog["by_scope_ms"] = {}
        for run in prog["runs"]:
            for scope, ms in run["by_scope"].items():
                mean[scope] = mean.get(scope, 0.0) + ms / prog["n"]
    return programs


def _idle(busy: list, marks: list, segments: list) -> dict:
    """The device's idle gaps (what the merged `busy` intervals leave of
    the window: the caller's first mark, else first to last device event)
    by the innermost program span over each."""
    w0, w1 = marks[0][:2] if marks else (busy[0][0], busy[-1][1])
    busy = [(max(s, w0), min(e, w1)) for s, e in busy if s < w1 and e > w0]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle_ns = sum(e - s for s, e in gaps)
    by_span = _by_segment(gaps, segments)
    return {
        "window_ms": (w1 - w0) / 1e6,
        "window": marks[0][2] if marks else "first to last device event",
        "busy_ms": (w1 - w0 - idle_ns) / 1e6,
        "idle_ms": idle_ns / 1e6,
        "idle_share": 100.0 * idle_ns / (w1 - w0) if w1 > w0 else 0.0,
        "by_span_ms": {k: v / 1e6 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
    }


# ----------------------------------------------------------------- the view
def device_view(planes: list, window: str = None) -> dict:
    """The four tables of the module docstring from `load()`'s planes.
    `window` bounds the idle table: the name of a host span the caller put
    around what it measures (the first of that name; it is no program
    span), else — None, or no such span — first to last device event. A
    trace without a device plane (a CPU run) keeps `spans` and says
    `"device": None`."""
    all_spans = _host_spans(planes, window)
    marks = [s for s in all_spans if s[2] == window]
    spans = [s for s in all_spans if s[2] != window]
    segments = innermost_segments(spans)
    view = {"device": None, "chips": 0, "programs": {}, "clock": None,
            "idle": None, "spans": _span_table(spans, segments)}
    devices = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = [e for e in lines.get(OP_LINE, ()) if e[2] > 0]
        modules = sorted((e for e in lines.get(MODULE_LINE, ())
                          if e[2] > 0), key=lambda e: e[1])
        if ops or modules:
            devices.append((sum(e[2] for e in modules), p["name"], modules,
                            ops))
    if not devices:
        return view
    _, view["device"], modules, ops = max(devices, key=lambda d: d[0])
    view["chips"] = len(devices)
    view["programs"] = _programs(modules, ops)
    # device times below are on the host's line
    view["clock"] = clock = _clock(modules, spans)
    shift = clock["offset_ms"] * 1e6 if clock else 0.0
    seg_starts = [s[0] for s in segments]
    for prog in view["programs"].values():
        under = []
        for run in prog["runs"]:
            mid = run["start_ns"] + shift + run["ms"] * 5e5
            i = bisect.bisect_right(seg_starts, mid) - 1
            under.append(segments[i][2] if i >= 0 and mid < segments[i][1]
                         else OUTSIDE)
        prog["under"] = statistics.mode(under)
    busy = _merge((s + shift, s + d + shift)
                  for _, s, d, _ in (ops or modules))
    view["idle"] = _idle(busy, marks, segments)
    return view


def load_device_view(path_or_dir: str, window: str = None) -> dict:
    """`device_view` of the trace at `path_or_dir` (a file, or a trace
    directory's newest `.xplane.pb`), with its `path`."""
    path = find_trace(path_or_dir)
    view = device_view(load(path), window)
    view["path"] = path
    return view


# ---------------------------------------------------------------- the table
def format_view(view: dict, top: int = 12) -> str:
    out = []
    spans = view["spans"]
    if spans:
        out.append(f"{'program span':<28} {'n':>6} {'total ms':>11} "
                   f"{'mean ms':>9} {'self ms':>11}")
        for name, r in sorted(spans.items(), key=lambda kv: -kv[1]["total_ms"]):
            out.append(f"{name:<28} {r['n']:>6} {r['total_ms']:>11.3f} "
                       f"{r['mean_ms']:>9.3f} {r['self_ms']:>11.3f}")
    if view["device"] is None:
        out.append("no device plane in this trace: nothing ran on a TPU "
                   "while it recorded")
        return "\n".join(out)
    out.append("")
    out.append(f"{view['device']} ({view['chips']} chip(s) traced)")
    out.append(f"{'program (XLA Modules)':<44} {'runs':>5} {'median ms':>10} "
               f"{'min':>9} {'max':>9}  mostly under")
    programs = sorted(view["programs"].items(),
                      key=lambda kv: -kv[1]["total_ms"])
    for name, p in programs:
        out.append(f"{name[:44]:<44} {p['n']:>5} {p['median_ms']:>10.3f} "
                   f"{p['min_ms']:>9.3f} {p['max_ms']:>9.3f}  {p['under']}")
    for name, p in programs[:4]:
        if not p["by_scope_ms"]:
            continue
        out.append("")
        out.append(f"own device ms a run by scope: {name[:60]}")
        rows = sorted(p["by_scope_ms"].items(), key=lambda kv: -kv[1])
        for scope, ms in rows[:top]:
            out.append(f"  {scope[:56]:<56} {ms:>9.3f}")
        if len(rows) > top:
            rest = sum(ms for _, ms in rows[top:])
            out.append(f"  {'(' + str(len(rows) - top) + ' more)':<56} "
                       f"{rest:>9.3f}")
    clock = view["clock"]
    out.append("")
    if clock is None:
        out.append(f"clock: no {TICK} with its {DISPATCH} / {PULL} around "
                   "one program run; device times are not shifted")
    else:
        out.append(
            f"clock over {clock['ticks']} decode ticks: the device line is "
            f"{clock['offset_ms']:+.3f} ms against the host's, within "
            f"[{clock['offset_low_ms']:+.3f}, {clock['offset_high_ms']:+.3f}]"
            f" (+-{clock['error_ms']:.3f} ms"
            + ("" if clock["consistent"] else "; the bounds cross: the "
               "clocks drift by more than the ticks' slack") + ")")
        out.append(
            f"  launch {clock['launch_ms_median']:.3f} ms + program "
            f"{clock['program_ms_median']:.3f} ms + return "
            f"{clock['return_ms_median']:.3f} ms (medians; dispatch opening "
            f"-> pull closing {clock['dispatch_to_pull_ms_median']:.3f} ms)")
    idle = view["idle"]
    out.append("")
    out.append(f"device idle {idle['idle_share']:.2f}% of "
               f"{idle['window_ms']:.1f} ms ({idle['window']}), by the "
               "innermost program span:")
    for name, ms in idle["by_span_ms"].items():
        share = 100.0 * ms / idle["idle_ms"] if idle["idle_ms"] else 0.0
        out.append(f"  {name:<28} {ms:>11.3f} ms {share:>6.1f}%")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) not in (1, 2):
        print("usage: python -m paddle_tpu.profiler.device_trace "
              "<xplane.pb or trace dir> [window span]", file=sys.stderr)
        return 2
    print(format_view(load_device_view(*argv)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
