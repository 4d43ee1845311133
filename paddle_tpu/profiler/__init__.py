"""paddle_tpu.profiler — tracing/profiling subsystem.

Reference analog: python/paddle/profiler/profiler.py:340 (`Profiler` with
scheduler states), utils.py:37 (`RecordEvent`), profiler_statistic.py (stats
tables), timer.py (throughput/ips benchmark auto-attached to DataLoader);
C++ substrate paddle/fluid/platform/profiler/ (RecordEvent spans into a
host-event recorder + CUPTI tracer, chrome-trace export).

TPU-native design — ONE recorder, `RecordEvent`, seen from two sides:
- Host spans: every `RecordEvent` lands in a bounded process-local ring
  (`SPAN_RING` records: name, start, duration, nesting depth, thread, the
  span's counts, and whether a profiler session was recording). On TPU the
  host side is dispatch/scheduling work; this is what `summary()` tabulates,
  what the flight recorder dumps and what the benchmark's per-layer readers
  ask for (`get_profiler_spans()`).
- Device/XLA trace: the same `RecordEvent` is a
  `jax.profiler.TraceAnnotation` carrying the same counts as the event's
  stats, so while a profiler session runs (`jax.profiler.start_trace`, this
  module's `Profiler(trace_dir=...)`, the benchmark's `--trace 1`) the span
  sits on the device trace's own clock beside the XLA ops. "Tracing on"
  means exactly that — a session is recording; there is no other switch.
  With none running an annotation records nothing and a span costs one
  `deque.append`. `device_trace` reads the file a session wrote: the
  device's programs, their scopes and idle gaps beside these spans on one
  clock (`load_profiler_result`, `Profiler.summary()`).
  `export_chrome_trace(path)` additionally renders the host spans as a
  standalone chrome-trace JSON (Perfetto / chrome://tracing), written
  beside the device trace on Profiler.stop().

Runtime telemetry substrate (docs/observability.md): `monitor` is the
thread-safe counter/gauge registry (platform/monitor.h analog) the
instrumented hot paths publish into; `telemetry` is the batched
step-metrics JSONL pipeline; `flight_recorder` is the crash black box.
"""
from __future__ import annotations

import collections
import enum
import json
import threading
import time
from typing import Callable, Iterable, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .timer import benchmark  # noqa: F401  (reference: profiler/timer.py)
from . import monitor  # noqa: F401  (reference: platform/monitor.h)


class ProfilerState(enum.Enum):
    """Scheduler states (reference profiler.py:79)."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1          # accepted for API compat; mapped onto the device trace
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-indexed state machine: skip_first CLOSED steps, then cycles of
    [closed CLOSED, ready READY, record RECORD(last=RECORD_AND_RETURN)],
    `repeat` times (0 = forever). Reference: profiler.py make_scheduler."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_scheduler(_step: int) -> ProfilerState:
    return ProfilerState.RECORD


# ------------------------------------------------------------- span recorder
SPAN_RING = 65536       # completed spans kept; a server's life is unbounded


class Span(collections.namedtuple(
        "Span", "name start dur_s depth tid counts in_trace")):
    """One completed span: perf_counter start and duration in seconds,
    nesting depth on its thread (a span's parent is the enclosing span
    of depth - 1 on the same tid), the span's counts (dict or None) and
    whether a profiler session was recording when it began. A tuple
    still: the first five fields are the historical record."""
    __slots__ = ()


class _SpanLog:
    """Process-local ring of completed `Span`s (the HostEventRecorder
    analog). Writers only `deque.append`, which is atomic — no lock on
    the hot path; readers copy through `snapshot()`."""

    def __init__(self):
        self._tls = threading.local()
        self.spans = collections.deque(maxlen=SPAN_RING)
        self.enabled = True

    def depth(self) -> int:
        return getattr(self._tls, "depth", 0)

    def push(self):
        self._tls.depth = self.depth() + 1

    def pop(self, name: str, start: float, dur_s: float, counts=None,
            in_trace: bool = False):
        d = self.depth() - 1
        self._tls.depth = d
        if self.enabled:
            self.spans.append(Span(name, start, dur_s, d,
                                   threading.get_ident(), counts, in_trace))

    def snapshot(self) -> list:
        """The ring copied, oldest first. Another thread (a concurrent
        router's worker, the checkpoint thread) may append mid-copy, and
        a deque refuses to be iterated then: copy again."""
        while True:
            try:
                return list(self.spans)
            except RuntimeError:
                continue

    def clear(self):
        self.spans.clear()


_LOG = _SpanLog()


class RecordEvent:
    """Span context manager / decorator (reference utils.py:37). The
    keyword arguments are the span's COUNTS (ints, floats, short
    strings): they ride the `TraceAnnotation` into the device trace as
    the event's stats and sit in the span's ring record; `set(**counts)`
    adds the ones known only inside. Once open, `in_trace` says whether
    a profiler session was recording and `start_s` is the perf_counter
    reading it opened at; after exit `dur_s` holds the duration and
    `end_s` the reading it ended at, so a call site needs no clock pair
    of its own."""

    def __init__(self, name: str, event_type=None, **counts):
        self.name = name
        self.counts = counts or None
        self.in_trace = False
        self.start_s = None
        self.dur_s = None
        self.end_s = None
        self._annot = None              # the open annotation, else None

    def begin(self):
        self.in_trace = _TraceAnnotation.is_enabled()
        self.start_s = time.perf_counter()
        _LOG.push()
        self._annot = _TraceAnnotation(self.name, **(self.counts or {}))
        self._annot.__enter__()
        return self

    def set(self, **counts):
        """Counts known only inside the span (tokens emitted, ...)."""
        if self.counts is None:
            self.counts = counts
        else:
            self.counts.update(counts)
        if self._annot is not None:
            self._annot.set_metadata(**counts)

    def end(self):
        if self._annot is None:
            return
        self._annot.__exit__(None, None, None)
        self._annot = None
        self.end_s = time.perf_counter()
        self.dur_s = self.end_s - self.start_s
        _LOG.pop(self.name, self.start_s, self.dur_s, self.counts,
                 self.in_trace)

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with RecordEvent(self.name, **(self.counts or {})):
                return fn(*a, **k)
        return wrapped


def export_chrome_trace(path: str, spans=None) -> str:
    """Write the completed host spans as a chrome-trace JSON file
    (reference ChromeTracingLogger, chrometracing_logger.h:31): complete
    "X" events with microsecond ts/dur keyed by pid/tid, loadable in
    Perfetto / chrome://tracing and by TensorBoard's trace viewer. The
    jax.profiler device trace (when a trace dir is active) is a separate
    TensorBoard artifact; this file covers the HOST side — dispatch,
    checkpoint IO, launcher phases — with zero device involvement.

    Atomic tmp+rename write; returns `path`."""
    import os
    spans = _LOG.snapshot() if spans is None else spans
    pid = os.getpid()
    events = []
    for rec in list(spans):
        name, start, dur = rec[0], rec[1], rec[2]
        tid = rec[4] if len(rec) > 4 else 0
        event = {
            "name": name, "ph": "X", "cat": "host",
            "ts": round(start * 1e6, 3), "dur": round(dur * 1e6, 3),
            "pid": pid, "tid": tid,
        }
        if len(rec) > 5 and rec[5]:
            event["args"] = dict(rec[5])
        events.append(event)
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": {"producer": "paddle_tpu.profiler"}}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp-{pid}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory: configures the XLA trace dir (TensorBoard /
    chrome-trace loadable — reference ChromeTracingLogger analog)."""

    def handler(prof: "Profiler"):
        prof._trace_dir = dir_name
    handler._trace_dir = dir_name
    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """Alias of export_chrome_tracing: the jax trace IS a protobuf dump."""
    return export_chrome_tracing(dir_name, worker_name)


class Profiler:
    """Reference-shaped profiler (profiler.py:340).

    with profiler.Profiler(scheduler=(2, 5)) as p:
        for batch in loader:
            train_step(...)
            p.step()
    print(p.summary())
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, timer_only: bool = False,
                 trace_dir: Optional[str] = None):
        if scheduler is None:
            self._schedule = _default_scheduler
        elif callable(scheduler):
            self._schedule = scheduler
        else:  # (start, end) step-range tuple, reference-accepted form
            lo, hi = scheduler
            self._schedule = make_scheduler(closed=lo, ready=0, record=hi - lo,
                                            repeat=1)
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        self._trace_dir = trace_dir
        if on_trace_ready is not None:
            td = getattr(on_trace_ready, "_trace_dir", None)
            if td:
                self._trace_dir = td
        self._on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_tracing = False
        self._step_times = []
        self._last_step_t = None

    # -------------------------------------------------------------- control
    def start(self):
        benchmark().begin()
        self.current_state = self._schedule(self.step_num)
        self._sync_device_trace()
        self._last_step_t = time.perf_counter()
        return self

    def stop(self):
        if self._device_tracing:
            import jax
            jax.profiler.stop_trace()
            self._device_tracing = False
        benchmark().end()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        if self._trace_dir is not None and not self.timer_only:
            # host spans beside the jax.profiler device trace: one
            # Perfetto/chrome://tracing-loadable JSON per process
            import os
            try:
                export_chrome_trace(os.path.join(
                    self._trace_dir, f"host_trace.{os.getpid()}.json"))
            except OSError:
                pass
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        benchmark().step(num_samples)
        self.step_num += 1
        prev = self.current_state
        self.current_state = self._schedule(self.step_num)
        if prev != self.current_state:
            self._sync_device_trace()

    def _sync_device_trace(self):
        want = (self.current_state in (ProfilerState.RECORD,
                                       ProfilerState.RECORD_AND_RETURN)
                and self._trace_dir is not None and not self.timer_only)
        if want and not self._device_tracing:
            import jax
            jax.profiler.start_trace(self._trace_dir)
            self._device_tracing = True
        elif not want and self._device_tracing:
            import jax
            jax.profiler.stop_trace()
            self._device_tracing = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------- reporting
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms") -> str:
        """Host-span stats table + step-time stats (the reference's
        profiler_statistic tables, host side), and, where this
        profiler's `trace_dir` holds an `.xplane.pb`, the device view of
        the newest one (`device_trace`: programs, scopes, the clock, idle
        by innermost span)."""
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)
        agg = {}
        for name, _start, dur, *_rest in _LOG.snapshot():
            c, tot, mx = agg.get(name, (0, 0.0, 0.0))
            agg[name] = (c + 1, tot + dur, max(mx, dur))
        lines = [f"{'name':<40} {'calls':>6} {'total':>10} {'avg':>10} "
                 f"{'max':>10}  ({time_unit})"]
        for name, (c, tot, mx) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40} {c:>6} {tot * unit:>10.3f} "
                         f"{tot / c * unit:>10.3f} {mx * unit:>10.3f}")
        if self._step_times:
            st = sorted(self._step_times)
            n = len(st)
            lines.append("")
            lines.append(
                f"steps: {n}  avg {sum(st) / n * unit:.3f}{time_unit}  "
                f"p50 {st[n // 2] * unit:.3f}{time_unit}  "
                f"min {st[0] * unit:.3f}{time_unit}  "
                f"max {st[-1] * unit:.3f}{time_unit}")
        if self._trace_dir is not None:
            from . import device_trace
            try:
                view = device_trace.load_device_view(self._trace_dir)
            except FileNotFoundError:       # no session wrote there yet
                pass
            else:
                lines += ["", f"trace {view['path']}",
                          device_trace.format_view(view)]
        return "\n".join(lines)

    @property
    def step_times(self):
        return list(self._step_times)


def get_profiler_spans():
    """The ring's completed spans, oldest first:
    [Span(name, start, dur_s, depth, tid, counts, in_trace), ...]."""
    return _LOG.snapshot()


def clear_profiler_spans():
    _LOG.clear()


def load_profiler_result(filename: str, window: str = None) -> dict:
    """The device view of a profiler trace (reference profiler.py
    `load_profiler_result`): `filename` is an `.xplane.pb` or the trace
    directory a session wrote (`Profiler(trace_dir=...)`, the newest
    trace under it). Returns `device_trace.load_device_view`'s dict —
    programs of the `XLA Modules` line, own device time by scope, the
    device line's offset against the host's with launch and return
    latency, idle gaps by the innermost program span (over `window`, the
    name of a host span the caller put around what it measures; first to
    last device event without one), the spans' self times — which
    `device_trace.format_view` prints."""
    from .device_trace import load_device_view
    return load_device_view(filename, window)


def cost_analysis(fn, *example_args, **jit_kwargs):
    """XLA's own static cost model for a jitted callable (reference
    analog: paddle/fluid/framework/ir/cost_model.py + the profiler's op
    FLOPs accounting). Returns a dict with flops, bytes accessed, and
    (when the backend reports it) optimal_seconds — computable without
    running the program, so it works even when no accelerator is
    reachable. Use it to sanity-check an MFU measurement: measured_time /
    (flops / peak_flops) is the achievable-vs-actual gap.

    Caveat: XLA counts a lax.scan/while body ONCE, not per iteration —
    for scan-stacked models (models.gpt) the reported flops are a lower
    bound; multiply the body's share by the trip count for truth."""
    import jax
    compiled = jax.jit(fn, **jit_kwargs).lower(*example_args).compile()
    raw = compiled.cost_analysis()
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else {}
    out = {"flops": float(raw.get("flops", 0.0)),
           "bytes_accessed": float(raw.get("bytes accessed", 0.0)),
           "optimal_seconds": float(raw.get("optimal_seconds", 0.0))}
    # mem_audit is THE home for compiled-memory reads; same historical
    # output keys (temp/argument/output_size_bytes) plus its extras
    from .mem_audit import compiled_memory_stats
    out.update(compiled_memory_stats(compiled))
    out["raw"] = dict(raw)
    return out


def __getattr__(name):
    # telemetry / flight_recorder pull in jax lazily; loading them only
    # on attribute access keeps `import paddle_tpu.profiler` backend-free
    # (serving_telemetry / tracing / slo are jax-free but ride the same
    # lazy seam so the profiler package stays import-light)
    if name in ("telemetry", "flight_recorder", "serving_telemetry",
                "tracing", "slo", "hlo_audit", "mem_audit",
                "device_trace"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class SortedKeys(enum.Enum):
    """reference profiler_statistic.py:49 — summary-table sort keys."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    """reference profiler.py:46 — summary views. `Profiler.summary()`
    prints the host spans (OverView) and, where its trace directory holds
    a trace, `device_trace`'s tables: the programs (DeviceView) and
    their own time by scope and kernel (KernelView)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
