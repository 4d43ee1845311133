"""Profiler subsystem tests.

Reference analog: test coverage for python/paddle/profiler (scheduler state
machine, RecordEvent spans, stats summary, timer ips).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import (Profiler, ProfilerState, ProfilerTarget,
                                 RecordEvent, make_scheduler,
                                 export_chrome_tracing, get_profiler_spans,
                                 clear_profiler_spans, benchmark)


class TestScheduler:
    def test_make_scheduler_cycle(self):
        s = make_scheduler(closed=1, ready=1, record=2, repeat=1)
        states = [s(i) for i in range(6)]
        assert states[:4] == [ProfilerState.CLOSED, ProfilerState.READY,
                              ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN]
        assert states[4] == ProfilerState.CLOSED      # repeat=1 exhausted
        assert states[5] == ProfilerState.CLOSED

    def test_skip_first(self):
        s = make_scheduler(closed=0, ready=0, record=1, skip_first=3)
        assert s(2) == ProfilerState.CLOSED
        assert s(3) == ProfilerState.RECORD_AND_RETURN

    def test_repeat_forever(self):
        s = make_scheduler(closed=1, ready=0, record=1, repeat=0)
        assert s(101) == ProfilerState.RECORD_AND_RETURN


class TestRecordEvent:
    def test_spans_recorded_with_nesting(self):
        clear_profiler_spans()
        with RecordEvent("outer"):
            with RecordEvent("inner"):
                time.sleep(0.01)
        spans = get_profiler_spans()
        names = {s[0] for s in spans}
        assert names == {"outer", "inner"}
        by = {s[0]: s for s in spans}
        assert by["inner"][3] == 1          # depth
        assert by["outer"][3] == 0
        assert by["inner"][2] >= 0.009      # duration
        assert by["outer"][2] >= by["inner"][2]

    def test_decorator_form(self):
        clear_profiler_spans()

        @RecordEvent("fn_span")
        def f(x):
            return x + 1

        assert f(1) == 2
        assert any(s[0] == "fn_span" for s in get_profiler_spans())

    def test_begin_end_form(self):
        clear_profiler_spans()
        ev = RecordEvent("manual")
        ev.begin()
        ev.end()
        assert any(s[0] == "manual" for s in get_profiler_spans())


class TestSpanRing:
    """The one recorder's record (docs/observability.md "Traces"):
    (name, start, dur_s, depth, tid, counts, in_trace) in a bounded
    ring, the counts in the device trace as the event's stats."""

    def test_the_ring_is_bounded(self):
        from paddle_tpu import profiler
        clear_profiler_spans()
        for i in range(profiler.SPAN_RING + 10):
            with RecordEvent("flood", i=i):
                pass
        spans = get_profiler_spans()
        assert len(spans) == profiler.SPAN_RING
        assert spans[0][5]["i"] == 10           # the oldest fell out
        assert spans[-1][5]["i"] == profiler.SPAN_RING + 9
        clear_profiler_spans()
        assert get_profiler_spans() == []

    def test_a_copy_survives_appends_from_other_threads(self):
        """Readers copy the ring while a concurrent router's workers, the
        checkpoint thread or the watchdog append to it."""
        import threading
        clear_profiler_spans()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                with RecordEvent("bg"):
                    pass
        threads = [threading.Thread(target=writer) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                assert all(s.name == "bg" for s in get_profiler_spans())
        finally:
            stop.set()
            for t in threads:
                t.join()
        clear_profiler_spans()

    def test_counts_duration_and_the_five_field_prefix(self):
        import threading
        clear_profiler_spans()
        with RecordEvent("outer", slot=3, kind="dense") as outer:
            with RecordEvent("inner") as inner:
                time.sleep(0.002)
            outer.set(tokens=7)
        inner_rec, outer_rec = get_profiler_spans()
        assert len(outer_rec) == 7
        assert outer_rec._fields == ("name", "start", "dur_s", "depth",
                                     "tid", "counts", "in_trace")
        assert outer_rec.counts is outer_rec[5] and not outer_rec.in_trace
        name, start, dur, depth, tid = outer_rec[:5]
        assert (name, depth, tid) == ("outer", 0, threading.get_ident())
        assert dur == outer.dur_s >= inner.dur_s >= 0.002
        assert start <= inner_rec[1]
        assert outer_rec[5] == {"slot": 3, "kind": "dense", "tokens": 7}
        assert inner_rec[3] == 1 and inner_rec[5] is None
        # consumers that unpack the historical prefix keep working
        for (n, s, d, dep, *_t) in get_profiler_spans():
            assert isinstance(n, str) and d >= 0 and dep in (0, 1)

    def test_in_trace_and_counts_reach_the_xplane(self, tmp_path):
        """Tracing on means a profiler session is recording: in_trace
        is sampled from it, and the counts arrive in the .xplane.pb as
        the host event's stats."""
        import glob
        import jax
        from jax.profiler import ProfileData
        clear_profiler_spans()
        with RecordEvent("before"):
            pass
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with RecordEvent("traced.span", true_len=5, bucket=8) as ev:
                ev.set(tokens=2)
        finally:
            jax.profiler.stop_trace()
        with RecordEvent("after"):
            pass
        flags = {s[0]: s[6] for s in get_profiler_spans()}
        assert flags == {"before": False, "traced.span": True,
                         "after": False}
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name == "traced.span"]
        assert len(found) == 1
        stats = {k: int(v) for k, v in found[0].items()
                 if k in ("true_len", "bucket", "tokens")}
        assert stats == {"true_len": 5, "bucket": 8, "tokens": 2}

    def test_chrome_trace_carries_the_counts(self, tmp_path):
        import json
        from paddle_tpu.profiler import export_chrome_trace
        clear_profiler_spans()
        with RecordEvent("with_counts", n=4):
            pass
        with RecordEvent("bare"):
            pass
        path = export_chrome_trace(str(tmp_path / "host.json"))
        events = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
        assert events["with_counts"]["args"] == {"n": 4}
        assert "args" not in events["bare"]


class TestProfiler:
    def test_step_loop_and_summary(self):
        clear_profiler_spans()
        with Profiler(targets=[ProfilerTarget.CPU]) as p:
            for _ in range(4):
                with RecordEvent("train_step"):
                    np.dot(np.ones((64, 64)), np.ones((64, 64)))
                p.step(num_samples=32)
        assert p.step_num == 4
        assert len(p.step_times) == 4
        s = p.summary()
        assert "train_step" in s
        assert "steps: 4" in s

    def test_scheduler_tuple_form(self):
        p = Profiler(scheduler=(1, 3))
        p.start()
        assert p.current_state == ProfilerState.CLOSED
        p.step()
        assert p.current_state in (ProfilerState.RECORD,
                                   ProfilerState.RECORD_AND_RETURN)
        p.stop()

    def test_chrome_tracing_configures_dir(self, tmp_path):
        p = Profiler(on_trace_ready=export_chrome_tracing(str(tmp_path)),
                     timer_only=True)
        assert p._trace_dir == str(tmp_path)

    def test_lazy_namespace(self):
        assert paddle.profiler.Profiler is Profiler


class TestTimer:
    def test_benchmark_ips(self):
        bm = benchmark()
        bm.reset()
        bm.begin()
        for _ in range(5):
            time.sleep(0.002)
            bm.step(num_samples=10)
        bm.end()
        s = bm.summary(skip=1)
        assert s["steps"] == 4
        assert s["ips"] > 0
        assert s["avg_batch_cost_s"] >= 0.002

    def test_dataloader_reader_cost_hook(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return np.float32(i)

        bm = benchmark()
        bm.reset()
        bm.begin()
        n = 0
        for _batch in DataLoader(DS(), batch_size=4):
            bm.step(num_samples=4)
            n += 1
        assert n == 2
        s = bm.summary(skip=0)
        assert "avg_reader_cost_s" in s
