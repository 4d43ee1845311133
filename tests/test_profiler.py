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


# ------------------------------------------------------------ the device view
HOST_T0_NS = 1_000_000          # the host line's own timestamp
DEVICE_EARLY_MS = 0.5           # the device line sits this early
HOST_SPANS = [                  # (name, start ms, end ms) on the host's clock
    ("bench.traced", 10, 110),
    ("$noise.py:1 f", 0, 200),                 # not a program span
    ("serving.router_tick", 12, 40), ("serving.tick", 13, 39),
    ("serving.admit", 14, 24), ("serving.prefill", 15, 23),
    ("serving.upload", 24.5, 25.5),
    ("serving.decode_tick", 26, 36), ("serving.decode_dispatch", 26, 27),
    ("serving.decode_pull", 27.2, 35.8), ("serving.emit", 36.5, 38),
    ("serving.router_tick", 50, 70), ("serving.tick", 51, 69),
    ("serving.decode_tick", 52, 62), ("serving.decode_dispatch", 52, 52.5),
    ("serving.decode_pull", 52.6, 61.9), ("serving.emit", 62.5, 64),
]
PREFILL, TICK_PROGRAM = "jit_prefill(111)", "jit__decode_tick(222)"
TICK_SCOPE = "jit(_decode_tick)/while/body/closed_call/"
MODULES = [(PREFILL, 16, 22), (TICK_PROGRAM, 27.5, 35.5),
           (TICK_PROGRAM, 52.8, 60.8)]        # on the HOST's clock
OPS = [                         # (name, start, end, tf_op), host's clock
    ("%fusion.1 = bf16[8,64]{1,0} fusion(%p)", 16, 19,
     "jit(prefill)/attention/dot_general:"),
    ("%while.2 = (s32[]) while(%t)", 19, 22, "jit(prefill)/while:"),
    ("%fusion.3 = bf16[8,64]{1,0} fusion(%q)", 19.5, 21,
     "jit(prefill)/while/body/closed_call/mlp/dot_general:"),
    ("%fusion.7 = bf16[4,1,64]{2,1,0} fusion(%a)", 27.5, 30.5,
     TICK_SCOPE + "mlp/dot_general:"),
    ("%decode_attention_live_blocks.3 = f32[4,1,4,16]{3,2,1,0} "
     "custom-call(%b)", 30.5, 33, TICK_SCOPE + "attention/pallas_call:"),
    ("%scatter.4 = bf16[2,4,64]{2,1,0} scatter(%c)", 33, 33.5,
     TICK_SCOPE + "attention/kv_update/scatter:"),
    ("%fusion.9 = f32[4,640]{1,0} fusion(%d)", 34, 35.5,
     "jit(_decode_tick)/lm_head/dot_general:"),
    ("%fusion.7 = bf16[4,1,64]{2,1,0} fusion(%a)", 52.8, 60.8,
     TICK_SCOPE + "mlp/dot_general:"),
]


def _synthetic_xspace(pb2):
    """Two programs (a prefill with a nested `while`, two decode ticks),
    ops with `tf_op` in their metadata (one by `ref_value`), and the
    program's spans on a host line whose clock runs 0.5 ms ahead."""
    space = pb2.XSpace()
    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="python", timestamp_ns=HOST_T0_NS)
    ids = {}
    for name, start, end in HOST_SPANS:
        mid = ids.setdefault(name, len(ids) + 1)
        host.event_metadata[mid].id = mid
        host.event_metadata[mid].name = name
        line.events.add(metadata_id=mid,
                        offset_ps=int(start * 1e9) - HOST_T0_NS * 1000,
                        duration_ps=int((end - start) * 1e9))
    device = space.planes.add(name="/device:TPU:0")
    device.stat_metadata[1].name = "tf_op"
    device.stat_metadata[2].name = OPS[0][3]       # reached by ref_value

    def add(line, rows):
        for name, start, end, *tf_op in rows:
            key = (name, *tf_op)
            if key not in ids:
                ids[key] = mid = len(ids) + 1
                meta = device.event_metadata[mid]
                meta.id, meta.name = mid, name
                if tf_op and tf_op[0] == OPS[0][3]:
                    meta.stats.add(metadata_id=1, ref_value=2)
                elif tf_op:
                    meta.stats.add(metadata_id=1, str_value=tf_op[0])
            line.events.add(
                metadata_id=ids[key],
                offset_ps=int((start - DEVICE_EARLY_MS) * 1e9),
                duration_ps=int((end - start) * 1e9))
    add(device.lines.add(name="XLA Modules"), MODULES)
    add(device.lines.add(name="XLA Ops"), OPS)
    device.lines.add(name="Steps")                 # a line nobody reads
    space.planes.add(name="/device:TPU:1")         # a chip that ran nothing
    return space


class TestDeviceView:
    @pytest.fixture
    def trace_dir(self, tmp_path):
        from paddle_tpu.profiler import device_trace
        pb2 = device_trace._xplane_pb2()
        assert pb2 is not None
        d = tmp_path / "plugins" / "profile" / "2026_10_05"
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(
            _synthetic_xspace(pb2).SerializeToString())
        return tmp_path

    def test_programs_and_own_time_by_scope(self, trace_dir):
        from paddle_tpu.profiler import load_profiler_result
        view = load_profiler_result(str(trace_dir))
        assert view["path"].endswith("vm.xplane.pb")
        assert view["device"] == "/device:TPU:0" and view["chips"] == 1
        tick, prefill = (view["programs"][k] for k in (TICK_PROGRAM, PREFILL))
        assert set(view["programs"]) == {TICK_PROGRAM, PREFILL}
        assert (tick["n"], prefill["n"]) == (2, 1)
        # the span most of a program's runs sat under tells the two apart
        assert tick["under"] == "serving.decode_pull"
        assert prefill["under"] == "serving.prefill"
        for key in ("median_ms", "min_ms", "max_ms"):
            assert tick[key] == pytest.approx(8.0)
            assert prefill[key] == pytest.approx(6.0)
        # the while's own time is its 3 ms less the 1.5 of its child
        assert prefill["by_scope_ms"] == pytest.approx(
            {"attention": 3.0, "(no scope)": 1.5, "mlp": 1.5})
        # a tick: mean over the two runs; the kernel under its own name
        assert tick["by_scope_ms"] == pytest.approx({
            "mlp": (3.0 + 8.0) / 2,
            "attention/decode_attention_live_blocks": 1.25,
            "attention/kv_update": 0.25, "lm_head": 0.75})
        first, second = tick["runs"]
        assert first["by_scope"] == pytest.approx({
            "mlp": 3.0, "attention/decode_attention_live_blocks": 2.5,
            "attention/kv_update": 0.5, "lm_head": 1.5})
        assert second["by_scope"] == pytest.approx({"mlp": 8.0})
        assert first["ms"] == pytest.approx(8.0)

    def test_the_clock_and_the_two_latencies(self, trace_dir):
        from paddle_tpu.profiler import load_profiler_result
        clock = load_profiler_result(str(trace_dir))["clock"]
        # tick 1: dispatch 26 - module 27.0 = -1.0; pull 35.8 - 35.0 = 0.8
        # tick 2: dispatch 52 - module 52.3 = -0.3; pull 61.9 - 60.3 = 1.6
        assert clock["ticks"] == 2 and clock["program"] == TICK_PROGRAM
        assert clock["offset_low_ms"] == pytest.approx(-0.3)
        assert clock["offset_high_ms"] == pytest.approx(0.8)
        assert clock["offset_ms"] == pytest.approx(0.25)
        assert clock["error_ms"] == pytest.approx(0.55)
        assert clock["consistent"]
        assert clock["offset_low_ms"] <= DEVICE_EARLY_MS \
            <= clock["offset_high_ms"]
        # launch 1.25 and 0.55, return 0.55 and 1.35, at the offset applied
        assert clock["launch_ms_median"] == pytest.approx(0.9)
        assert clock["return_ms_median"] == pytest.approx(0.95)
        assert clock["program_ms_median"] == pytest.approx(8.0)
        assert clock["dispatch_to_pull_ms_median"] == pytest.approx(9.85)
        assert clock["launch_ms_median"] + clock["program_ms_median"] \
            + clock["return_ms_median"] == pytest.approx(9.85)

    def test_idle_gaps_by_the_innermost_span(self, trace_dir):
        """Busy (ops, shifted by the 0.25 applied): 15.75-21.75,
        27.25-33.25, 33.75-35.25, 52.55-60.55 of the window 10-110, which
        the caller names by the span it put around it; each of the five
        gaps split at the edges of the spans over it."""
        from paddle_tpu.profiler import load_profiler_result
        idle = load_profiler_result(str(trace_dir), "bench.traced")["idle"]
        assert idle["window"] == "bench.traced"
        assert idle["window_ms"] == pytest.approx(100.0)
        assert idle["busy_ms"] == pytest.approx(21.5)
        assert idle["idle_share"] == pytest.approx(78.5)
        assert idle["by_span_ms"] == pytest.approx({
            "(outside the program)": 2 + 10 + 40,
            "serving.router_tick": 4.0,
            "serving.tick": 1 + .5 + .5 + .5 + 1 + 1 + .5 + 5,
            "serving.admit": 2.0,
            "serving.prefill": .75 + 1.25,
            "serving.upload": 1.0,
            "serving.decode_dispatch": 1 + .5,
            "serving.decode_tick": .2 + .2 + .05 + .1,
            "serving.decode_pull": .05 + .5 + .55 + 1.35,
            "serving.emit": 3.0})
        assert sum(idle["by_span_ms"].values()) == pytest.approx(78.5)

    @pytest.mark.parametrize("window", [None, "no.such.span"])
    def test_without_a_window_idle_is_first_to_last_device_event(
            self, trace_dir, window):
        """The view knows no caller's mark by name: with no window (or a
        name the trace does not hold) the three gaps between 15.75 and
        60.55 are the idle time, and only what lies between the two router
        ticks (40-50) is outside the program."""
        from paddle_tpu.profiler import load_profiler_result
        idle = load_profiler_result(str(trace_dir), window)["idle"]
        assert idle["window"] == "first to last device event"
        assert idle["window_ms"] == pytest.approx(44.8)
        assert idle["busy_ms"] == pytest.approx(21.5)
        assert idle["idle_ms"] == pytest.approx(5.5 + 0.5 + 17.3)
        assert idle["idle_share"] == pytest.approx(100 * 23.3 / 44.8)
        assert idle["by_span_ms"]["(outside the program)"] \
            == pytest.approx(10.0)
        assert sum(idle["by_span_ms"].values()) == pytest.approx(23.3)

    def test_the_spans_self_times_sum_to_the_router_ticks(self, trace_dir):
        from paddle_tpu.profiler import load_profiler_result
        spans = load_profiler_result(str(trace_dir))["spans"]
        assert "bench.traced" not in spans and "$noise.py:1 f" not in spans
        self_ms = {k: v["self_ms"] for k, v in spans.items()}
        assert self_ms == pytest.approx({
            "serving.router_tick": 4.0, "serving.tick": 3.5 + 6.5,
            "serving.admit": 2.0, "serving.prefill": 8.0,
            "serving.upload": 1.0, "serving.decode_tick": 0.6,
            "serving.decode_dispatch": 1.5, "serving.decode_pull": 17.9,
            "serving.emit": 3.0})
        assert spans["serving.router_tick"]["total_ms"] == pytest.approx(48.0)
        assert sum(self_ms.values()) == pytest.approx(48.0)
        assert spans["serving.decode_tick"]["n"] == 2
        assert spans["serving.decode_tick"]["mean_ms"] == pytest.approx(10.0)

    def test_the_printed_table_and_the_command(self, trace_dir, capsys):
        from paddle_tpu.profiler import device_trace
        assert device_trace.main([str(trace_dir)]) == 0
        assert "device idle 52.01% of 44.8 ms (first to last device event)" \
            in capsys.readouterr().out
        assert device_trace.main([str(trace_dir), "bench.traced"]) == 0
        text = capsys.readouterr().out
        for piece in (TICK_PROGRAM, "attention/decode_attention_live_blocks",
                      "+0.250 ms", "launch 0.900 ms", "return 0.950 ms",
                      "device idle 78.50%", "(outside the program)",
                      "serving.decode_pull"):
            assert piece in text
        assert device_trace.main([]) == 2
        with pytest.raises(FileNotFoundError):
            device_trace.load_device_view(str(trace_dir / "plugins" / "nope"))

    def test_without_xplane_pb2_everything_but_the_scopes(
            self, trace_dir, monkeypatch):
        from paddle_tpu.profiler import device_trace, load_profiler_result
        whole = load_profiler_result(str(trace_dir), "bench.traced")
        monkeypatch.setattr(device_trace, "_xplane_pb2", lambda: None)
        view = load_profiler_result(str(trace_dir), "bench.traced")
        # ProfileData rounds a start to whole nanoseconds
        assert view["clock"] == pytest.approx(whole["clock"], abs=1e-5)
        assert view["idle"]["by_span_ms"] == pytest.approx(
            whole["idle"]["by_span_ms"], abs=1e-5)
        assert view["programs"][TICK_PROGRAM]["median_ms"] \
            == pytest.approx(8.0)
        assert view["programs"][TICK_PROGRAM]["by_scope_ms"] \
            == pytest.approx({"(no scope)": (7.5 + 8.0) / 2})

    def test_scope_of_a_tf_op_path(self):
        from paddle_tpu.profiler.device_trace import scope_of
        assert scope_of("jit(<unknown>)/while/body/closed_call/attention/"
                        "kv_update/scatter:") == "attention/kv_update"
        assert scope_of("jit(step)/jit(main)/transpose(jvp(attention))/"
                        "dot_general:") == "attention"
        assert scope_of("jit(step)/checkpoint/mlp/dot_general:") == "mlp"
        assert scope_of("jit(f)/add:") == "(no scope)"
        assert scope_of("") == "(no scope)"
        assert scope_of("jit(f)/ce_head/pallas_call:",
                        "%ce_fwd.1 = f32[8]{0} custom-call(%x)") \
            == "ce_head/ce_fwd"

    def test_a_cpu_trace_has_no_device_plane_and_says_so(self, tmp_path):
        """A real session on the CPU: the summary gains the view, which
        degrades to the program's spans and "no device plane"."""
        import jax.numpy as jnp
        from paddle_tpu.profiler import load_profiler_result
        clear_profiler_spans()
        with Profiler(trace_dir=str(tmp_path)) as p:
            with RecordEvent("serving.tick"):
                with RecordEvent("serving.decode_tick", active=1, slots=2):
                    jnp.ones((8, 8)).sum().block_until_ready()
        view = load_profiler_result(str(tmp_path))
        assert view["device"] is None and view["programs"] == {}
        assert view["clock"] is None and view["idle"] is None
        assert view["spans"]["serving.tick"]["n"] == 1
        assert view["spans"]["serving.decode_tick"]["self_ms"] > 0
        text = p.summary()
        assert "no device plane" in text and "serving.decode_tick" in text
        # a profiler with no trace directory keeps the host table alone
        assert "no device plane" not in Profiler().summary()
