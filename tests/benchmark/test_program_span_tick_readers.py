"""The readers behind the four metrics of ISSUE 39 — a phase's share of
the program's traced time, the mean of a count over the spans that
qualify, and the two that reuse `program_span_ms` — on span lists made by
hand, and on tiny serving cells driven on the CPU."""
import json

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import load_reader, read_metrics
from benchmark.readers import (program_span_count_mean, program_span_ms,
                               program_span_time_share)
from benchmark.tiny import tiny_cell
from paddle_tpu import profiler
from paddle_tpu.profiler import Span

SEED = 2 ** 31 + 98765
NEW = {"prefill_time_share.serve": "%", "decode_stall_ms_mean.serve": "ms",
       "decode_dispatch_ms_p50.serve": "ms", "emit_ms_mean.serve": "ms"}
SERVING_CELLS = ["gpt3-1.3b-serve.offline", "gpt3-1.3b-serve.chat",
                 "command-a-plus-serve.rag-offline",
                 "jamba2-3b-serve.reason-offline",
                 "joyai-llm-flash-serve.longdoc-offline"]


def _span(name, start_ms, dur_ms, depth=0, tid=1, counts=None, traced=True):
    return Span(name, start_ms / 1e3, dur_ms / 1e3, depth, tid, counts,
                traced)


@pytest.fixture
def spans(monkeypatch):
    """Hand the readers a span list in place of the program's ring."""
    def give(rows):
        monkeypatch.setattr(program_span_ms, "get_profiler_spans",
                            lambda: rows)
    return give


def _read(name, record=None):
    read, params = load_reader(name)
    return read(record or {}, **params)


def _tick(start_ms, dur_ms, **counts):
    return _span("serving.decode_tick", start_ms, dur_ms, 2,
                 counts={"active": 2, "slots": 4, **counts})


def test_a_phases_share_of_the_programs_traced_time(spans):
    spans([
        # three router ticks 0..40, 50..80, 100..200: the frame is 0..200
        _span("serving.router_tick", 0, 40),
        _span("serving.prefill", 5, 20, 3),
        _span("serving.router_tick", 50, 30),
        _span("serving.router_tick", 100, 100),
        _span("serving.prefill", 110, 30, 3),
        _span("serving.prefill", 150, 10, 3, tid=2),   # a worker's thread
        # outside the frame, and in no session: neither counts
        _span("serving.prefill", 300, 50, 3),
        _span("serving.prefill", 60, 10, 3, traced=False),
        _span("serving.router_tick", 400, 100, traced=False),
    ])
    assert program_span_time_share.read(
        {}, "serving.prefill", "serving.router_tick") \
        == pytest.approx(100.0 * (20 + 30 + 10) / 200)
    assert _read("prefill_time_share.serve") == pytest.approx(30.0)


def test_ticks_traced_and_no_prefill_is_nought_nothing_traced_is_none(spans):
    spans([_span("serving.router_tick", 0, 40),
           _span("serving.router_tick", 50, 30),
           _span("serving.prefill", 10, 5, 3, traced=False)])
    assert _read("prefill_time_share.serve") == 0.0
    spans([_span("serving.prefill", 10, 5, 3),
           _span("serving.router_tick", 0, 40, traced=False)])
    assert _read("prefill_time_share.serve") is None
    spans([])
    assert all(_read(name) is None for name in NEW)
    # a program whose records are bare five-field tuples (a parent commit)
    spans([("serving.router_tick", 0.0, 0.01, 0, 1),
           ("serving.decode_tick", 0.0, 0.01, 2, 1)])
    assert all(_read(name) is None for name in NEW)


def test_the_mean_wait_between_ticks_over_the_ticks_that_carried_a_row(spans):
    spans([
        _tick(0, 5),                                  # an engine's first
        _tick(10, 5, since_last_ms=2.0, carried=2),
        _tick(20, 5, since_last_ms=3.0, carried=1),
        # an admission between two ticks, on one tick in four: a median
        # would not see it, the mean does
        _tick(130, 5, since_last_ms=103.0, carried=2),
        # nobody waited: every row is new (the slots had emptied)
        _tick(900, 5, since_last_ms=700.0, carried=0),
        _tick(950, 5, since_last_ms=9.0, carried=2, ),
        _span("serving.decode_tick", 960, 5, 2, traced=False,
              counts={"since_last_ms": 5000.0, "carried": 3}),
        _span("serving.prefill", 30, 95, 3,
              counts={"since_last_ms": 1e6, "carried": 1}),
    ])
    assert _read("decode_stall_ms_mean.serve") \
        == pytest.approx((2.0 + 3.0 + 103.0 + 9.0) / 4)
    # without `where`, every span that has the count
    assert program_span_count_mean.read(
        {}, "serving.decode_tick", "since_last_ms") \
        == pytest.approx((2.0 + 3.0 + 103.0 + 700.0 + 9.0) / 5)
    # a parent's ticks carry neither count: nothing to read, never 0
    spans([_tick(0, 5), _tick(10, 5)])
    assert _read("decode_stall_ms_mean.serve") is None
    spans([_tick(0, 5, since_last_ms=4.0, carried=0)])
    assert _read("decode_stall_ms_mean.serve") is None


def test_dispatch_is_a_median_and_emission_a_mean(spans):
    spans([_span("serving.decode_dispatch", 0, 0.2, 3),
           _span("serving.decode_dispatch", 10, 0.4, 3),
           _span("serving.decode_dispatch", 20, 9.0, 3),
           _span("serving.emit", 5, 0.1, 2, counts={"tokens": 4}),
           _span("serving.emit", 15, 0.1, 2, counts={"tokens": 4}),
           _span("serving.emit", 25, 1.0, 2, counts={"tokens": 3}),
           _span("serving.emit", 35, 50.0, 2, traced=False)])
    assert _read("decode_dispatch_ms_p50.serve") == pytest.approx(0.4)
    assert _read("emit_ms_mean.serve") == pytest.approx(0.4)


def test_the_four_are_listed_for_the_five_serving_cells_and_no_other():
    from benchmark.harness import load_benchmark
    by_name = {m["name"]: m for m in load_benchmark()["per_layer"]}
    assert list(by_name)[-4:] == list(NEW)
    for name, unit in NEW.items():
        m = by_name[name]
        assert m["workloads"] == SERVING_CELLS and m["unit"] == unit
        assert m["moves"] == "serve_tpot_p95_ms" and m["better"] == "lower"
        assert m["layer"] == "serving scheduler"
        assert m["source"] in ("program_span", "program_counter")
        assert not any(word in name for word in ("idle", "mfu", "hbm"))


def _drive(capsys, cell, trace):
    profiler.clear_profiler_spans()        # the ring is process-global
    rc = bench_run.drive(cell, SEED, 1.0, trace, jax.devices()[:1])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["gpt3-1.3b-serve.offline",
                                  "gpt3-1.3b-serve.chat"])
def test_a_traced_tiny_cell_reports_the_four(name, capsys):
    cell = tiny_cell(name)
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}
    got = _drive(capsys, cell, trace=True)["metrics"]
    for metric, unit in NEW.items():
        value = got[metric]["value"]
        assert got[metric]["unit"] == unit
        assert value == value and 0 <= value < float("inf")
    assert got["prefill_time_share.serve"]["value"] <= 100
    # the leaves of a tick cannot take longer than the tick around them
    assert got["decode_dispatch_ms_p50.serve"]["value"] \
        <= got["decode_wait_ms_p50.serve"]["value"]
    # what is read is what the traced spans hold
    traced = [s for s in profiler.get_profiler_spans() if s.in_trace]
    waits = [s.counts["since_last_ms"] for s in traced
             if s.name == "serving.decode_tick"
             and s.counts.get("carried", 0) > 0]
    assert got["decode_stall_ms_mean.serve"]["value"] \
        == pytest.approx(sum(waits) / len(waits))
    # without a profiler session nothing is in_trace: none is reported
    line = _drive(capsys, cell, trace=False)
    assert not set(NEW) & set(line["metrics"])


def test_a_program_without_the_spans_leaves_the_four_out(spans):
    """The parent commit under this benchmark: its ring holds the six
    spans of PR 28 and no leaf, so the line lacks three of the four and
    says 0 for nothing it cannot see — `prefill_time_share.serve` alone
    reads the spans the parent has."""
    spans([_span("serving.router_tick", 0, 40),
           _span("serving.tick", 1, 38, 1),
           _span("serving.admit", 2, 12, 2, counts={"request": 1}),
           _span("serving.prefill", 3, 10, 3,
                 counts={"request": 1, "true_len": 5, "bucket": 8}),
           _span("serving.upload", 15, 1, 2),
           _tick(17, 20)])
    metrics = [{"name": n, "unit": u} for n, u in NEW.items()]
    assert read_metrics(metrics, {}) == {
        "prefill_time_share.serve": {"value": 25.0, "unit": "%"}}
