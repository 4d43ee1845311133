"""The two readers of the program's own spans (ISSUE 28): their arithmetic
on span lists made by hand, and tiny serving cells driven on the CPU with
and without the profiler session that turns the spans' `in_trace` on."""
import json

import jax
import pytest

from benchmark import run as bench_run
from benchmark.readers import program_span_ms, program_span_ratio
from benchmark.tiny import tiny_cell
from paddle_tpu import profiler
from paddle_tpu.profiler import Span

SEED = 2 ** 31 + 54321
NEW = {"decode_wait_ms_p50.serve", "tick_host_ms_mean.serve",
       "prefill_ms_p50.serve", "prefill_padded_token_share.serve",
       "decode_slot_occupancy.serve"}


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


def test_a_percentile_of_a_spans_duration(spans):
    spans([_span("tick", 0, 10), _span("tick", 20, 30), _span("tick", 60, 20),
           _span("tick", 90, 1000, traced=False),      # no session: left out
           _span("other", 0, 500)])
    assert program_span_ms.read({}, "tick", 50) == pytest.approx(20.0)
    assert program_span_ms.read({}, "tick", 100) == pytest.approx(30.0)
    assert program_span_ms.read({}, "tick", 0) == pytest.approx(10.0)


def test_self_time_with_nested_and_with_sibling_children(spans):
    spans([
        # tick A 0..100: dev 10..40 and, inside an admit 50..90, a
        # prefill 55..85 with a dev-named span nested in it 60..70: the
        # union covers 30 + 30 = 60 -> self 40
        _span("tick", 0, 100), _span("dev", 10, 30, 1),
        _span("admit", 50, 40, 1), _span("prefill", 55, 30, 2),
        _span("dev", 60, 10, 3),
        # tick B 200..250: two sibling devs 205..215, 220..240 -> self 20
        _span("tick", 200, 50), _span("dev", 205, 10, 1),
        _span("dev", 220, 20, 1),
        # tick C 300..310 has no children -> self 10
        _span("tick", 300, 10),
        # another thread's dev inside tick C's interval is not its child
        _span("dev", 301, 8, 0, tid=2),
        # an untraced child is not subtracted, as its parent is not read
        _span("tick", 400, 10, traced=False),
        _span("dev", 401, 5, 1, traced=False),
    ])
    minus = ("dev", "prefill")
    assert program_span_ms.read({}, "tick", 0, minus) == pytest.approx(10.0)
    assert program_span_ms.read({}, "tick", 50, minus) == pytest.approx(20.0)
    assert program_span_ms.read({}, "tick", 100, minus) == pytest.approx(40.0)
    assert program_span_ms.read({}, "tick", 100) == pytest.approx(100.0)
    # the mean weighs a phase by how often it runs: (40 + 20 + 10) / 3
    assert program_span_ms.read({}, "tick", "mean", minus) \
        == pytest.approx(70.0 / 3)
    assert program_span_ms.read({}, "tick", "mean") \
        == pytest.approx(160.0 / 3)


def test_a_ratio_of_two_counts_and_its_complement(spans):
    spans([_span("prefill", 0, 1, counts={"true_len": 100, "bucket": 128}),
           _span("prefill", 2, 1, counts={"true_len": 20, "bucket": 32}),
           _span("prefill", 4, 1, counts={"true_len": 9, "bucket": 16},
                 traced=False),
           _span("prefill", 6, 1, counts={"bucket": 64}),    # no numerator
           _span("prefill", 8, 1)])                          # no counts
    assert program_span_ratio.read({}, "prefill", "true_len", "bucket") \
        == pytest.approx(75.0)
    assert program_span_ratio.read({}, "prefill", "true_len", "bucket",
                                   complement=True) == pytest.approx(25.0)


def test_nothing_to_read_is_none_never_nought(spans):
    spans([_span("tick", 0, 10, traced=False),
           _span("tick", 20, 10, counts={"active": 0, "slots": 0})])
    assert program_span_ms.read({}, "absent", 50) is None
    assert program_span_ms.read({}, "absent", "mean") is None
    assert program_span_ratio.read({}, "absent", "a", "b") is None
    assert program_span_ratio.read({}, "tick", "active", "slots") is None
    # a program whose records are bare five-field tuples (a parent commit)
    spans([("tick", 0.0, 0.01, 0, 1)])
    assert program_span_ms.read({}, "tick", 50) is None
    assert program_span_ratio.read({}, "tick", "active", "slots") is None


def _drive(capsys, cell, trace):
    profiler.clear_profiler_spans()        # the ring is process-global
    rc = bench_run.drive(cell, SEED, 1.0, trace, jax.devices()[:1])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["gpt3-1.3b-serve.offline",
                                  "gpt3-1.3b-serve.chat"])
def test_a_traced_tiny_cell_reports_the_programs_spans(name, capsys):
    cell = tiny_cell(name)
    want = NEW & {m["name"] for m in cell["per_layer"]}
    assert len(want) == (5 if name.endswith("chat") else 3)
    line = _drive(capsys, cell, trace=True)
    got = line["metrics"]
    assert want <= set(got)
    for metric in want:
        value = got[metric]["value"]
        assert value == value and abs(value) != float("inf")   # finite
        assert value >= 0
    assert 0 < got["decode_slot_occupancy.serve"]["value"] <= 100
    if name.endswith("chat"):
        assert 0 <= got["prefill_padded_token_share.serve"]["value"] < 100
    # the two halves of a tick cannot exceed the whole timed from outside
    # by more than the outside clock's own jitter
    assert got["decode_wait_ms_p50.serve"]["value"] <= \
        1.5 * got["tick_ms_p50.serve"]["value"]
    # without a profiler session nothing is in_trace: none is reported
    line = _drive(capsys, cell, trace=False)
    assert not NEW & set(line["metrics"])
    assert not any(s.in_trace for s in profiler.get_profiler_spans())
