"""The serving tick's phases as `RecordEvent` spans with counts
(docs/observability.md "Serving spans"): one `serving.router_tick` and one
`serving.tick` per `router.step()`, children nested inside their parents
on one thread, `serving.prefill` carrying the request and its padding,
`serving.decode_tick` carrying the occupancy and what its rows waited
through since the last tick, its leaves `serving.decode_dispatch` /
`serving.decode_pull` and `serving.emit` after it — and none of it adds a
host pull or a trace to what tests/test_serving_observability.py pins.

Reference analog: python/paddle/profiler/utils.py:37 (`RecordEvent`
spans through the hot path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.router import create_router
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans

MAX_LEN = 64
GEN = 6
LENS = (5, 9, 13, 21)
SLOTS = 3
SPAN_NAMES = {"serving.router_tick", "serving.tick", "serving.admit",
              "serving.prefill", "serving.upload", "serving.decode_tick",
              "serving.decode_dispatch", "serving.decode_pull",
              "serving.emit"}
ENGINES = {
    "dense": dict(kv_layout="dense"),
    "paged": dict(kv_layout="paged", page_size=8, prefill_chunk=8),
    "spec": dict(kv_layout="dense", spec_decode="spec", gamma=2),
    "multi_tick": dict(kv_layout="dense", multi_tick=2),
}


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, ffn_hidden=64, max_seq_len=128,
                    sequence_parallel=False, remat=False,
                    dtype=jnp.float32)
    return cfg, init_gpt_params(cfg, jax.random.PRNGKey(0))


def _prompts(seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 60, n).astype(np.int32) for n in LENS]


def _router(gpt_setup, kind, slots=SLOTS):
    cfg, params = gpt_setup
    kw = {"spec_decode": "off", "multi_tick": 1, **ENGINES[kind]}
    return create_router(params, cfg, replicas=1, num_slots=slots,
                         max_len=MAX_LEN, **kw)


def _serve(router, prompts):
    """Submit, then step to the end; -> (requests, spans per step)."""
    reqs = [router.submit(p, GEN) for p in prompts]
    per_step = []
    while router.has_work():
        before = len(get_profiler_spans())
        router.step()
        per_step.append(get_profiler_spans()[before:])
        assert len(per_step) < 500
    return reqs, per_step


def _count_pulls(eng):
    counts = [0]
    orig = eng._pull

    def counted(value, stall_s=0.0):
        counts[0] += 1
        return orig(value, stall_s)
    eng._pull = counted
    return counts


@pytest.mark.parametrize("kind", list(ENGINES))
def test_one_step_is_one_tree_of_spans_with_counts(gpt_setup, kind):
    router = _router(gpt_setup, kind)
    eng = router.replicas[0].eng
    assert eng.paged == (kind == "paged") and eng.spec == (kind == "spec")
    assert eng.mt_k == (2 if kind == "multi_tick" else 1)
    _serve(router, _prompts(seed=3))             # warm: every trace made
    warm_traces = eng.trace_counts()
    pulls = _count_pulls(eng)
    clear_profiler_spans()
    reqs, per_step = _serve(router, _prompts())
    spans = [s for step in per_step for s in step]
    assert {s.name for s in spans} == SPAN_NAMES

    # one router tick and one engine tick per step, nested on one thread
    for step in per_step:
        names = [s.name for s in step]
        assert names.count("serving.router_tick") == 1
        assert names.count("serving.tick") == 1
        assert len({s.tid for s in step}) == 1
        by_depth = sorted(step, key=lambda s: (s.start, s.depth))
        stack = []
        for s in by_depth:                     # parents start first
            while stack and s.start >= stack[-1].start + stack[-1].dur_s:
                stack.pop()
            assert len(stack) == s.depth       # depth = open ancestors
            if stack:                          # inside its parent
                assert s.start + s.dur_s <= \
                    stack[-1].start + stack[-1].dur_s
            stack.append(s)
        root = by_depth[0]
        assert root.name == "serving.router_tick" and root.depth == 0
        tick = next(s for s in step if s.name == "serving.tick")
        assert tick.depth == 1

    # the five-field prefix, counts and in_trace of every record
    for name, start, dur, depth, tid, counts, in_trace in spans:
        assert isinstance(name, str) and dur >= 0 and start > 0
        assert in_trace is False               # no profiler session here

    # admissions and prefills: the request's id, true_len <= bucket
    admits = [s for s in spans if s.name == "serving.admit"]
    prefills = [s for s in spans if s.name == "serving.prefill"]
    assert len({s.counts["request"] for s in admits}) == len(LENS)
    assert {s.counts["request"] for s in prefills} == \
        {s.counts["request"] for s in admits}
    for s in prefills:
        assert 0 < s.counts["true_len"] <= s.counts["bucket"]
    if kind == "paged":                # 13 and 21 tokens run in chunks
        assert len(prefills) > len(admits)
        assert sum(s.counts["true_len"] for s in prefills) == sum(LENS)
    else:
        assert sorted(s.counts["true_len"] for s in prefills) == sorted(LENS)

    # decode: occupancy from the host mirror
    ticks = [s for s in spans if s.name == "serving.decode_tick"]
    decoded = sum(len(r.tokens) for r in reqs) - len(LENS)
    assert all(s.counts["slots"] == SLOTS
               and 1 <= s.counts["active"] <= SLOTS for s in ticks)
    if kind in ("dense", "paged"):             # one token a slot a tick
        assert sum(s.counts["active"] for s in ticks) == decoded
    else:                          # several tokens a slot a tick
        assert sum(s.counts["active"] for s in ticks) <= decoded
    assert any(s.name == "serving.upload" for s in spans)

    # what tests/test_serving_observability.py pins: one pull per tick
    # and one per final prefill chunk, no new trace
    assert pulls[0] == len(ticks) + len(LENS)
    assert eng.trace_counts() == warm_traces
    assert all(len(r.tokens) == GEN for r in reqs)


@pytest.mark.parametrize("kind", list(ENGINES))
def test_on_the_einsum_path_the_kv_read_is_the_whole_pool(gpt_setup, kind):
    """`kv_positions_read` / `kv_positions_pool` on every decode tick
    (docs/observability.md): off a TPU — and paged, speculative or
    multi-tick anywhere — the attention is the masked einsum over every
    position of every row, so the two are equal and
    `kv_read_share_of_pool.serve` reads 100%. The kernel's block
    arithmetic is pinned in tests/test_decode_attention_kernel.py."""
    cfg, _ = gpt_setup
    router = _router(gpt_setup, kind)
    eng = router.replicas[0].eng
    assert not eng.length_aware_tick()
    clear_profiler_spans()
    _serve(router, _prompts())
    ticks = [s.counts for s in get_profiler_spans()
             if s.name == "serving.decode_tick"]
    view = eng.max_pages * eng.page_size if kind == "paged" else MAX_LEN
    assert ticks and all(
        c["kv_positions_read"] == c["kv_positions_pool"]
        == cfg.num_layers * SLOTS * view for c in ticks)


def _end(span):
    return span.start + span.dur_s


@pytest.mark.parametrize("kind", list(ENGINES))
def test_a_decode_tick_is_its_dispatch_its_pull_and_then_emission(
        gpt_setup, kind):
    """Every tick form: `serving.decode_dispatch` then
    `serving.decode_pull` inside `serving.decode_tick`, `serving.emit`
    after it inside `serving.tick`, each once a tick; the leaves' time
    lies within their parent's, and none of the three carries a count
    (a count no metric, view or tool reads is not recorded)."""
    router = _router(gpt_setup, kind)
    clear_profiler_spans()
    reqs, per_step = _serve(router, _prompts())
    n_ticks = 0
    for step in per_step:
        by_name = {}
        for s in step:
            by_name.setdefault(s.name, []).append(s)
        ticks = by_name.get("serving.decode_tick", [])
        assert len(ticks) <= 1
        leaves = [by_name.get(n, []) for n in (
            "serving.decode_dispatch", "serving.decode_pull",
            "serving.emit")]
        assert [len(x) for x in leaves] == [len(ticks)] * 3
        if not ticks:
            continue
        n_ticks += 1
        (tick,), (outer,) = ticks, by_name["serving.tick"]
        (dispatch,), (pull,), (emit,) = leaves
        assert dispatch.depth == pull.depth == tick.depth + 1
        assert emit.depth == tick.depth == outer.depth + 1
        assert tick.start <= dispatch.start <= _end(dispatch) \
            <= pull.start <= _end(pull) <= _end(tick) <= emit.start \
            <= _end(emit) <= _end(outer)
        assert dispatch.dur_s + pull.dur_s <= tick.dur_s
        assert tick.dur_s + emit.dur_s <= outer.dur_s
        assert dispatch.counts is pull.counts is emit.counts is None
    assert n_ticks > 0 and all(len(r.tokens) == GEN for r in reqs)


def _scripted(router, tmp_path=None, fail_pull=0):
    """Two slots: A (3 tokens) and B (6) admitted, tick, tick (A ends),
    C (3) admitted into A's slot, tick, tick (C ends), tick (B ends) —
    under a profiler session where `tmp_path` is given, the `fail_pull`th
    pull of the engine raising once. -> the run's spans by name."""
    eng = router.replicas[0].eng
    orig, calls = eng._pull, [0]

    def pull(value, stall_s=0.0):
        calls[0] += 1
        if calls[0] == fail_pull:
            raise RuntimeError("injected: the pull failed")
        return orig(value, stall_s)
    eng._pull = pull
    rng = np.random.RandomState(11)
    clear_profiler_spans()
    for n, gen in ((5, 3), (9, 6), (7, 3)):
        router.submit(rng.randint(0, 60, n).astype(np.int32), gen)
    if tmp_path is not None:
        jax.profiler.start_trace(str(tmp_path))
    try:
        while router.has_work():
            router.step()
    finally:
        if tmp_path is not None:
            jax.profiler.stop_trace()
    by_name = {}
    for s in get_profiler_spans():
        assert s.in_trace == (tmp_path is not None)
        by_name.setdefault(s.name, []).append(s)
    return by_name


def test_what_a_row_waited_through_between_two_ticks(gpt_setup, tmp_path):
    """`since_last_ms` and `carried` on `serving.decode_tick` over the
    scripted run, a session recording. Absent on the first tick;
    `carried` counts the rows that decoded in the tick before under the
    same request; `since_last_ms` runs from that tick's pull returning
    to this span opening, so it holds the admission in between."""
    spans = _scripted(_router(gpt_setup, "dense", slots=2), tmp_path)
    ticks, pulls, prefills = (spans["serving." + n] for n in (
        "decode_tick", "decode_pull", "prefill"))
    assert len(ticks) == len(pulls) == 5 and len(prefills) == 3
    assert "carried" not in ticks[0].counts
    assert "since_last_ms" not in ticks[0].counts
    assert [t.counts["carried"] for t in ticks[1:]] == [2, 1, 2, 1]
    for before, tick in zip(pulls, ticks[1:]):
        gap_ms = 1e3 * (tick.start - _end(before))
        assert tick.counts["since_last_ms"] == pytest.approx(gap_ms,
                                                             abs=1e-6)
        assert gap_ms > 0
    # C's admission lies between the second tick's pull and the third tick
    late = prefills[2]
    assert _end(pulls[1]) < late.start and _end(late) < ticks[2].start
    assert ticks[2].counts["since_last_ms"] > 1e3 * late.dur_s


def test_with_no_session_the_two_counts_are_not_computed(gpt_setup):
    """Every reader of `since_last_ms` / `carried` reads traced spans:
    with no profiler session the tick computes neither and keeps no rows
    to compare with, so the first traced tick after it starts afresh."""
    router = _router(gpt_setup, "dense", slots=2)
    spans = _scripted(router)
    ticks = spans["serving.decode_tick"]
    assert len(ticks) == 5
    for t in ticks:
        assert "carried" not in t.counts and "since_last_ms" not in t.counts
        assert {"active", "slots"} <= set(t.counts)
    eng = router.replicas[0].eng
    assert eng._tick_rows is None
    assert eng._last_pull_end == pytest.approx(
        _end(spans["serving.decode_pull"][-1]))


def test_a_failed_attempt_is_not_the_last_tick(gpt_setup, tmp_path):
    """The third tick's pull (the engine's sixth: three admissions, two
    ticks before it) fails once and the tick is retried. Only a tick
    whose pull came back is "the last tick": the retried tick compares
    its rows with the SECOND tick's, so C, admitted in between, is not
    carried, and it waited through the failed attempt as well."""
    spans = _scripted(_router(gpt_setup, "dense", slots=2), tmp_path,
                      fail_pull=6)
    ticks, pulls = spans["serving.decode_tick"], spans["serving.decode_pull"]
    assert len(ticks) == len(pulls) == 6       # five ticks and the attempt
    assert [t.counts["carried"] for t in ticks[1:]] == [2, 1, 1, 2, 1]
    failed, retried = ticks[2], ticks[3]
    assert retried.counts["since_last_ms"] > \
        failed.counts["since_last_ms"] + 1e3 * failed.dur_s
    assert retried.counts["since_last_ms"] == pytest.approx(
        1e3 * (retried.start - _end(pulls[1])), abs=1e-6)


def test_a_reset_or_a_restore_forgets_the_last_ticks_rows(gpt_setup,
                                                          tmp_path):
    """After `_hard_reset`, and after a snapshot is restored into a slot,
    the next tick carries neither count: no row of it ticked here
    before under what the engine remembers."""
    router = _router(gpt_setup, "dense", slots=2)
    eng = router.replicas[0].eng
    rng = np.random.RandomState(5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        router.submit(rng.randint(0, 60, 9).astype(np.int32), GEN)
        router.step()
        router.step()
        assert eng._tick_rows is not None
        (req,) = [r for r in eng._slot_req if r is not None]
        twin = eng.restore_request(eng.snapshot_request(req))
        assert twin is not None and eng._tick_rows is None
        clear_profiler_spans()
        router.step()
        (tick,) = [s for s in get_profiler_spans()
                   if s.name == "serving.decode_tick"]
        assert tick.counts["active"] == 2 and "carried" not in tick.counts
        router.step()
        assert eng._tick_rows is not None
        eng._hard_reset("test")
        assert eng._tick_rows is None and req.finish_reason == "evicted"
    finally:
        jax.profiler.stop_trace()


def test_span_durations_feed_the_telemetry_records(gpt_setup):
    """`ServingTelemetry`'s dur_ms is the span's own duration: no second
    clock pair beside the RecordEvent."""
    router = _router(gpt_setup, "dense")
    eng = router.replicas[0].eng
    clear_profiler_spans()
    n0 = len(eng.tick_records())
    _serve(router, _prompts())
    spans = get_profiler_spans()
    recs = eng.tick_records()[n0:]
    tick_ms = [1e3 * s.dur_s for s in spans
               if s.name == "serving.decode_tick"]
    pf_ms = [1e3 * s.dur_s for s in spans if s.name == "serving.prefill"]
    assert [r["dur_ms"] for r in recs if r["kind"] == "serving_tick"] == \
        pytest.approx(tick_ms, abs=1e-3)
    assert [r["dur_ms"] for r in recs
            if r["kind"] == "serving_prefill"] == \
        pytest.approx(pf_ms, abs=1e-3)


def test_no_clock_pair_is_left_beside_a_record_event():
    import inspect
    from paddle_tpu.inference import serving
    src = inspect.getsource(serving)
    assert "t_dev0" not in src and "t_pf0" not in src
