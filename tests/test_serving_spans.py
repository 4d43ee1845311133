"""The serving tick's phases as `RecordEvent` spans with counts
(docs/observability.md "Serving spans"): one `serving.router_tick` and one
`serving.tick` per `router.step()`, children nested inside their parents
on one thread, `serving.prefill` carrying the request and its padding,
`serving.decode_tick` carrying the occupancy — and none of it adds a host
pull or a trace to what tests/test_serving_observability.py pins.

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
              "serving.prefill", "serving.upload", "serving.decode_tick"}
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


def _router(gpt_setup, kind):
    cfg, params = gpt_setup
    kw = {"spec_decode": "off", "multi_tick": 1, **ENGINES[kind]}
    return create_router(params, cfg, replicas=1, num_slots=SLOTS,
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
