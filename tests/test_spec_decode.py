"""Speculative decoding tests (inference/spec_decode.py + the serving
engine's spec tick).

Reference analog: the inference decoder loops of
incubate/nn/layer/fused_transformer.py:1022 (one token per full
forward), accelerated per Leviathan et al. 2023 — self-draft propose +
one-pass verify inside the serving tick.

The load-bearing guarantees:
- greedy speculative streams are BIT-IDENTICAL to the non-spec engine
  (and therefore to per-request greedy decode) for gpt AND llama/GQA,
  on dense and paged KV layouts, at ANY draft depth (acceptance rate
  affects speed, never tokens);
- the PR 4-6 invariants survive: one host pull per tick, <= 2 decode
  traces with zero recompiles after warmup, exactly-once terminal
  resolution (EOS / max_new_tokens truncation mid-accepted-block);
- mixed spec/non-spec batches: sampled slots ride the same tick and
  reproduce the non-spec engine's sampled streams exactly;
- draft-NaN degrades to non-spec decode for the slot (never
  quarantines the target stream);
- selection: off by default, on by the spec_decode= argument alone;
- facade/hapi passthrough: spec knobs reach the engine and its cache
  key (switching gamma/draft depth rebuilds).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference import spec_decode as sd
from paddle_tpu.models.decode import greedy_accept
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
from paddle_tpu.models import llama as llama_mod

MAXLEN = 64


def _gpt_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, ffn_hidden=64, max_seq_len=128,
                     sequence_parallel=False, remat=False,
                     dtype=jnp.float32)


def _llama_cfg():
    return llama_mod.LlamaConfig(vocab_size=64, hidden_size=32,
                                 num_layers=2, num_heads=4,
                                 num_kv_heads=2, max_seq_len=128,
                                 dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = _gpt_cfg()
    return cfg, init_gpt_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def llama_setup():
    cfg = _llama_cfg()
    return cfg, llama_mod.init_llama_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_flight_ring():
    """The engine notes serving faults into the PROCESS-GLOBAL flight
    recorder ring (the target-nan quarantine test triggers one);
    leaving them behind would leak into other tests' dumps (e.g. the
    resilient trainer's rollback dump asserts over its step records).
    Clear the ring after every test here, as test_serving_robustness
    does."""
    from paddle_tpu.profiler import flight_recorder
    yield
    rec = flight_recorder.recorder()
    rec.clear()
    rec.set_dir(None)


def _prompts(lens, seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, L).astype(np.int32) for L in lens]


def _eng(params, cfg, family="gpt", **kw):
    kw.setdefault("num_slots", 3)
    return ServingEngine(params, cfg, family=family, max_len=MAXLEN, **kw)


def _spec(params, cfg, family="gpt", **kw):
    kw.setdefault("gamma", 3)
    kw.setdefault("draft_layers", cfg.num_layers)
    return _eng(params, cfg, family=family, spec_decode="spec", **kw)


# --------------------------------------------------------------------------
# the acceptance rule
# --------------------------------------------------------------------------
class TestGreedyAccept:
    def test_rule(self):
        draft = jnp.asarray([[5, 6, 7],      # all match
                             [5, 9, 7],      # first only
                             [9, 6, 7],      # none
                             [5, 6, 9]])     # first two
        target = jnp.asarray([[5, 6, 7, 1],
                              [5, 6, 7, 1],
                              [5, 6, 7, 1],
                              [5, 6, 7, 1]])
        np.testing.assert_array_equal(
            np.asarray(greedy_accept(draft, target)), [3, 1, 0, 2])


# --------------------------------------------------------------------------
# tentpole: greedy spec streams == the non-spec engine, bit for bit
# --------------------------------------------------------------------------
class TestSpecParityGPT:
    def test_dense_mixed_lengths_and_joins(self, gpt_setup):
        """More requests than slots, mixed lengths and gen budgets —
        joins land mid-speculation and every stream is exact."""
        cfg, params = gpt_setup
        lens = [3, 5, 8, 10, 4, 13]
        gens = [4, 6, 3, 7, 5, 6]
        prompts = _prompts(lens, seed=1)
        base = _eng(params, cfg)
        want = [base.generate([p], g)[0]
                for p, g in zip(prompts, gens)]
        eng = _spec(params, cfg)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        eng.drain()
        for r, w in zip(reqs, want):
            assert r.done and r.finish_reason == "length"
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), w)

    def test_truncated_draft_still_exact(self, gpt_setup):
        """draft_layers=1 on random-init params means near-zero
        acceptance — the speed floor — but the stream NEVER moves:
        every emitted token is the target's own argmax."""
        cfg, params = gpt_setup
        prompts = _prompts([4, 9], seed=2)
        want = _eng(params, cfg).generate(prompts, 8)
        eng = _spec(params, cfg, draft_layers=1, gamma=4)
        got = eng.generate(prompts, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_paged_with_prefix_sharing(self, gpt_setup):
        cfg, params = gpt_setup
        rng = np.random.RandomState(3)
        system = rng.randint(0, 64, 16).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.randint(0, 64, k).astype(np.int32)])
            for k in (2, 3, 5)]
        want = _eng(params, cfg).generate(prompts, 8)
        eng = _spec(params, cfg, kv_layout="paged", page_size=8)
        got = eng.generate(prompts, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        st = eng.pool_stats()
        assert st["pages_in_use"] == 0 and st["pages_reserved"] == 0

    def test_eos_and_length_truncate_mid_block(self, gpt_setup):
        """EOS (or the max_new budget) landing INSIDE an accepted
        block truncates exactly where the non-spec engine stops."""
        cfg, params = gpt_setup
        p = _prompts([5], seed=4)[0]
        want = _eng(params, cfg, num_slots=1).generate([p], 8)[0]
        eos = int(want[3])
        base = _eng(params, cfg, num_slots=1)
        r0 = base.submit(p, 8, eos_id=eos)
        base.drain()
        eng = _spec(params, cfg, num_slots=1, gamma=4)
        r1 = eng.submit(p, 8, eos_id=eos)
        eng.drain()
        assert (r0.finish_reason, r0.tokens) == \
            (r1.finish_reason, r1.tokens)
        # max_new smaller than one full accepted block
        r2 = _spec(params, cfg, num_slots=1, gamma=4).generate([p], 2)[0]
        np.testing.assert_array_equal(r2, want[:2])

    def test_boundary_legal_request_at_max_len(self, gpt_setup):
        """A request whose budget ends exactly at the cache end
        (prompt + max_new == max_len) must finish 'length' with every
        token, even when the final accepted block lands the position
        mirror on max_len mid-block — the cache-full 'evicted' check
        must not fire over tokens the non-spec engine would emit
        (regression: block-advancing the mirror before the per-token
        loop dropped the tail of the final block)."""
        cfg, params = gpt_setup
        ml = 32
        p = _prompts([ml - 4], seed=19)[0]
        base = ServingEngine(params, cfg, family="gpt", num_slots=1,
                             max_len=ml)
        r0 = base.submit(p, 4)
        base.drain()
        assert r0.finish_reason == "length" and len(r0.tokens) == 4
        eng = ServingEngine(params, cfg, family="gpt", num_slots=1,
                            max_len=ml, spec_decode="spec", gamma=4,
                            draft_layers=cfg.num_layers)
        r1 = eng.submit(p, 4)
        eng.drain()
        assert r1.finish_reason == "length", r1.finish_reason
        assert r1.tokens == r0.tokens


class TestSpecParityLlama:
    def test_gqa_dense_and_paged(self, llama_setup):
        cfg, params = llama_setup
        prompts = _prompts([4, 9, 6, 12], seed=5)
        want = _eng(params, cfg, family="llama").generate(prompts, 6)
        got_d = _spec(params, cfg, family="llama").generate(prompts, 6)
        got_p = _spec(params, cfg, family="llama", kv_layout="paged",
                      page_size=8, draft_layers=1).generate(prompts, 6)
        for w, a, b in zip(want, got_d, got_p):
            np.testing.assert_array_equal(a, w)
            np.testing.assert_array_equal(b, w)


class TestMixedBatches:
    def test_sampled_slots_ride_the_spec_tick(self, gpt_setup):
        """Greedy slots speculate while sampled slots emit ONE
        reproducible token per tick from verify row 0 — both streams
        equal the non-spec engine's exactly."""
        cfg, params = gpt_setup
        prompts = _prompts([5, 8], seed=6)
        base = _eng(params, cfg, num_slots=2, max_top_k=8, seed=11)
        bg = base.submit(prompts[0], 6)
        bs = base.submit(prompts[1], 6, temperature=0.9, top_k=5)
        base.drain()
        eng = _spec(params, cfg, num_slots=2, max_top_k=8, seed=11)
        rg = eng.submit(prompts[0], 6)
        rs = eng.submit(prompts[1], 6, temperature=0.9, top_k=5)
        eng.drain()
        assert rg.tokens == bg.tokens
        assert rs.tokens == bs.tokens
        # sampled slots never propose: the ledger counts the greedy
        # slot only, and at K=L it accepts everything it proposes
        assert eng._spec_prop_total > 0
        assert eng._spec_prop_total % eng.spec_gamma == 0
        assert eng._spec_acc_total == eng._spec_prop_total


# --------------------------------------------------------------------------
# invariants: traces, ticks, telemetry
# --------------------------------------------------------------------------
class TestSpecInvariants:
    def test_zero_recompiles_and_fewer_ticks(self, gpt_setup):
        cfg, params = gpt_setup
        from paddle_tpu.profiler import monitor
        eng = _spec(params, cfg)
        eng.generate(_prompts([3, 5, 8], seed=7), 8)     # bucket 8
        t0 = eng.trace_counts()
        assert t0[0] == 1                 # greedy-only: ONE decode trace
        tick0 = monitor.counter("serving.decode_ticks").value
        eng.generate(_prompts([2, 7, 6], seed=8), 8)     # same bucket
        assert eng.trace_counts() == t0
        spec_ticks = monitor.counter("serving.decode_ticks").value - tick0
        base = _eng(params, cfg)
        base.generate(_prompts([3, 5, 8], seed=7), 8)
        tick1 = monitor.counter("serving.decode_ticks").value
        base.generate(_prompts([2, 7, 6], seed=8), 8)
        dense_ticks = monitor.counter("serving.decode_ticks").value \
            - tick1
        # full-depth self-draft accepts everything: ~(gamma+1)x fewer
        assert spec_ticks < dense_ticks

    def test_acceptance_telemetry_and_report_block(self, gpt_setup,
                                                   tmp_path):
        cfg, params = gpt_setup
        from paddle_tpu.profiler import monitor
        path = str(tmp_path / "tele.jsonl")
        monitor.registry().export_jsonl(path)
        p0 = monitor.counter("serving.spec_proposed").value
        a0 = monitor.counter("serving.spec_accepted").value
        eng = _spec(params, cfg)                  # K = L: accept all
        eng.generate(_prompts([4, 6], seed=9), 6)
        dp = monitor.counter("serving.spec_proposed").value - p0
        da = monitor.counter("serving.spec_accepted").value - a0
        assert dp > 0 and da == dp                # full acceptance
        assert eng._spec_acc_total == eng._spec_prop_total
        assert monitor.gauge("serving.spec_accept_rate").value == 1.0
        monitor.registry().export_jsonl(path)
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from telemetry_report import summarize
        srv = summarize(path).get("serving", {})
        assert srv["spec"]["spec_proposed"] == dp
        assert srv["spec"]["spec_accepted"] == da
        assert srv["spec"]["spec_accept_rate"] == 1.0

    def test_partial_acceptance_exact_and_counted(self, gpt_setup):
        """Random-init residual blocks are near-identity, so even a
        truncated draft accepts almost everything; AMPLIFIED blocks
        make depth matter — acceptance drops well below 1 and the
        partial-acceptance host path (cut < gamma+1 mid-stream) still
        reproduces the non-spec stream bit for bit."""
        cfg, _ = gpt_setup
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        for k in ("qkv_w", "attn_out_w", "mlp_up_w", "mlp_down_w"):
            params[k] = params[k] * 8.0
        prompts = _prompts([4, 7, 11], seed=10)
        want = _eng(params, cfg).generate(prompts, 12)
        eng = _spec(params, cfg, draft_layers=1, gamma=4)
        got = eng.generate(prompts, 12)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        assert 0 < eng._spec_acc_total < eng._spec_prop_total

    def test_gamma_validation(self, gpt_setup):
        cfg, params = gpt_setup
        with pytest.raises(ValueError):
            _spec(params, cfg, gamma=0)
        with pytest.raises(ValueError):
            _spec(params, cfg, draft_layers=99)


# --------------------------------------------------------------------------
# selection: the spec_decode= argument alone; 'auto' is off
# --------------------------------------------------------------------------
class TestSelection:
    def test_default_off(self, gpt_setup):
        cfg, params = gpt_setup
        assert not _eng(params, cfg, num_slots=1).spec

    def test_resolve_validates(self):
        with pytest.raises(ValueError):
            sd.resolve_spec("sometimes")


# --------------------------------------------------------------------------
# degradation: draft nan never touches the target stream
# --------------------------------------------------------------------------
class TestDraftDegrade:
    def test_draft_nan_degrades_not_quarantines(self, gpt_setup):
        from paddle_tpu.testing import faults
        cfg, params = gpt_setup
        prompts = _prompts([3, 5, 8], seed=11)
        want = _eng(params, cfg).generate(prompts, 8)
        faults.install("draft_nan@1:1")
        try:
            eng = _spec(params, cfg)
            reqs = [eng.submit(p, 8) for p in prompts]
            eng.drain()
        finally:
            faults.uninstall()
        assert all(r.finish_reason == "length" for r in reqs)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), w)
        # the poisoned tick accepted nothing — the ledger shows it
        assert eng._spec_acc_total < eng._spec_prop_total

    def test_target_nan_still_quarantines(self, gpt_setup):
        from paddle_tpu.testing import faults
        cfg, params = gpt_setup
        prompts = _prompts([3, 5, 8], seed=12)
        want = _eng(params, cfg).generate(prompts, 8)
        faults.install("nan_logits@1:1")
        try:
            eng = _spec(params, cfg)
            reqs = [eng.submit(p, 8) for p in prompts]
            eng.drain()
        finally:
            faults.uninstall()
        reasons = [r.finish_reason for r in reqs]
        assert reasons.count("poisoned") == 1
        for r, w in zip(reqs, want):
            if r.finish_reason == "length":
                np.testing.assert_array_equal(
                    np.asarray(r.tokens, np.int32), w)


# --------------------------------------------------------------------------
# facade / hapi passthrough + engine cache key distinctness
# --------------------------------------------------------------------------
class TestFacadeHapi:
    def test_knobs_reach_engine_and_cache_key(self, gpt_setup):
        cfg, _ = gpt_setup
        from paddle_tpu.models.gpt import GPTModel
        gm = GPTModel(cfg)
        prompts = _prompts([5, 9], seed=13)
        want = gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN)
        outs = gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                           spec_decode="spec", gamma=2,
                           draft_layers=cfg.num_layers)
        eng = gm._serving_engine
        assert eng.spec and eng.spec_gamma == 2
        for a, b in zip(want, outs):
            np.testing.assert_array_equal(a, b)
        # same knobs -> cached engine; different gamma -> rebuild
        gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                    spec_decode="spec", gamma=2,
                    draft_layers=cfg.num_layers)
        assert gm._serving_engine is eng
        gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                    spec_decode="spec", gamma=3,
                    draft_layers=cfg.num_layers)
        assert gm._serving_engine is not eng
        assert gm._serving_engine.spec_gamma == 3

    def test_hapi_passthrough(self, gpt_setup):
        cfg, _ = gpt_setup
        from paddle_tpu.models.gpt import GPTModel
        from paddle_tpu.hapi import Model
        gm = GPTModel(cfg)
        prompts = _prompts([5, 9], seed=14)
        want = gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN)
        outs = Model(gm).generate(prompts, 4, num_slots=2,
                                  max_len=MAXLEN, spec_decode="spec",
                                  gamma=2, draft_layers=cfg.num_layers)
        assert gm._serving_engine.spec
        for a, b in zip(want, outs):
            np.testing.assert_array_equal(a, b)
