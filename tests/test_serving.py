"""Continuous-batching serving engine tests (inference/serving.py).

Reference analog: the serving runtime — AnalysisPredictor
(inference/api/analysis_predictor.h:94) + the FusedMultiTransformer
decode loops (incubate/nn/layer/fused_transformer.py:1022) — here as
iteration-level scheduling over a slot-pool KV cache.

The two load-bearing guarantees:
- token streams from continuous batching (requests joining/leaving
  mid-decode, mixed prompt lengths, slot reuse over stale cache
  contents) are BIT-IDENTICAL to per-request `greedy_generate`, for
  gpt AND llama (GQA cache shape);
- zero recompiles after warmup: the decode tick keeps ONE trace per
  sampling mode and prefill one per prompt bucket, asserted via jit
  cache sizes across varying prompt lengths and join/leave patterns.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import decode as decode_mod
from paddle_tpu.models.decode import (greedy_generate_with, generate_fn,
                                      next_pow2, prompt_bucket)
from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                   init_kv_cache, gpt_forward_cached,
                                   greedy_generate)
from paddle_tpu.models import llama as llama_mod


MAXLEN = 32


def _gpt_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, ffn_hidden=64, max_seq_len=64,
                     sequence_parallel=False, remat=False,
                     dtype=jnp.float32)


def _llama_cfg():
    return llama_mod.LlamaConfig(vocab_size=64, hidden_size=32,
                                 num_layers=2, num_heads=4,
                                 num_kv_heads=2, max_seq_len=64,
                                 dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = _gpt_cfg()
    return cfg, init_gpt_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def llama_setup():
    cfg = _llama_cfg()
    return cfg, llama_mod.init_llama_params(cfg, jax.random.PRNGKey(0))


def _prompts(lens, seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, L).astype(np.int32) for L in lens]


def _expected_greedy(params, cfg, gen_fn, prompt, n, max_len=MAXLEN):
    out = gen_fn(params, jnp.asarray(prompt)[None], cfg, n,
                 max_len=max_len)
    return np.asarray(out)[0, len(prompt):]


# --------------------------------------------------------------------------
# satellite: bucketed greedy_generate_with
# --------------------------------------------------------------------------
class TestBucketedGreedy:
    def test_bucket_policy(self):
        assert next_pow2(3) == 8          # lo floor
        assert next_pow2(8) == 8
        assert next_pow2(9) == 16
        assert prompt_bucket(20, 24) == 24    # clamped to the cache
        with pytest.raises(ValueError):
            prompt_bucket(40, 32)

    def test_trace_count_within_bucket(self, gpt_setup):
        """Prompt lengths sharing a bucket reuse ONE compiled
        executable — the retracing fix this satellite demands."""
        cfg, params = gpt_setup
        fn = generate_fn(gpt_forward_cached, init_kv_cache, cfg, 4,
                         MAXLEN)
        n0 = fn._cache_size()
        for L in (3, 5, 7, 8):            # all bucket 8
            p = _prompts([L], seed=L)[0]
            greedy_generate(params, jnp.asarray(p)[None], cfg, 4,
                            max_len=MAXLEN)
        assert fn._cache_size() - n0 <= 1
        greedy_generate(params,
                        jnp.asarray(_prompts([12])[0])[None], cfg, 4,
                        max_len=MAXLEN)   # bucket 16 -> one new trace
        assert fn._cache_size() - n0 <= 2

    def test_padded_prefill_parity(self, gpt_setup):
        """Bucket padding must not perturb the greedy stream: compare
        against the token-by-token no-cache argmax loop."""
        cfg, params = gpt_setup
        from paddle_tpu.models.gpt import gpt_forward
        prompt = jnp.asarray(_prompts([5], seed=3)[0])[None]
        out = greedy_generate(params, prompt, cfg, 6, max_len=MAXLEN)
        cur = prompt
        for _ in range(6):
            lg = gpt_forward(params, cur, cfg)
            nx = jnp.argmax(lg[:, -1].astype(jnp.float32), -1)[:, None]
            cur = jnp.concatenate([cur, nx], 1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_error_semantics_preserved(self, gpt_setup):
        cfg, params = gpt_setup
        prompt = jnp.asarray(_prompts([4])[0])[None]
        assert greedy_generate(params, prompt, cfg, 0).shape == (1, 4)
        with pytest.raises(ValueError):
            greedy_generate(params, prompt, cfg, -1)
        with pytest.raises(ValueError):
            greedy_generate(params, prompt, cfg, 8, max_len=8)


# --------------------------------------------------------------------------
# tentpole: continuous batching == per-request greedy, bit for bit
# --------------------------------------------------------------------------
class TestServingGPT:
    def test_streams_match_greedy(self, gpt_setup):
        """Mixed prompt lengths, more requests than slots: requests
        queue, join mid-decode into freed slots, finish at different
        ticks — and every stream equals its solo greedy run exactly."""
        cfg, params = gpt_setup
        lens = [3, 5, 8, 10, 4, 13, 6, 2]
        gens = [4, 6, 3, 5, 7, 2, 5, 4]
        prompts = _prompts(lens, seed=1)
        eng = ServingEngine(params, cfg, family="gpt", num_slots=3,
                            max_len=MAXLEN)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        eng.drain()
        for p, g, r in zip(prompts, gens, reqs):
            assert r.done and r.finish_reason == "length"
            want = _expected_greedy(params, cfg, greedy_generate, p, g)
            np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                          want)

    def test_slot_reuse_over_stale_cache(self, gpt_setup):
        """A slot freed by a LONG request and reused by a SHORT one
        leaves stale K/V beyond the new prompt; the position mask keeps
        it invisible and the stream exact."""
        cfg, params = gpt_setup
        eng = ServingEngine(params, cfg, family="gpt", num_slots=1,
                            max_len=MAXLEN)
        long_p, short_p = _prompts([14, 3], seed=2)
        eng.submit(long_p, 8)
        r2 = eng.submit(short_p, 6)
        eng.drain()
        want = _expected_greedy(params, cfg, greedy_generate, short_p, 6)
        np.testing.assert_array_equal(np.asarray(r2.tokens, np.int32),
                                      want)

    def test_zero_recompiles_after_warmup(self, gpt_setup):
        """Acceptance: after a warmup covering the prompt buckets, NEW
        lengths and join/leave patterns add zero traces; the decode
        tick holds exactly one trace throughout."""
        cfg, params = gpt_setup
        eng = ServingEngine(params, cfg, family="gpt", num_slots=3,
                            max_len=MAXLEN)
        eng.generate(_prompts([3, 9, 5, 16], seed=4), 3)   # buckets 8,16
        dec0, pre0 = eng.trace_counts()
        assert dec0 == 1
        # different lengths, counts and finish patterns, same buckets
        for p in (_prompts([7, 2, 11, 4, 15, 8], seed=5),
                  _prompts([6, 13], seed=6)):
            eng.generate(p, 5)
        dec1, pre1 = eng.trace_counts()
        assert (dec1, pre1) == (dec0, pre0)

    def test_eos_eviction_and_midstream_join(self, gpt_setup):
        """EOS evicts immediately; the freed slot admits the queued
        request whose stream must still be exact."""
        cfg, params = gpt_setup
        prompts = _prompts([5, 7], seed=7)
        want0 = _expected_greedy(params, cfg, greedy_generate,
                                 prompts[0], 8)
        eos = int(want0[2])
        eng = ServingEngine(params, cfg, family="gpt", num_slots=1,
                            max_len=MAXLEN)
        r0 = eng.submit(prompts[0], 8, eos_id=eos)
        r1 = eng.submit(prompts[1], 4)
        eng.drain()
        assert r0.finish_reason == "eos"
        assert r0.tokens == [int(t) for t in
                             want0[:np.nonzero(want0 == eos)[0][0] + 1]]
        want1 = _expected_greedy(params, cfg, greedy_generate,
                                 prompts[1], 4)
        np.testing.assert_array_equal(np.asarray(r1.tokens, np.int32),
                                      want1)

    def test_submit_validation(self, gpt_setup):
        cfg, params = gpt_setup
        eng = ServingEngine(params, cfg, family="gpt", num_slots=2,
                            max_len=16)
        with pytest.raises(ValueError):
            eng.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(ValueError):
            eng.submit(np.zeros(4, np.int32), 0)
        with pytest.raises(ValueError):
            eng.submit(np.zeros(14, np.int32), 4)   # 14+4 > 16
        with pytest.raises(ValueError):
            eng.submit(np.zeros(4, np.int32), 4, top_k=3)  # max_top_k=0

    def test_step_emissions_and_monitor(self, gpt_setup):
        cfg, params = gpt_setup
        from paddle_tpu.profiler import monitor
        sub0 = monitor.counter("serving.requests_submitted").value
        tok0 = monitor.counter("serving.tokens_emitted").value
        eng = ServingEngine(params, cfg, family="gpt", num_slots=2,
                            max_len=MAXLEN)
        r = eng.submit(_prompts([4], seed=8)[0], 3)
        seen = []
        while eng.has_work():
            for req, tok in eng.step():
                assert req is r
                seen.append(tok)
        assert seen == r.tokens and len(seen) == 3
        assert monitor.counter("serving.requests_submitted").value \
            == sub0 + 1
        assert monitor.counter("serving.tokens_emitted").value == tok0 + 3


class TestServingLlama:
    def test_streams_match_greedy_gqa(self, llama_setup):
        """The GQA cache shape ([L, N, S, KV, hd], KV < H) through the
        same engine: continuous batching equals solo greedy decode."""
        cfg, params = llama_setup
        lens = [4, 9, 6, 12, 3]
        gens = [5, 3, 6, 4, 5]
        prompts = _prompts(lens, seed=9)
        eng = ServingEngine(params, cfg, family="llama", num_slots=2,
                            max_len=MAXLEN)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        eng.drain()
        for p, g, r in zip(prompts, gens, reqs):
            want = _expected_greedy(params, cfg,
                                    llama_mod.greedy_generate, p, g)
            np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                          want)

    def test_llama_bucketed_trace_count(self, llama_setup):
        cfg, params = llama_setup
        fn = generate_fn(llama_mod.llama_forward_cached,
                         llama_mod.init_kv_cache, cfg, 3, MAXLEN)
        n0 = fn._cache_size()
        for L in (2, 6, 8):
            llama_mod.greedy_generate(
                params, jnp.asarray(_prompts([L], seed=L)[0])[None],
                cfg, 3, max_len=MAXLEN)
        assert fn._cache_size() - n0 <= 1


class TestSampling:
    def test_temperature_reproducible_and_slot_invariant(self, gpt_setup):
        """Sampled streams fold (request id, token index) into the
        engine key: identical across runs AND across pool sizes (slot
        placement / batch composition must not leak into the rng)."""
        cfg, params = gpt_setup
        prompts = _prompts([5, 7, 3], seed=10)
        outs = []
        for slots in (3, 1):
            eng = ServingEngine(params, cfg, family="gpt",
                                num_slots=slots, max_len=MAXLEN,
                                max_top_k=8, seed=11)
            outs.append(eng.generate(prompts, 6, temperature=0.9,
                                     top_k=5))
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a, b)
        for o in outs[0]:
            assert np.all(o >= 0) and np.all(o < cfg.vocab_size)

    def test_top_k_one_is_greedy(self, gpt_setup):
        """top_k=1 truncates to the argmax bucket: any temperature must
        reproduce the greedy stream exactly."""
        cfg, params = gpt_setup
        p = _prompts([6], seed=12)[0]
        eng = ServingEngine(params, cfg, family="gpt", num_slots=2,
                            max_len=MAXLEN, max_top_k=4)
        out = eng.generate([p], 5, temperature=1.3, top_k=1)[0]
        want = _expected_greedy(params, cfg, greedy_generate, p, 5)
        np.testing.assert_array_equal(out, want)

    def test_mixed_greedy_and_sampled_requests(self, gpt_setup):
        """Greedy requests stay bit-exact while sharing ticks with
        sampled ones (the static sampling flag covers the batch)."""
        cfg, params = gpt_setup
        prompts = _prompts([5, 8], seed=13)
        eng = ServingEngine(params, cfg, family="gpt", num_slots=2,
                            max_len=MAXLEN, max_top_k=4)
        r_g = eng.submit(prompts[0], 6)                    # greedy
        r_s = eng.submit(prompts[1], 6, temperature=1.0, top_k=4)
        eng.drain()
        want = _expected_greedy(params, cfg, greedy_generate,
                                prompts[0], 6)
        np.testing.assert_array_equal(np.asarray(r_g.tokens, np.int32),
                                      want)
        assert len(r_s.tokens) == 6


# --------------------------------------------------------------------------
# facade / hapi exposure + observability + compile-cache satellite
# --------------------------------------------------------------------------
class TestExposure:
    def test_facade_and_hapi_generate(self, gpt_setup):
        cfg, _ = gpt_setup
        from paddle_tpu.models.gpt import GPTModel
        from paddle_tpu.hapi import Model
        gm = GPTModel(cfg)
        prompts = _prompts([5, 9], seed=14)
        outs = gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN)
        assert [o.shape for o in outs] == [(4,), (4,)]
        # engine is cached across calls with the same pool knobs
        eng = gm._serving_engine
        gm.generate(prompts, 4, num_slots=2, max_len=MAXLEN)
        assert gm._serving_engine is eng
        outs2 = Model(gm).generate(prompts, 4, num_slots=2,
                                   max_len=MAXLEN)
        for a, b in zip(outs, outs2):
            np.testing.assert_array_equal(a, b)
        # parity with the engine built from raw params
        from paddle_tpu.framework.dispatch import raw_value
        params = {n: raw_value(p) for n, p in gm._params.items()}
        want = _expected_greedy(params, cfg, greedy_generate,
                                prompts[0], 4)
        np.testing.assert_array_equal(outs[0], want)

    def test_hapi_generate_rejects_non_decoder(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model
        with pytest.raises(NotImplementedError):
            Model(nn.Linear(4, 4)).generate([[1, 2]], 3)

    def test_telemetry_report_serving_section(self, gpt_setup, tmp_path):
        cfg, params = gpt_setup
        from paddle_tpu.profiler import monitor
        import sys, os
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from telemetry_report import summarize
        path = str(tmp_path / "serve.jsonl")
        monitor.registry().export_jsonl(path)
        eng = ServingEngine(params, cfg, family="gpt", num_slots=2,
                            max_len=MAXLEN)
        eng.generate(_prompts([4, 6], seed=15), 3)
        monitor.registry().export_jsonl(path)
        doc = summarize(path)
        assert doc["serving"]["tokens_emitted"] >= 6
        assert doc["serving"]["prefills"] >= 2
        assert "decode_ticks" in doc["serving"]


class TestCompileCacheHelpers:
    def test_xla_cache_dir_is_in_the_checkout(self):
        from paddle_tpu.utils import compile_cache as cc
        import os
        d = cc.xla_cache_dir()
        assert os.path.isdir(d) and d.endswith(os.path.join("perf",
                                                            "xla_cache"))

    def test_sync_policy(self, monkeypatch):
        """With nothing set from outside, the TPU gets the checkout's
        cache and the CPU gets none."""
        from paddle_tpu.utils import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prior = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            cc.sync_compile_cache_for("tpu")
            assert jax.config.jax_compilation_cache_dir == \
                cc.xla_cache_dir()
            cc.sync_compile_cache_for("cpu")
            assert jax.config.jax_compilation_cache_dir is None
        finally:
            jax.config.update("jax_compilation_cache_dir", prior)
