"""Inference Predictor + KV-cache decode tests.

Reference analog: AnalysisPredictor serving loop
(inference/api/analysis_predictor.h:94) and the FusedMultiTransformer
cached decoder (incubate/nn/layer/fused_transformer.py:1022).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params, gpt_forward,
                                   init_kv_cache, gpt_forward_cached,
                                   greedy_generate)


def _small_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=3,
                     num_heads=2, ffn_hidden=64, max_seq_len=32,
                     sequence_parallel=False, remat=False,
                     dtype=jnp.float32)


class TestPredictor:
    def _save_model(self, tmp_path):
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        from paddle_tpu.jit import InputSpec
        path = str(tmp_path / "m" / "model")
        paddle.jit.save(model, path,
                        input_spec=[InputSpec([2, 8], "float32")])
        return model, path

    def test_named_handle_serving_loop(self, tmp_path):
        model, path = self._save_model(tmp_path)
        config = Config(path + ".pdmodel")
        predictor = create_predictor(config)
        names = predictor.get_input_names()
        assert len(names) == 1
        x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
        h = predictor.get_input_handle(names[0])
        h.reshape([2, 8])
        h.copy_from_cpu(x)
        predictor.run()
        out_names = predictor.get_output_names()
        assert len(out_names) == 1
        got = predictor.get_output_handle(out_names[0]).copy_to_cpu()
        model.eval()
        want = model(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_positional_run(self, tmp_path):
        model, path = self._save_model(tmp_path)
        predictor = create_predictor(Config(path))
        x = np.ones((2, 8), np.float32)
        outs = predictor.run([x])
        assert outs[0].shape == (2, 4)

    def test_clone(self, tmp_path):
        _, path = self._save_model(tmp_path)
        p1 = create_predictor(Config(path))
        p2 = p1.clone()
        x = np.ones((2, 8), np.float32)
        np.testing.assert_array_equal(p1.run([x])[0], p2.run([x])[0])

    def test_config_compat_surface(self):
        c = Config("/tmp/foo.pdmodel")
        c.enable_use_gpu(100, 0)       # accepted, XLA owns placement
        c.enable_tensorrt_engine()
        c.switch_ir_optim(True)
        assert not c.use_gpu()
        assert "Config" in c.summary()

    def test_precision_applied_to_params(self, tmp_path):
        """Config._precision is honored (the round-5 silent-ignore
        fix): bf16/f16 land as a weight round-trip cast on the loaded
        params (the StableHLO artifact pins compute dtypes at save)."""
        from paddle_tpu.inference import PrecisionType
        _, path = self._save_model(tmp_path)
        for prec, dt in ((PrecisionType.Bfloat16, jnp.bfloat16),
                         (PrecisionType.Half, jnp.float16)):
            p = create_predictor(Config(path).set_precision(prec))
            for w in p._layer._params:
                np.testing.assert_array_equal(
                    np.asarray(w),
                    np.asarray(w.astype(dt).astype(w.dtype)))
            p.run([np.ones((2, 8), np.float32)])   # still serves

    def test_precision_int8_round_trip(self, tmp_path):
        """Int8 routes to the weight-only converter (per-output-
        channel round-trip on every floating matrix param — the
        serving engines' quant= path applied at Predictor load):
        weights land exactly on their int8 grid, vectors stay fp, and
        the served outputs sit inside a logit-error budget vs fp."""
        from paddle_tpu.inference import PrecisionType
        from paddle_tpu.quantization.int8 import quantize_weight
        _, path = self._save_model(tmp_path)
        fp = create_predictor(Config(path))
        p8 = create_predictor(Config(path).set_precision(
            PrecisionType.Int8))
        changed = 0
        for w_fp, w_q in zip(fp._layer._params, p8._layer._params):
            w_fp, w_q = np.asarray(w_fp), np.asarray(w_q)
            if w_fp.ndim < 2:
                np.testing.assert_array_equal(w_fp, w_q)  # vectors fp
                continue
            # round-tripping the quantized weights is a FIXED POINT:
            # they already sit on their per-channel int8 grid
            q, s = quantize_weight(w_q.astype(np.float32),
                                   channel_axis=w_q.ndim - 1)
            shape = (1,) * (w_q.ndim - 1) + (-1,)
            np.testing.assert_allclose(
                w_q, q.astype(np.float32) * (s / 127.0).reshape(shape),
                rtol=1e-6, atol=1e-7)
            changed += int(not np.array_equal(w_fp, w_q))
        assert changed > 0
        x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
        out_fp, out_q = fp.run([x])[0], p8.run([x])[0]
        span = max(float(np.abs(out_fp).max()), 1.0)
        assert float(np.abs(out_fp - out_q).max()) < 0.05 * span

    def test_precision_unknown_refused(self):
        with pytest.raises(ValueError):
            Config("/tmp/foo.pdmodel").set_precision("int4")

    def test_tensorrt_precision_mode_sets_precision(self):
        from paddle_tpu.inference import PrecisionType
        c = Config("/tmp/foo.pdmodel")
        c.enable_tensorrt_engine(precision_mode=PrecisionType.Half)
        assert c._precision == PrecisionType.Half

    def test_output_handles_cached_across_runs(self, tmp_path):
        _, path = self._save_model(tmp_path)
        p = create_predictor(Config(path))
        x = np.ones((2, 8), np.float32)
        out1 = p.run([x])[0]
        h1 = p.get_output_handle(p.get_output_names()[0])
        out2 = p.run([x + 1])[0]
        h2 = p.get_output_handle(p.get_output_names()[0])
        assert h1 is h2                 # refilled in place, not rebuilt
        np.testing.assert_array_equal(h2.copy_to_cpu(), out2)
        assert not np.array_equal(out1, out2)


class TestKVCacheDecode:
    def test_prefill_matches_full_forward(self):
        cfg = _small_cfg()
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        cache = init_kv_cache(cfg, 2, 16)
        lg_c, cache = gpt_forward_cached(params, toks, cache, 0, cfg)
        lg_f = gpt_forward(params, toks, cfg)
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_f),
                                   atol=1e-5)
        # cache holds the prompt k/v (nonzero), tail empty
        assert float(jnp.abs(cache["k"][:, :, :8]).sum()) > 0
        assert float(jnp.abs(cache["k"][:, :, 8:]).sum()) == 0

    def test_decode_step_matches_full_forward(self):
        cfg = _small_cfg()
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        cache = init_kv_cache(cfg, 2, 16)
        _, cache = gpt_forward_cached(params, toks, cache, 0, cfg)
        nxt = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, 64)
        lg_d, _ = gpt_forward_cached(params, nxt, cache, 8, cfg)
        lg_f = gpt_forward(params, jnp.concatenate([toks, nxt], 1), cfg)
        np.testing.assert_allclose(np.asarray(lg_d[:, 0]),
                                   np.asarray(lg_f[:, -1]), atol=1e-5)

    def test_moe_decode_matches_full_forward(self):
        """MoE configs decode through the cache too (reference inference
        global_scatter path). capacity_factor = num_experts guarantees no
        token drops, so cached decode must equal the full forward."""
        cfg = _small_cfg()
        import dataclasses
        cfg = dataclasses.replace(cfg, num_experts=2,
                                  expert_capacity_factor=2.0,
                                  moe_gate="switch", moe_aux_weight=0.0)
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        cache = init_kv_cache(cfg, 2, 16)
        _, cache = gpt_forward_cached(params, toks, cache, 0, cfg)
        nxt = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, 64)
        lg_d, _ = gpt_forward_cached(params, nxt, cache, 8, cfg)
        lg_f = gpt_forward(params, jnp.concatenate([toks, nxt], 1), cfg)
        np.testing.assert_allclose(np.asarray(lg_d[:, 0]),
                                   np.asarray(lg_f[:, -1]), atol=2e-3,
                                   rtol=2e-3)

    def test_greedy_generate_parity_vs_nocache(self):
        """The acceptance test: greedy decode with KV cache equals
        argmax over the no-cache full forward at every step."""
        cfg = _small_cfg()
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 64)
        out = greedy_generate(params, prompt, cfg, 7, max_len=16)
        cur = prompt
        for _ in range(7):
            lg = gpt_forward(params, cur, cfg)
            nx = jnp.argmax(lg[:, -1].astype(jnp.float32), -1)[:, None]
            cur = jnp.concatenate([cur, nx], 1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_generate_jits_once(self):
        """greedy_generate is scan-based: wrap in jit and run twice with
        different prompts — same compiled fn, consistent outputs."""
        cfg = _small_cfg()
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        import functools
        gen = jax.jit(functools.partial(greedy_generate, cfg=cfg,
                                        max_new_tokens=4, max_len=16))
        p1 = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        p2 = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        o1, o2 = gen(params, p1), gen(params, p2)
        assert o1.shape == o2.shape == (1, 8)
