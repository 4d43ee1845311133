"""Weight-only int8 quantized serving (inference/serving.py quant=,
kernels/quant_matmul.py, quantization/serving.py).

The load-bearing guarantees:

- the stacked quantizer is numerically identical to the per-layer
  reference (quantize_weight_stacked vs quantize_weight per layer);
- the Pallas fused dequant-matmul is BITWISE identical to the XLA impl
  in interpret mode (same contraction, same f32 accumulation), and
  both sit within one rounding of the dequant-first jax oracle;
- a quantized engine's streams are bit-identical ACROSS layouts —
  dense/paged, spec on/off, tp-sharded/unsharded, gpt and llama/GQA —
  (weight-only dequant is deterministic), while quant-vs-fp logits
  carry a measured error budget;
- selection: the quant= argument alone ("auto" is off), and the
  matmul constant takes its Pallas form on the TPU only;
- the engine invariants survive quantization: trace-count ceilings,
  one host pull per tick, cache-key distinctness of facade quant=.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.kernels import quant_matmul as qm
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
from paddle_tpu.models import llama as llama_mod
from paddle_tpu.quantization.int8 import (quantize_weight,
                                          quantize_weight_stacked)
from paddle_tpu.quantization.serving import quantize_serving_params

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


LENS = (5, 9, 13, 3)


def _setup_for(family, gpt_setup, llama_setup):
    return gpt_setup if family == "gpt" else llama_setup


# --------------------------------------------------------------------------
# quantizer parity
# --------------------------------------------------------------------------
def test_stacked_quantize_matches_per_layer_loop():
    w = np.random.RandomState(0).randn(4, 6, 10).astype(np.float32) * 3
    w_q, scale = quantize_weight_stacked(w)
    assert w_q.dtype == np.int8 and scale.shape == (4, 10)
    for l in range(w.shape[0]):
        w_q1, scale1 = quantize_weight(w[l], channel_axis=w.ndim - 2)
        np.testing.assert_array_equal(w_q[l], w_q1)
        np.testing.assert_array_equal(scale[l], scale1)


def test_stacked_quantize_rejects_matrices():
    with pytest.raises(ValueError):
        quantize_weight_stacked(np.zeros((3, 4), np.float32))


def test_quantize_serving_params_tree_shape(gpt_setup):
    cfg, params = gpt_setup
    qp, qspecs, info = quantize_serving_params(
        params, "gpt", {"qkv_w": P(None, None, "tp"),
                        "attn_out_w": P(None, "tp", None),
                        "wte": P("tp", None)})
    # fp matmul leaves dropped, int8 pairs + transposed head added
    for name in info["quant_leaf_names"]:
        assert name not in qp
        assert qp[name + "_q"].dtype == jnp.int8
        assert qp[name + "_scale"].dtype == jnp.float32
    assert "wte" in qp                      # embedding stays fp
    assert qp["head_q"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert qp["head_scale"].shape == (cfg.vocab_size,)
    assert info["quant_bytes"] < 0.55 * info["fp_bytes"]
    # scale specs follow the weight's output-channel axis: column-
    # parallel scales tp-shard, row-parallel scales replicate, the
    # head flips the vocab-parallel embedding spec
    assert qspecs["qkv_w_scale"] == P(None, "tp")
    assert qspecs["attn_out_w_scale"] == P(None, None)
    assert qspecs["head_q"] == P(None, "tp")
    assert qspecs["head_scale"] == P("tp")


def test_quantize_serving_params_unknown_family(gpt_setup):
    with pytest.raises(ValueError, match="quant leaf table"):
        quantize_serving_params(gpt_setup[1], "bert")


# --------------------------------------------------------------------------
# the fused dequant-matmul kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N", [(5, 32, 48), (1, 200, 130),
                                   (130, 64, 64)])
def test_pallas_interpret_bitwise_matches_xla(M, K, N):
    rng = np.random.RandomState(1)
    w_q, scale = quantize_weight(
        rng.randn(K, N).astype(np.float32), channel_axis=1)
    scale = (scale / 127.0).astype(np.float32)
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    y_xla = qm.quant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                            impl="xla")
    y_pl = qm.quant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                           impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_pl))


def test_quant_matmul_vs_dequant_first_oracle():
    rng = np.random.RandomState(2)
    w_q, scale = quantize_weight(
        rng.randn(16, 24).astype(np.float32), channel_axis=1)
    scale = (scale / 127.0).astype(np.float32)
    x = rng.randn(3, 7, 16).astype(np.float32)
    y = qm.quant_matmul(jnp.asarray(x), jnp.asarray(w_q),
                        jnp.asarray(scale), impl="xla")
    oracle = x.reshape(-1, 16) @ (w_q.astype(np.float32)
                                  * scale[None, :])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 24), oracle,
                               rtol=1e-5, atol=1e-5)
    assert y.shape == (3, 7, 24) and y.dtype == jnp.float32


def test_quant_matmul_preserves_dtype():
    w_q, scale = quantize_weight(
        np.random.RandomState(3).randn(8, 8).astype(np.float32),
        channel_axis=1)
    x = jnp.ones((2, 8), jnp.bfloat16)
    y = qm.quant_matmul(x, jnp.asarray(w_q),
                        jnp.asarray(scale / 127.0))
    assert y.dtype == jnp.bfloat16


def test_leaf_matmul_routes_by_tree():
    rng = np.random.RandomState(4)
    w = rng.randn(8, 12).astype(np.float32)
    x = jnp.asarray(rng.randn(2, 3, 8).astype(np.float32))
    y_fp = qm.leaf_matmul(x, {"w": jnp.asarray(w)}, "w")
    # a float32 reduction over 8 terms: summation order alone moves a
    # small sum by an ulp (1.5e-6 relative)
    np.testing.assert_allclose(
        np.asarray(y_fp), np.einsum("btk,kn->btn", np.asarray(x), w),
        rtol=1e-5)
    w_q, scale = quantize_weight(w, channel_axis=1)
    y_q = qm.leaf_matmul(
        x, {"w_q": jnp.asarray(w_q),
            "w_scale": jnp.asarray(scale / 127.0)}, "w")
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_fp),
                               atol=0.15)


# --------------------------------------------------------------------------
# selection: the quant= argument, and the matmul constant
# --------------------------------------------------------------------------
def test_resolve_validates():
    assert qm.resolve_quant("int8") is True
    assert qm.resolve_quant("off") is False
    assert qm.resolve_quant("auto") is False
    with pytest.raises(ValueError):
        qm.resolve_quant("fp8")


def test_matmul_impl_follows_the_constant_on_tpu_only(monkeypatch):
    assert qm.matmul_impl() == "xla"
    monkeypatch.setattr(qm, "QUANT_MATMUL_IMPL", "pallas")
    # off-TPU the matmul site degrades to the identical xla form
    assert qm.matmul_impl() == "xla"
    monkeypatch.setattr(qm, "is_tpu", lambda: True)
    assert qm.matmul_impl() == "pallas"
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert qm.matmul_impl() == "xla"


# --------------------------------------------------------------------------
# the quantized engine: stream matrix + error budgets
# --------------------------------------------------------------------------
def _engine(params, cfg, family, **kw):
    kw.setdefault("num_slots", 4)
    return ServingEngine(params, cfg, family=family, max_len=MAXLEN,
                         **kw)


def _streams(params, cfg, family, **kw):
    eng = _engine(params, cfg, family, **kw)
    outs = eng.generate(_prompts(LENS), 8)
    return eng, [np.asarray(o) for o in outs]


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_quant_streams_identical_across_layouts(family, gpt_setup,
                                                llama_setup):
    cfg, params = _setup_for(family, gpt_setup, llama_setup)
    _, dense = _streams(params, cfg, family, quant="int8")
    _, paged = _streams(params, cfg, family, quant="int8",
                        kv_layout="paged", page_size=8)
    _, spec = _streams(params, cfg, family, quant="int8",
                       spec_decode="spec", gamma=2,
                       draft_layers=cfg.num_layers)
    _, spec_paged = _streams(params, cfg, family, quant="int8",
                             kv_layout="paged", page_size=8,
                             spec_decode="spec", gamma=2,
                             draft_layers=cfg.num_layers)
    for other in (paged, spec, spec_paged):
        for a, b in zip(dense, other):
            np.testing.assert_array_equal(a, b)
    assert all(len(s) == 8 for s in dense)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_quant_logit_error_budget(family, gpt_setup, llama_setup):
    """Quant-vs-fp logits shift by the weight-only dequant error —
    bounded, and small relative to the logit span (the BASELINE.md
    budget methodology)."""
    cfg, params = _setup_for(family, gpt_setup, llama_setup)
    from paddle_tpu.inference.serving import family_for
    fam = family_for(family)
    qp, _, _ = quantize_serving_params(params, family)
    toks = jnp.asarray(_prompts((12,), seed=5)[0])[None]
    lg_fp, _ = fam.forward_cached(
        params, toks, fam.init_cache(cfg, 1, 12), 0, cfg)
    lg_q, _ = fam.forward_cached(
        qp, toks, fam.init_cache(cfg, 1, 12), 0, cfg)
    err = float(jnp.max(jnp.abs(lg_fp - lg_q)))
    span = float(jnp.max(jnp.abs(lg_fp)))
    assert err < 0.05 * max(span, 1.0), (err, span)


def test_quant_sampled_streams_reproducible(gpt_setup):
    cfg, params = gpt_setup
    _, a = _streams(params, cfg, "gpt", quant="int8", max_top_k=4)
    _, b = _streams(params, cfg, "gpt", quant="int8", max_top_k=4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_quant_tp_bit_parity_and_scale_shardings(gpt_setup):
    from paddle_tpu.parallel.mesh import build_mesh
    cfg, params = gpt_setup
    mesh = build_mesh({"tp": 2})
    _, base = _streams(params, cfg, "gpt", quant="int8")
    eng, tp = _streams(params, cfg, "gpt", quant="int8", mesh=mesh)
    for a, b in zip(base, tp):
        np.testing.assert_array_equal(a, b)
    # column-parallel scales carry tp on the output axis; row-parallel
    # scales replicate; the head stays vocab-parallel
    assert "tp" in str(eng._params["qkv_w_q"].sharding.spec)
    assert "tp" in str(eng._params["qkv_w_scale"].sharding.spec)
    assert "tp" not in str(eng._params["attn_out_w_scale"].sharding.spec)
    assert "tp" in str(eng._params["head_scale"].sharding.spec)


def test_quant_trace_ceilings_and_one_pull_per_tick(gpt_setup):
    cfg, params = gpt_setup
    eng = _engine(params, cfg, "gpt", quant="int8")
    counts = [0]
    orig = eng._pull

    def counted(value, stall_s=0.0):
        counts[0] += 1
        return orig(value, stall_s)
    eng._pull = counted
    eng.generate(_prompts(LENS), 8)
    warm = eng.trace_counts()
    t0 = eng._ticks
    counts[0] = 0
    n_pre = len(LENS)
    eng.generate(_prompts(LENS), 8)
    assert eng.trace_counts() == warm          # zero recompiles
    decode_ticks = eng._ticks - t0
    # one pull per decode tick + one per prefill
    assert counts[0] <= decode_ticks + n_pre
    assert warm[0] <= 2


def test_quant_telemetry_surface(gpt_setup):
    from paddle_tpu.profiler import monitor
    cfg, params = gpt_setup
    q0 = monitor.counter("serving.quant_matmuls").value
    eng = _engine(params, cfg, "gpt", quant="int8")
    eng.generate(_prompts(LENS), 4)
    st = eng.quant_stats()
    assert st["quant"] == "int8"
    assert monitor.gauge("serving.quant_weights_bytes").value \
        == st["quant_bytes"]
    assert monitor.gauge("serving.fp_weights_bytes").value \
        == st["fp_bytes"]
    assert st["quant_bytes"] < 0.55 * st["fp_bytes"]
    # per tick: per_layer * L + head fused matmuls
    per_pass = st["per_layer"] * cfg.num_layers + st["head"]
    moved = monitor.counter("serving.quant_matmuls").value - q0
    assert moved > 0 and moved % per_pass == 0


def test_quant_off_engine_has_no_quant_leaves(gpt_setup):
    cfg, params = gpt_setup
    eng = _engine(params, cfg, "gpt")             # default auto -> off
    assert eng.quant is False
    assert eng.quant_stats() == {"quant": "off"}
    assert not any(k.endswith("_q") for k in eng._params)


def test_facade_engine_cache_key_quant_distinct(gpt_setup):
    from paddle_tpu.models.gpt import GPTModel
    model = GPTModel(_gpt_cfg())
    prompts = _prompts((4, 6))
    model.generate(prompts, 2)
    e_fp = model._serving_engine
    model.generate(prompts, 2, quant="int8")
    e_q = model._serving_engine
    assert e_q is not e_fp and e_q.quant is True
    model.generate(prompts, 2, quant="int8")
    assert model._serving_engine is e_q           # stable reuse
    model.generate(prompts, 2)
    assert model._serving_engine is not e_q


def test_quant_guardrails_poison_isolation(gpt_setup):
    """The in-jit quarantine still isolates a poisoned slot on the
    quantized engine (the chaos_serving quant_nan_logits assertion,
    in-process)."""
    from paddle_tpu.testing import faults
    cfg, params = gpt_setup
    _, want = _streams(params, cfg, "gpt", quant="int8")
    faults.install("nan_logits@2:1")
    try:
        eng = _engine(params, cfg, "gpt", quant="int8")
        reqs = [eng.submit(p, 8) for p in _prompts(LENS)]
        eng.drain()
    finally:
        faults.uninstall()
    reasons = [r.finish_reason for r in reqs]
    assert reasons.count("poisoned") == 1
    for r, w in zip(reqs, want):
        got = np.asarray(r.tokens, np.int32)
        if r.finish_reason == "poisoned":
            np.testing.assert_array_equal(got, w[:len(got)])
        else:
            np.testing.assert_array_equal(got, w)
