"""The serving weights are rounded to the compute dtype once, at engine
build (quantization/serving.py round_serving_params), not by the cached
forward's `astype` on every tick and every prefill.

The load-bearing guarantees:

- rounding is element-wise, so the cached forward over the rounded tree
  is BIT-IDENTICAL — logits and KV pools — to the forward over the
  float32 tree it came from, for both families, both cache layouts, with
  and without the int8 pairs, at prefill, per-row decode and the
  `layers=` draft;
- an engine built from float32 leaves serves the token streams it served
  when it kept them float32;
- what is done follows from the tree: idempotent, norm leaves (and
  gpt's `wpe`) stay float32, a tree at the compute dtype and a family
  with no table come back as the same object;
- replicas of one router on one device share ONE rounded tree, and the
  gauges read what was handed and what is held.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.router import create_router
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import cohere2_moe as cohere_mod
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.models import llama as llama_mod
from paddle_tpu.profiler import monitor
from paddle_tpu.quantization import serving as qs
from paddle_tpu.quantization.serving import (COMPUTE_LEAVES,
                                             quantize_serving_params,
                                             round_serving_params,
                                             tree_bytes)

VOCAB, MAXLEN, PAGE = 64, 32, 8
# what the forward reads in float32, or (gpt's `wpe`) converts row by
# row after slicing: kept as handed
KEPT = {"gpt": ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                "ln_f_scale", "ln_f_bias", "wpe"),
        "llama": ("attn_norm", "ffn_norm", "norm_f")}


def _cfg(family, dtype=jnp.bfloat16):
    if family == "gpt":
        return gpt_mod.GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
            ffn_hidden=64, max_seq_len=64, sequence_parallel=False,
            remat=False, dtype=dtype)
    return llama_mod.LlamaConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, dtype=dtype, remat=False)


def _setup(family, dtype=jnp.bfloat16):
    """(cfg, float32 params with every leaf drawn non-trivial, forward,
    init_cache)."""
    cfg = _cfg(family, dtype)
    mod = gpt_mod if family == "gpt" else llama_mod
    init = (gpt_mod.init_gpt_params if family == "gpt"
            else llama_mod.init_llama_params)
    params = init(cfg, jax.random.PRNGKey(0))
    # biases and norm leaves start at 0 / 1: perturb them so that a
    # rounded bias, or a norm leaf wrongly rounded, would show
    params = {n: v + 0.05 * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1), i), v.shape, v.dtype)
        if v.ndim <= 2 and n != "wte" else v
        for i, (n, v) in enumerate(sorted(params.items()))}
    assert all(v.dtype == jnp.float32 for v in params.values())
    fwd = (gpt_mod.gpt_forward_cached if family == "gpt"
           else llama_mod.llama_forward_cached)
    return cfg, params, fwd, mod.init_kv_cache


def _cache(init_cache, cfg, batch, paged):
    if not paged:
        return init_cache(cfg, batch, MAXLEN)
    probe = jax.eval_shape(lambda: init_cache(cfg, 1, 1))["k"]
    max_pages = MAXLEN // PAGE
    shape = (probe.shape[0], batch * max_pages + 1, PAGE) + probe.shape[3:]
    table = 1 + np.arange(batch * max_pages, dtype=np.int32).reshape(
        batch, max_pages)
    return {"k": jnp.zeros(shape, probe.dtype),
            "v": jnp.zeros(shape, probe.dtype), "pt": jnp.asarray(table)}


def _bits(x):
    a = np.asarray(x)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


def _same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("mode", ["prefill", "decode", "draft"])
@pytest.mark.parametrize("quant", ["off", "int8"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_forward_over_rounded_tree_is_bit_identical(family, layout, quant,
                                                    mode):
    cfg, params, fwd, init_cache = _setup(family)
    if quant == "int8":
        params, _, _ = quantize_serving_params(params, family)
    rounded = round_serving_params(params, family, cfg)
    assert rounded is not params
    assert rounded["wte"].dtype == jnp.bfloat16
    paged = layout == "paged"
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, VOCAB, (2, 9)), jnp.int32)
    start = jnp.zeros((2,), jnp.int32) if paged else 0
    cache = _cache(init_cache, cfg, 2, paged)
    layers = None
    if mode == "prefill":
        tokens, pos = prompt, start
    else:
        # both trees continue from ONE filled cache, each row at a
        # position of its own
        _, cache = jax.jit(
            lambda p, c: fwd(p, prompt, c, start, cfg))(params, cache)
        tokens = jnp.asarray(rng.randint(0, VOCAB, (2, 1)), jnp.int32)
        pos = jnp.asarray([9, 6], jnp.int32)
        if mode == "draft":
            layers = 1
            cache = {k: (v if k == "pt" else v[:layers])
                     for k, v in cache.items()}
    step = jax.jit(lambda p, c: fwd(p, tokens, c, pos, cfg, layers=layers))
    want_logits, want_cache = step(params, cache)
    got_logits, got_cache = step(rounded, cache)
    assert want_logits.dtype == jnp.bfloat16
    _same(got_logits, want_logits)
    assert set(got_cache) == set(want_cache)
    for name in want_cache:
        _same(got_cache[name], want_cache[name])
    assert np.asarray(want_cache["k"], np.float32).any()


@pytest.mark.parametrize("knobs", [
    dict(kv_layout="dense", spec_decode="off"),
    dict(kv_layout="paged", spec_decode="off", page_size=PAGE),
    dict(kv_layout="dense", spec_decode="spec", gamma=2),
], ids=["dense", "paged", "spec"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_engine_serves_the_streams_it_served_in_float32(family, knobs,
                                                        monkeypatch):
    cfg, params, _, _ = _setup(family)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (5, 9, 13, 3)]
    kw = dict(family=family, num_slots=2, max_len=MAXLEN, quant="off",
              **knobs)
    eng = ServingEngine(params, cfg, **kw)
    assert eng._params["wte"].dtype == jnp.bfloat16
    got = eng.generate(prompts, 8)
    # the engine as it was: the leaves kept as handed
    monkeypatch.setattr(qs, "round_serving_params", lambda p, f, c: p)
    before = ServingEngine(params, cfg, **kw)
    assert before._params["wte"].dtype == jnp.float32
    want = before.generate(prompts, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_rounds_the_table_and_nothing_else(family):
    cfg, params, _, _ = _setup(family)
    rounded = round_serving_params(params, family, cfg)
    assert set(rounded) == set(params)
    for name, leaf in rounded.items():
        if name in COMPUTE_LEAVES[family]:
            assert leaf.dtype == jnp.bfloat16, name
            _same(leaf, params[name].astype(jnp.bfloat16))
        else:
            assert leaf is params[name], name
    for name in KEPT[family]:
        assert rounded[name].dtype == jnp.float32
    assert set(KEPT[family]) | set(COMPUTE_LEAVES[family]) == set(params)
    # idempotent: a rounded tree has nothing left to round
    assert round_serving_params(rounded, family, cfg) is rounded
    # and so does a float32 tree under a float32 compute dtype
    cfg32 = _cfg(family, jnp.float32)
    assert round_serving_params(params, family, cfg32) is params


def test_family_without_a_table_is_left_alone():
    cfg = cohere_mod.Cohere2MoeConfig(
        vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_hidden=48, max_seq_len=64,
        sliding_window=8, num_experts=16, experts_held=4, first_expert=4,
        experts_per_token=4, num_shared_experts=2, prefill_chunk=8)
    assert "cohere2_moe" not in COMPUTE_LEAVES
    stored = cohere_mod.init_cohere2_moe_params(cfg, jax.random.PRNGKey(0))
    assert round_serving_params(stored, "cohere2_moe", cfg) is stored
    wide = {n: v.astype(jnp.float32) for n, v in stored.items()}
    assert round_serving_params(wide, "cohere2_moe", cfg) is wide


def test_host_leaves_are_rounded_on_the_host():
    """A numpy tree (on its way to `_shard_params`) is never staged on a
    device, and rounds to the bits the device path gives."""
    cfg, params, _, _ = _setup("gpt")
    on_device = round_serving_params(params, "gpt", cfg)
    host = {n: np.asarray(v) for n, v in params.items()}
    host["qkv_w"] = params["qkv_w"]           # a mixed tree
    rounded = round_serving_params(host, "gpt", cfg)
    for name in COMPUTE_LEAVES["gpt"]:
        assert isinstance(rounded[name], jax.Array) == (name == "qkv_w")
        _same(np.asarray(rounded[name]), np.asarray(on_device[name]))
    assert rounded["ln1_scale"] is host["ln1_scale"]


def test_int8_pairs_come_from_the_leaves_as_handed():
    """Order of the two rewrites: the int8 pairs are quantized from the
    float32 leaves; what stays floating point is rounded afterwards."""
    cfg, params, _, _ = _setup("gpt")
    eng = ServingEngine(params, cfg, num_slots=2, max_len=MAXLEN,
                        quant="int8", spec_decode="off")
    want, _, _ = quantize_serving_params(params, "gpt")
    held = eng._params
    for name in ("qkv_w_q", "qkv_w_scale", "head_q", "head_scale"):
        _same(np.asarray(held[name]), np.asarray(want[name]))
    assert "qkv_w" not in held
    for name in ("wte", "qkv_b", "mlp_down_b"):
        assert held[name].dtype == jnp.bfloat16
    assert held["ln_f_scale"].dtype == held["wpe"].dtype == jnp.float32
    stats = eng.weights_stats()
    assert stats["weights_rounded_leaves"] == 5
    assert stats["weights_bytes"] == tree_bytes(held) < 0.5 * tree_bytes(
        params)


def _count_roundings(monkeypatch) -> list:
    """-> a list that gets the number of leaves of every jitted rounding
    made from here on."""
    calls = []
    real = qs._round_on_device
    monkeypatch.setattr(
        qs, "_round_on_device",
        lambda leaves, dtype: calls.append(len(leaves)) or real(leaves,
                                                                dtype))
    return calls


def test_router_rounds_once_and_replicas_on_one_device_share_the_tree(
        monkeypatch):
    cfg, params, _, _ = _setup("gpt")
    calls = _count_roundings(monkeypatch)
    one = jax.local_devices()[:1]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: one)
    router = create_router(params, cfg, replicas=2, num_slots=2,
                           max_len=MAXLEN, quant="off", spec_decode="off")
    first, second = (r.eng for r in router.replicas)
    assert first._params is second._params
    assert calls == [len(COMPUTE_LEAVES["gpt"])]
    assert first._params["mlp_up_w"].dtype == jnp.bfloat16
    assert first._params["ln1_scale"] is params["ln1_scale"]
    # the gauges and the stats read what was handed and what is held
    given, held = tree_bytes(params), tree_bytes(first._params)
    assert held == given - sum(params[n].nbytes // 2
                               for n in COMPUTE_LEAVES["gpt"])
    assert monitor.gauge("serving.weights_given_bytes").value == given
    assert monitor.gauge("serving.weights_bytes").value == held
    for row in router.stats()["per_replica"]:
        assert row["weights_given_bytes"] == given
        assert row["weights_bytes"] == held
        assert row["weights_rounded_leaves"] == len(COMPUTE_LEAVES["gpt"])
    assert first.memory_ledger()["held"] == first.weights_stats()
    # the analytical ledger prices weights at the compute width, which
    # is now what the tree holds (but for the leaves kept float32 — at
    # this toy width a seventh of the tree, at GPT-1.3B 4 MB of 2.6 GB);
    # the float32 tree as handed is twice the ledger
    ledger = first.memory_ledger()["components"]["weights"]
    assert abs(held - ledger) < 0.2 * ledger < abs(given - ledger)


def test_replicas_on_their_own_devices_are_uploaded_rounded(monkeypatch):
    """On a host with several devices each replica's tree is placed on
    its device AFTER the one rounding: half the bytes go up."""
    if len(jax.local_devices()) < 2:
        pytest.skip("one device")
    cfg, params, _, _ = _setup("llama")
    calls = _count_roundings(monkeypatch)
    router = create_router(params, cfg, replicas=2, family="llama",
                           num_slots=2, max_len=MAXLEN, quant="off",
                           spec_decode="off")
    trees = [r.eng._params for r in router.replicas]
    assert len(calls) == 1
    assert trees[0] is not trees[1]
    for i, tree in enumerate(trees):
        assert tree["q_w"].dtype == jnp.bfloat16
        assert tree["q_w"].devices() == {jax.local_devices()[i]}
        assert tree["attn_norm"].dtype == jnp.float32
    assert router.replicas[0].eng.weights_stats()[
        "weights_given_bytes"] == tree_bytes(params)


def test_mesh_engine_shards_the_rounded_tree():
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    from paddle_tpu.parallel.mesh import build_mesh
    cfg, params, _, _ = _setup("gpt")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in (4, 11)]
    kw = dict(num_slots=2, max_len=MAXLEN, quant="off", spec_decode="off",
              kv_layout="dense")
    plain = ServingEngine(params, cfg, **kw)
    eng = ServingEngine(params, cfg, mesh=build_mesh({"tp": 2}), **kw)
    assert eng._params["mlp_up_w"].dtype == jnp.bfloat16
    assert len(eng._params["mlp_up_w"].sharding.device_set) == 2
    _same(np.asarray(eng._params["mlp_up_w"]),
          np.asarray(plain._params["mlp_up_w"]))
    assert eng.weights_stats() == plain.weights_stats()
    for g, w in zip(eng.generate(prompts, 6), plain.generate(prompts, 6)):
        np.testing.assert_array_equal(g, w)


def test_mem_audit_closes_on_weights(monkeypatch):
    """The analytical ledger prices serving weights at the compute
    width; the compiled tick's arguments now agree with it."""
    from paddle_tpu.profiler.mem_audit import audit_serving_memory
    cfg = gpt_mod.GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
        ffn_hidden=512, max_seq_len=64, sequence_parallel=False,
        remat=False, dtype=jnp.bfloat16)
    params = gpt_mod.init_gpt_params(cfg, jax.random.PRNGKey(0))
    kw = dict(num_slots=2, max_len=MAXLEN, quant="off", spec_decode="off",
              kv_layout="dense")
    held = audit_serving_memory(ServingEngine(params, cfg, **kw))
    monkeypatch.setattr(qs, "round_serving_params", lambda p, f, c: p)
    handed = audit_serving_memory(ServingEngine(params, cfg, **kw))
    total = held["ledger"]["total"]
    assert handed["ledger"]["total"] == total
    assert abs(held["compiled"]["argument_size_bytes"] - total) < 0.02 * total
    assert handed["compiled"]["argument_size_bytes"] > 1.9 * total
