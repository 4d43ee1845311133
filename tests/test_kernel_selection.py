"""Which implementation runs (docs/kernel_selection.md): the caller's
explicit argument where one exists (`kv_layout=`, `spec_decode=`,
`quant=`, `multi_tick=`, `host_kv_bytes=`), else a constant at the
consult site that depends on the platform only where the two platforms
differ. No environment variable, no file, no precedence.

Pins: the per-platform defaults of every consult site; that the eight
environment variables the old ladder read change nothing; that every
family's engine builds dense / spec off / quant off / K=1 / no host tier
with nothing passed; that the five arguments are the switches; that the
attention choice reads no file; the package's remaining environment
names, against an allow-list; and the roofline gate the measurement
tools keep (tools/bench_util.py)."""
import builtins
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from paddle_tpu.inference import multi_tick as mt
from paddle_tpu.inference import spec_decode as sd
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import gated_delta as gd
from paddle_tpu.kernels import pallas_ce, pallas_update
from paddle_tpu.kernels import quant_matmul as qm
from paddle_tpu.models import cohere2_moe, gpt, llama, losses
from paddle_tpu.nn.functional import attention as A


def _gpt():
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, ffn_hidden=64, max_seq_len=128,
                        sequence_parallel=False, remat=False,
                        dtype=jnp.float32)
    return cfg, gpt.init_gpt_params(cfg, jax.random.PRNGKey(0))


def _llama():
    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=128,
                            dtype=jnp.float32, remat=False)
    return cfg, llama.init_llama_params(cfg, jax.random.PRNGKey(0))


def _cohere2_moe():
    cfg = cohere2_moe.Cohere2MoeConfig(
        vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_hidden=48, max_seq_len=64,
        sliding_window=8, num_experts=16, experts_held=4, first_expert=4,
        experts_per_token=4, num_shared_experts=2, dtype=jnp.float32,
        param_dtype=jnp.float32, prefill_chunk=8)
    return cfg, cohere2_moe.init_cohere2_moe_params(
        cfg, jax.random.PRNGKey(0))


FAMILIES = {"gpt": _gpt, "llama": _llama, "cohere2_moe": _cohere2_moe}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(family):
        if family not in built:
            built[family] = FAMILIES[family]()
        return built[family]
    return get


def _engine(models, family="gpt", **kw):
    cfg, params = models(family)
    return ServingEngine(params, cfg, family=family, num_slots=2,
                         max_len=64, **kw)


def _built(eng):
    """What an engine's selection came to."""
    return {"paged": eng.paged, "spec": eng.spec, "quant": eng.quant,
            "mt_k": eng.mt_k, "host_tier": eng._host_tier is not None}


ALL_OFF = {"paged": False, "spec": False, "quant": False, "mt_k": 1,
           "host_tier": False}


def _ce_flavour():
    return (pallas_ce.ce_fused_train if losses.CE_FUSED_GRAD
            else pallas_ce.ce_with_logits).__name__


# site -> (what it resolves to with nothing set, {platform: expected});
# docs/kernel_selection.md is this table in prose
SITES = {
    "attention": (lambda m: fa._attn_impl(),
                  {"tpu": "tiled", "cpu": "pallas"}),
    # the train cell's attention, a ragged length, a 32-wide head
    "attention_tiled_kernels": (
        lambda m: tuple(fa._tiled_engages(*(jax.ShapeDtypeStruct(
            shp, jnp.bfloat16),) * 3) for shp in (
                (8, 1024, 16, 64), (8, 197, 16, 64), (8, 1024, 16, 32))),
        {"tpu": (True, False, False), "cpu": (False, False, False)}),
    "ce": (lambda m: (losses._pallas_ce_enabled(), _ce_flavour()),
           {"tpu": (True, "ce_with_logits"),
            "cpu": (False, "ce_with_logits")}),
    "fused_update": (lambda m: pallas_update.fused_update_enabled(),
                     {"tpu": False, "cpu": False}),
    "varlen_attention": (
        lambda m: (A._varlen_impl(64),
                   A._varlen_impl(A._VARLEN_DENSE_MAX + 1)),
        {"tpu": ("dense", "blockwise"), "cpu": ("dense", "blockwise")}),
    "decode_attention": (
        lambda m: (da.DECODE_ATTN_IMPL, _engine(m).paged),
        {"tpu": ("dense", False), "cpu": ("dense", False)}),
    # the benchmark's serving pool, one query token a row
    "decode_attention_kernel": (
        lambda m: (da.length_aware(1, jax.ShapeDtypeStruct(
            (24, 16, 1024, 16, 128), jnp.bfloat16)),
                   da.length_aware(5, jax.ShapeDtypeStruct(
                       (24, 16, 1024, 16, 128), jnp.bfloat16))),
        {"tpu": (True, False), "cpu": (False, False)}),
    # a prompt's attention at Command A+'s last chunk of a 16,384 bucket,
    # Jamba's 4,096 bucket, and JoyAI's 192-wide q/k
    "prompt_attention_kernel": (
        lambda m: (da.prompt_kernel_engages(2048, 16384, 128, 8, 128, 128,
                                            jnp.bfloat16, 14336),
                   da.prompt_kernel_engages(4096, 4096, 20, 1, 128, 128,
                                            jnp.bfloat16),
                   da.prompt_kernel_engages(8192, 8192, 32, 32, 192, 128,
                                            jnp.bfloat16)),
        {"tpu": (True, True, False), "cpu": (False, False, False)}),
    # the delta-rule step of GigaChat3.5's tick: 64 value heads of
    # 128 x 128 float32 state; the same in bfloat16; 64-wide heads
    "delta_rule_tick_kernel": (
        lambda m: (gd.tick_kernel_engages(64, 128, 128, jnp.float32),
                   gd.tick_kernel_engages(64, 128, 128, jnp.bfloat16),
                   gd.tick_kernel_engages(64, 64, 64, jnp.float32)),
        {"tpu": (True, False, False), "cpu": (False, False, False)}),
    "quant_matmul": (lambda m: (qm.resolve_quant("auto"),
                                qm.matmul_impl()),
                     {"tpu": (False, "xla"), "cpu": (False, "xla")}),
    "spec_decode": (lambda m: sd.resolve_spec("auto"),
                    {"tpu": False, "cpu": False}),
    "multi_tick": (lambda m: mt.resolve_multi_tick(0),
                   {"tpu": 1, "cpu": 1}),
    "host_kv": (lambda m: (_engine(m).host_kv_bytes,
                           _engine(m, kv_layout="paged",
                                   prefix_sharing=True)._host_tier),
                {"tpu": (0, None), "cpu": (0, None)}),
}


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("site", sorted(SITES))
def test_default_by_platform(site, platform, models, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    resolve, expected = SITES[site]
    assert resolve(models) == expected[platform]


class TestAttentionSelection:
    def test_cpu_default_is_pallas(self, monkeypatch):
        """The CPU suite keeps exercising the homegrown kernel's path."""
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "cpu")
        assert fa._attn_impl() == "pallas"
        assert fa._pallas_attn_enabled()

    def test_tpu_default_is_tiled(self, monkeypatch):
        """What the train cell measures: the tiled kernels where the call
        engages them (`_tiled_engages`), else blockwise XLA attention —
        the 128x128 kernels' forward and backward gates both closed."""
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._attn_impl() == "tiled"
        assert not fa._pallas_attn_enabled()
        assert not fa._pallas_bwd_enabled()


class TestVarlenSelection:
    def test_heuristic_default(self):
        assert A._varlen_impl(A._VARLEN_DENSE_MAX + 1) == "blockwise"
        assert A._varlen_impl(64) == "dense"

    def test_dense_stops_at_the_memory_guard(self):
        """The guard is on the probs buffer's ELEMENT count, heads
        included: the same packing flips to blockwise when the head
        count carries it over."""
        total = 1024
        heads_at_guard = A._VARLEN_DENSE_MAX // (total * total)
        assert A._varlen_impl(heads_at_guard * total * total) == "dense"
        assert A._varlen_impl((heads_at_guard + 1) * total * total) \
            == "blockwise"


def test_mixed_decode_attention_tracks_dense():
    """The path DECODE_ATTN_IMPL = 'mixed' would switch on: cache-dtype
    QK^T and P.V with an f32 softmax, against the f32 default."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((2, 16, 2, 8)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((2, 16, 2, 8)), jnp.bfloat16)
    pos = jnp.asarray([5, 11], jnp.int32)
    dense = da.cached_attention(q, kc, vc, pos)
    np.testing.assert_array_equal(
        np.asarray(dense),
        np.asarray(da.cached_attention(q, kc, vc, pos, impl="dense")))
    mixed = da.cached_attention(q, kc, vc, pos, impl="mixed")
    assert mixed.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(mixed), np.asarray(dense),
                               atol=5e-2)
    with pytest.raises(ValueError):
        da.cached_attention(q, kc, vc, pos, impl="paged")


# the names the ladder read, each at a value that used to switch its
# feature ON (or, for the kill switch, the Pallas update off)
DELETED_ENV = {
    "PADDLE_TPU_ATTN_IMPL": "splash",
    "PADDLE_TPU_DECODE_ATTN_IMPL": "paged",
    "PADDLE_TPU_VARLEN_IMPL": "dense",
    "PADDLE_TPU_QUANT": "pallas",
    "PADDLE_TPU_SPEC_DECODE": "spec",
    "PADDLE_TPU_MULTI_TICK": "4",
    "PADDLE_TPU_HOST_KV": str(1 << 20),
    "PADDLE_TPU_DISABLE_PALLAS_UPDATE": "1",
}


def _resolved(models):
    return {site: resolve(models) for site, (resolve, _) in SITES.items()}


@pytest.mark.parametrize("name", sorted(DELETED_ENV))
def test_selection_ignores_environment(name, models, monkeypatch):
    want = _resolved(models)
    want_built = _built(_engine(models, kv_layout="auto",
                                prefix_sharing=True))
    assert want_built == ALL_OFF
    monkeypatch.setenv(name, DELETED_ENV[name])
    assert _resolved(models) == want
    assert _built(_engine(models, kv_layout="auto",
                          prefix_sharing=True)) == want_built
    if name == "PADDLE_TPU_DISABLE_PALLAS_UPDATE":
        # it was a veto: with the constant on, the TPU runs the kernel
        monkeypatch.setattr(pallas_update, "FUSED_UPDATE", True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert pallas_update.fused_update_enabled()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_defaults(family, models):
    assert _built(_engine(models, family)) == ALL_OFF


_TIER = {"kv_layout": "paged", "prefix_sharing": True}


@pytest.mark.parametrize("option,on,on_built,off,off_built", [
    ("kv_layout", {"kv_layout": "paged"}, {"paged": True},
     {"kv_layout": "dense"}, {}),
    ("spec_decode", {"spec_decode": "spec"}, {"spec": True},
     {"spec_decode": "off"}, {}),
    ("quant", {"quant": "int8"}, {"quant": True}, {"quant": "off"}, {}),
    ("multi_tick", {"multi_tick": 4}, {"mt_k": 4}, {"multi_tick": 1}, {}),
    ("host_kv_bytes", {"host_kv_bytes": 1 << 20, **_TIER},
     {"paged": True, "host_tier": True},
     {"host_kv_bytes": 0, **_TIER}, {"paged": True}),
])
def test_explicit_argument_is_the_only_switch(option, on, on_built, off,
                                              off_built, models):
    assert _built(_engine(models, **on)) == {**ALL_OFF, **on_built}
    assert _built(_engine(models, **off)) == {**ALL_OFF, **off_built}


def test_attention_choice_opens_no_file(monkeypatch):
    opened = []
    real = builtins.open

    def spy(path, *a, **k):
        opened.append(path)
        return real(path, *a, **k)
    monkeypatch.setattr(builtins, "open", spy)
    for platform in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        fa._attn_impl()
        fa._pallas_attn_enabled()
    assert opened == []
    assert "paddle_tpu.kernels.registry" not in sys.modules


# every PADDLE_TPU_* name the package's sources mention. Selection is
# not among them: the global / targeted Pallas escapes, the attention
# block and autotune tuning (ROADMAP D2 owns those), and deployment
# settings (flight recorder, telemetry, fault injection, elastic world,
# worker start-up).
ENV_ALLOWED = {
    "PADDLE_TPU_DISABLE_PALLAS", "PADDLE_TPU_DISABLE_PALLAS_ATTN",
    "PADDLE_TPU_DISABLE_PALLAS_BWD", "PADDLE_TPU_DISABLE_PALLAS_CE",
    "PADDLE_TPU_FLASH_BLOCK_Q", "PADDLE_TPU_FLASH_BLOCK_K",
    "PADDLE_TPU_FLASH_BLOCK_BWD_Q", "PADDLE_TPU_FLASH_BLOCK_BWD_K",
    "PADDLE_TPU_AUTOTUNE", "PADDLE_TPU_AUTOTUNE_CACHE",
    "PADDLE_TPU_DISABLE_DY2STATIC_AST",
    "PADDLE_TPU_FLIGHT_DIR", "PADDLE_TPU_FLIGHT_N",
    "PADDLE_TPU_FLIGHT_AUTODUMP", "PADDLE_TPU_SERVING_TELEMETRY",
    "PADDLE_TPU_FAULTS", "PADDLE_TPU_FAULTS_ONCE_DIR",
    "PADDLE_TPU_ELASTIC_WORLD", "PADDLE_TPU_ELASTIC_WORLD_FILE",
    "PADDLE_TPU_WORKER_START",
}


def test_package_reads_only_listed_env_names():
    found = set()
    for dirpath, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    found |= set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+",
                                            fh.read()))
    # a name ending in '_' is prose for a family ("PADDLE_TPU_FLASH_BLOCK_*")
    names = {n for n in found if not n.endswith("_")}
    prefixes = found - names
    assert names == ENV_ALLOWED
    assert not set(DELETED_ENV) & names
    for p in prefixes:
        assert any(n.startswith(p) for n in ENV_ALLOWED), p


class TestRooflineGate:
    """tools/bench_util.gate_ms: what the measurement tools that remain
    (autotune_kernels) refuse to record."""

    def test_too_fast_is_refused(self):
        from bench_util import gate_ms
        assert gate_ms(400.0, flops=1.9e13) is None
        assert "implausibly fast" in gate_ms(0.01, flops=1.9e13)

    def test_sub_floor_rate_is_refused(self):
        from bench_util import gate_ms
        assert "implausibly slow" in gate_ms(9e6, flops=1.9e13)
        assert "implausibly slow" in gate_ms(9e4, bytes_moved=1e8)

    def test_no_volume_cannot_pass(self):
        from bench_util import gate_ms, plausible_ms
        assert plausible_ms() == (0.0, 1e-3)
        assert gate_ms(400.0) is not None
