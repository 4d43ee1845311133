"""The names the device trace is read by (docs/observability.md "Names on
the device work"): `jax.named_scope` on the phases of the train step, of
the cached forward and of the jitted decode tick, and `name=` on every
`pl.pallas_call`. They are metadata — a refactor that drops one breaks no
numerics test — so this file is what fails on the CPU when one goes.

Reference analog: paddle/fluid/platform/profiler/ (RecordEvent names on
operators; the kernel's name in the CUPTI trace)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                   init_opt_state, train_step)


def _cfg(**kw):
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, ffn_hidden=64, max_seq_len=32,
                     sequence_parallel=False, dtype=jnp.float32, **kw)


def _has_scope(text: str, scope: str) -> bool:
    """The scope as one component of an op's name in the lowered text:
    `.../attention/dot_general`, `jvp(embed)`, `transpose(jvp(ce_head))`."""
    return re.search(rf'[/("]{scope}[/)"]', text) is not None


def test_the_train_step_names_its_phases():
    cfg = _cfg(remat=True)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    batch = jnp.zeros((2, 17), jnp.int32)
    text = jax.jit(functools.partial(train_step, cfg=cfg)).lower(
        params, init_opt_state(params), batch).as_text(debug_info=True)
    for scope in ("embed", "attention", "mlp", "ce_head", "optimizer"):
        assert _has_scope(text, scope), scope
    # the backward pass and the recomputation keep the phase's name
    assert "transpose(jvp(ce_head))" in text
    assert "rematted_computation/attention" in text


def test_the_decode_tick_names_its_phases():
    from paddle_tpu.inference.serving import ServingEngine
    cfg = _cfg(remat=False)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, family="gpt", num_slots=2, max_len=32,
                        kv_layout="dense", spec_decode="off", multi_tick=1)
    eng.generate([np.arange(5, dtype=np.int32)], 3)
    text = eng._decode.lower(
        eng._params, eng._cache, eng._dstate, eng._base_key,
        eng._poison_ones, sampling=False).as_text(debug_info=True)
    for scope in ("embed", "attention", "kv_update", "decode_attention",
                  "mlp", "lm_head", "sample"):
        assert _has_scope(text, scope), scope
    # the phases of the cached forward nest: the cache write and the
    # attention over it sit inside the block's attention phase
    assert "attention/kv_update" in text
    assert "attention/decode_attention" in text


def test_the_hybrid_tick_and_prompt_name_their_phases():
    """gigachat3_5: the linear-attention layer's phases (the chunked delta
    rule in a prompt's program, the one step in the tick's), the latent
    attention's path and the expert layer's, each in the program that runs
    them — what the device view's by-scope table groups."""
    from paddle_tpu.models import gigachat3_5 as m
    cfg = m.GigaChat35Config(
        vocab_size=64, hidden_size=32, num_layers=2,
        full_attention_layers=(1,), num_heads=2, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, ffn_hidden=48,
        moe_ffn_hidden=16, first_k_dense_replace=1, n_routed_experts=8,
        experts_held=4, num_experts_per_tok=2, max_seq_len=32,
        dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    params = m.init_gigachat3_5_params(cfg, jax.random.PRNGKey(0))
    tick = jax.jit(lambda p, t, c, pos: m.gigachat3_5_forward_cached(
        p, t, c, pos, cfg)).lower(
        params, jnp.zeros((2, 1), jnp.int32), m.init_cache(cfg, 2, 16),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    prompt = jax.jit(lambda p, t, c: m.gigachat3_5_forward_cached(
        p, t, c, 0, cfg)).lower(
        params, jnp.zeros((1, 16), jnp.int32),
        m.init_cache(cfg, 1, 16)).as_text(debug_info=True)
    for scope in ("gated_delta/proj", "gated_delta/conv", "gated_delta/out",
                  "moe_router", "moe_experts", "shared_expert", "dense_mlp",
                  "lm_head"):
        assert scope in tick and scope in prompt, scope
    for only, other, text in (
            (("gated_delta/step", "mla_absorbed"),
             ("gated_delta/chunked", "mla_prefill"), tick),
            (("gated_delta/chunked", "mla_prefill"),
             ("gated_delta/step", "mla_absorbed"), prompt)):
        assert all(_has_scope(text, s.split("/")[-1]) for s in only)
        assert all(s in text for s in only)
        assert not any(s in text for s in other)


def _eqns(jaxpr, outer=""):
    """Every equation with its whole scope path, into the bodies of scans,
    loops and nested jits."""
    for e in jaxpr.eqns:
        scope = "/".join(p for p in (outer, str(e.source_info.name_stack))
                         if p)
        yield e, scope
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _eqns(inner, scope)


def test_the_hybrid_ticks_state_kernel_sits_in_its_step_scope(monkeypatch):
    """gigachat3_5 where the delta-rule tick kernel engages (a TPU, 128-wide
    heads; here in the interpreter): one `pallas_call` named
    `gated_delta_tick` a linear layer of the tick, each under the tick's
    `gated_delta/step` scope, so the device view books it there and
    `breakdown.device_ops` lists it by name; none in a prompt."""
    import paddle_tpu.device as device
    from paddle_tpu.kernels import gated_delta as gd
    from paddle_tpu.models import gigachat3_5 as m
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(m, "gated_delta_tick", functools.partial(
        gd.gated_delta_tick, interpret=True))
    cfg = m.GigaChat35Config(
        vocab_size=64, hidden_size=32, num_layers=3,
        full_attention_layers=(2,), num_heads=2, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=128, linear_value_head_dim=128, ffn_hidden=48,
        moe_ffn_hidden=16, first_k_dense_replace=1, n_routed_experts=8,
        experts_held=4, num_experts_per_tok=2, max_seq_len=32,
        dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    params = m.init_gigachat3_5_params(cfg, jax.random.PRNGKey(0))
    tick = jax.make_jaxpr(lambda c: m.gigachat3_5_forward_cached(
        params, jnp.zeros((2, 1), jnp.int32), c, jnp.zeros((2,), jnp.int32),
        cfg))(m.init_cache(cfg, 2, 16))
    calls = [(e, scope) for e, scope in _eqns(tick.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == cfg.layers_of(m.LINEAR) == 2
    for e, scope in calls:
        assert e.params["name"] == "gated_delta_tick"
        assert scope.startswith("gated_delta/step/"), scope
    prompt = jax.make_jaxpr(lambda c: m.gigachat3_5_forward_cached(
        params, jnp.zeros((1, 16), jnp.int32), c, 0, cfg))(
        m.init_cache(cfg, 1, 16))
    assert not [e for e, _ in _eqns(prompt.jaxpr)
                if e.primitive.name == "pallas_call"]


def _flash_fwd():
    from paddle_tpu.kernels.pallas_attention import mha_fwd
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    return jax.make_jaxpr(
        lambda q: mha_fwd(q, q, q, causal=True, interpret=True))(q)


def _flash_bwd():
    from paddle_tpu.kernels.pallas_attention import mha_bwd
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    lse = jnp.zeros((1, 2, 128), jnp.float32)
    return jax.make_jaxpr(
        lambda q, lse: mha_bwd(q, q, q, q, lse, q, causal=True,
                               interpret=True))(q, lse)


def _flash_tiled():
    from paddle_tpu.kernels.pallas_attention import tiled_mha
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    return jax.make_jaxpr(jax.grad(
        lambda q: tiled_mha(q, q, q, True, True).sum()))(q)


def _ce(which):
    from paddle_tpu.kernels import pallas_ce
    x = jnp.zeros((128, 512), jnp.float32)
    t = jnp.zeros((128,), jnp.int32)
    row = jnp.zeros((128,), jnp.float32)
    if which == "ce_fused":
        return jax.make_jaxpr(functools.partial(
            pallas_ce._ce_fused, interpret=True))(x, t)
    if which == "ce_fwd":
        return jax.make_jaxpr(functools.partial(
            pallas_ce._ce_fwd, interpret=True))(x, t)
    return jax.make_jaxpr(functools.partial(
        pallas_ce._ce_bwd, interpret=True))(x, t, row, row)


def _adamw():
    from paddle_tpu.kernels.pallas_update import _leaf_update
    p = jnp.zeros((8, 1024), jnp.float32)
    return jax.make_jaxpr(functools.partial(_leaf_update, interpret=True))(
        p, p, p, p, jnp.zeros((7,), jnp.float32))


def _quant():
    from paddle_tpu.kernels.quant_matmul import _pallas_quant_matmul
    return jax.make_jaxpr(functools.partial(
        _pallas_quant_matmul, interpret=True))(
        jnp.zeros((128, 128), jnp.bfloat16), jnp.zeros((128, 128), jnp.int8),
        jnp.zeros((128,), jnp.float32))


def _decode_live_blocks():
    from paddle_tpu.kernels import decode_attention as da
    pool = jnp.zeros((2, 2, da.DECODE_BLOCK, 8, 128), jnp.bfloat16)
    q = jnp.zeros((2, 1, 8, 128), jnp.bfloat16)
    return jax.make_jaxpr(lambda q, pool: da.length_aware_attention(
        q, pool, pool, jnp.int32(1),
        da.work_list(jnp.int32(5), None, 2, da.DECODE_BLOCK),
        interpret=True))(q, pool)


def _mla_live_blocks():
    from paddle_tpu.kernels import decode_attention as da
    from paddle_tpu.kernels import latent_attention as la
    S = la.LATENT_BLOCK
    ckv = jnp.zeros((2, 2, S, 128), jnp.bfloat16)
    kpe = jnp.zeros((2, 2, S, 64), jnp.bfloat16)
    return jax.make_jaxpr(lambda ckv, kpe: la.absorbed_attention_live_blocks(
        jnp.zeros((2, 8, 128), jnp.bfloat16),
        jnp.zeros((2, 8, 64), jnp.bfloat16), ckv, kpe, jnp.int32(1),
        da.work_list(jnp.int32(5), None, 2, S, S), 192,
        interpret=True))(ckv, kpe)


def _prompt_flash():
    from paddle_tpu.kernels.pallas_attention import prompt_flash
    q = jnp.zeros((1, 256, 16, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    return jax.make_jaxpr(functools.partial(
        prompt_flash, window=100, interpret=True))(q, kv, kv)


def _gated_delta_tick():
    from paddle_tpu.kernels import gated_delta as gd
    pool = jnp.zeros((2, 2, 2, 128, 128), jnp.float32)
    rows = jnp.zeros((2, 2, 128), jnp.float32)
    heads = jnp.zeros((2, 2), jnp.float32)
    return jax.make_jaxpr(lambda pool: gd.gated_delta_tick(
        pool, 1, rows, rows, rows, heads, heads, jnp.ones((2,), bool),
        interpret=True))(pool)


KERNELS = {
    "flash_fwd": _flash_fwd,
    "flash_bwd_dq": _flash_bwd,
    "flash_bwd_dkv": _flash_bwd,
    "flash_tiled_fwd": _flash_tiled,
    "flash_tiled_bwd": _flash_tiled,
    "ce_fused": functools.partial(_ce, "ce_fused"),
    "ce_fwd": functools.partial(_ce, "ce_fwd"),
    "ce_bwd": functools.partial(_ce, "ce_bwd"),
    "adamw_update": _adamw,
    "quant_matmul": _quant,
    "decode_attention_live_blocks": _decode_live_blocks,
    "mla_absorbed_live_blocks": _mla_live_blocks,
    "prompt_flash_fwd": _prompt_flash,
    "gated_delta_tick": _gated_delta_tick,
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_every_pallas_call_carries_its_name(name):
    """Interpret mode inlines a kernel, so its name is not in CPU HLO: it
    is read off the jaxpr of the kernel's wrapper."""
    text = str(KERNELS[name]())
    assert "pallas_call[" in text
    assert f"name={name}\n" in text or f"name={name} " in text \
        or f"name={name}]" in text, text[:2000]


def test_no_pallas_call_is_left_unnamed():
    import os
    import re
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "kernels")
    names = []
    for fname in sorted(os.listdir(root)):
        if not fname.endswith(".py"):
            continue
        src = open(os.path.join(root, fname)).read()
        calls = src.count("pl.pallas_call(")
        found = re.findall(r'^\s+name="(\w+)",$', src, flags=re.M)
        assert len(found) == calls, fname
        names += found
    assert sorted(names) == sorted(KERNELS)
