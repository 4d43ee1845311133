"""The serving tick's delta-rule step as one Pallas kernel
(`kernels/gated_delta.gated_delta_tick`) against the `jnp` step it
replaces on the chip (`gated_delta_step`), in the Pallas interpreter on
the CPU: the output and the new state to float32 round-off, an idle row's
state and every other layer of the pool bit for bit, the decay at its
two ends, a run of steps against the chunked prompt form, the rule that
decides where it engages, and the counts the tick sets on its span.

TOLERANCE. Both forms compute the same float32 arithmetic; only the order
of the 128-term sums of S^T k and S^T q may differ. With q, k L2-normed,
v and S entries near 1 the outputs and states are O(1) to O(10): 2e-5
covers the reordering, and a fault (the decay or beta left out, S^T q
after the write) moves them by 1e-2 and more.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import gated_delta as gd

TOL = 2e-5
D = 128


def tick(*args, **kw):
    return gd.gated_delta_tick(*args, interpret=True, **kw)


def _inputs(R, H, dk=D, dv=D, seed=0, layers=2):
    rng = np.random.default_rng(seed)
    f32 = np.float32

    def l2(x):
        return x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)
    return (rng.standard_normal((layers, R, H, dk, dv)).astype(f32),
            (l2(rng.standard_normal((R, H, dk))) / np.sqrt(dk)).astype(f32),
            l2(rng.standard_normal((R, H, dk))).astype(f32),
            rng.standard_normal((R, H, dv)).astype(f32),
            (-0.3 * np.exp(rng.standard_normal((R, H)))).astype(f32),
            (1 / (1 + np.exp(-rng.standard_normal((R, H))))).astype(f32))


def _live(R):
    """Every third row idle, the first always live."""
    return np.arange(R) % 3 != 2


@pytest.mark.parametrize("heads", [2, 64])
@pytest.mark.parametrize("rows", [1, 3, 8])
def test_the_kernel_is_the_jnp_step(rows, heads):
    s, q, k, v, g, beta = _inputs(rows, heads, seed=rows * heads)
    live = _live(rows)
    o, pool = jax.jit(tick)(s, jnp.int32(1), q, k, v, g, beta, live)
    want_o, want_s = gd.gated_delta_step(jnp.asarray(s[1]), q, k, v, g,
                                         beta, live=jnp.asarray(live))
    assert o.dtype == pool.dtype == jnp.float32
    assert pool.shape == s.shape
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(pool[1]), np.asarray(want_s),
                               atol=TOL, rtol=0)
    # an idle row and the other layer keep every bit
    np.testing.assert_array_equal(np.asarray(pool[1])[~live], s[1][~live])
    np.testing.assert_array_equal(np.asarray(pool[0]), s[0])
    assert not np.array_equal(np.asarray(pool[1])[live], s[1][live])


@pytest.mark.parametrize("decay", ["one", "none"])
def test_the_decay_at_its_two_ends(decay):
    """g = 0: the state is carried whole (decay 1); g far below 0: it is
    forgotten (decay 0 in float32) and the new state is k u^T alone, with
    u = beta v."""
    s, q, k, v, g, beta = _inputs(3, 4, seed=7)
    g = np.full_like(g, 0.0 if decay == "one" else -1e4)
    live = np.ones(3, bool)
    o, pool = tick(s, 0, q, k, v, g, beta, live)
    want_o, want_s = gd.gated_delta_step(jnp.asarray(s[0]), q, k, v, g, beta,
                                         live=jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(pool[0]), np.asarray(want_s),
                               atol=TOL, rtol=0)
    if decay == "none":
        u = beta[..., None] * v
        np.testing.assert_allclose(np.asarray(pool[0]),
                                   k[..., :, None] * u[..., None, :],
                                   atol=1e-6, rtol=0)
        np.testing.assert_allclose(
            np.asarray(o), np.sum(k * q, -1, keepdims=True) * u, atol=1e-6,
            rtol=0)


def test_sixty_four_steps_are_the_chunked_prompt_form():
    """One row stepped 64 times through the kernel (a pool of 2 layers,
    the state in layer 1) gives the outputs and the final state of the
    chunked form over the same 64 positions — float32 round-off carried
    through 64 steps in two orders of summation (1e-4)."""
    T, H = 64, 2
    rng = np.random.default_rng(3)
    s, *_ = _inputs(1, H, seed=3)
    steps = [_inputs(1, H, seed=100 + t)[1:] for t in range(T)]
    q, k, v, g, beta = (np.concatenate(a) for a in zip(*steps))
    s0 = rng.standard_normal((H, D, D)).astype(np.float32)
    pool = s.copy()
    pool[1, 0] = s0
    step = jax.jit(tick)
    pool, outs = jnp.asarray(pool), []
    for t in range(T):
        o, pool = step(pool, jnp.int32(1), q[t:t + 1], k[t:t + 1],
                       v[t:t + 1], g[t:t + 1], beta[t:t + 1],
                       np.ones(1, bool))
        outs.append(np.asarray(o[0]))
    want_o, want_s = gd.gated_delta_chunked(q, k, v, g, beta, s0, T)
    np.testing.assert_allclose(np.stack(outs), np.asarray(want_o), atol=1e-4,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(pool[1, 0]), np.asarray(want_s),
                               atol=1e-4, rtol=0)
    np.testing.assert_array_equal(np.asarray(pool[0]), s[0])


def test_it_writes_in_place_at_a_traced_layer_of_a_four_layer_pool():
    """`at` traced: the kernel addresses the pool itself, so each of the
    four layers can be the one written, from ONE traced program, and the
    other three keep every bit."""
    s, q, k, v, g, beta = _inputs(3, 4, seed=11, layers=4)
    live = np.ones(3, bool)
    traced = []

    def once(*a):
        traced.append(1)
        return tick(*a)
    step = jax.jit(once)
    for at in range(4):
        _, pool = step(s, jnp.int32(at), q, k, v, g, beta, live)
        _, want = gd.gated_delta_step(jnp.asarray(s[at]), q, k, v, g, beta)
        pool = np.asarray(pool)
        np.testing.assert_allclose(pool[at], np.asarray(want), atol=TOL,
                                   rtol=0)
        for other in set(range(4)) - {at}:
            np.testing.assert_array_equal(pool[other], s[other])
    assert len(traced) == 1
    # the pool is the kernel's output by aliasing, not a copy
    text = str(jax.make_jaxpr(tick)(s, jnp.int32(0), q, k, v, g, beta, live))
    assert "input_output_aliases=((2, 1),)" in text


def _as_tpu(monkeypatch):
    """The rule as it decides on the chip (a test steers what `is_tpu()`
    answers; the program has no option)."""
    import paddle_tpu.device as device
    monkeypatch.setattr(device, "is_tpu", lambda: True)


@pytest.mark.parametrize("case,engages", [
    ("cpu", False), ("tpu", True), ("tpu_mesh", False), ("tpu_dk64", False),
    ("tpu_dv64", False), ("tpu_bf16", False), ("tpu_128_heads", True),
    ("tpu_96_heads", False)])
def test_where_it_engages(case, engages, monkeypatch):
    """A TPU with no ambient mesh of several devices, 128-lane dk and dv,
    a float32 state and a head block that divides the heads; nothing else
    — and the rule reads nothing of the environment while it decides."""
    import os
    H, dk, dv, dtype = 64, D, D, jnp.float32
    if case != "cpu":
        _as_tpu(monkeypatch)
    if case.endswith("dk64"):
        dk = 64
    if case.endswith("dv64"):
        dv = 64
    if case.endswith("bf16"):
        dtype = jnp.bfloat16
    if case.endswith("heads"):
        H = int(case.split("_")[1])
    with monkeypatch.context() as mp:
        class Closed(dict):
            def __getitem__(self, key):
                raise AssertionError(f"the rule read ${key}")
            get = __contains__ = __getitem__
        mp.setattr(os, "environ", Closed())
        mp.setattr(os, "getenv", Closed().get)
        if case == "tpu_mesh":
            from paddle_tpu.parallel.mesh import build_mesh, use_mesh
            with use_mesh(build_mesh({"tp": 2})):
                got = gd.tick_kernel_engages(H, dk, dv, dtype)
        else:
            got = gd.tick_kernel_engages(H, dk, dv, dtype)
    assert got is engages


# ------------------------------------------------- in the gigachat3_5 tick
def _model():
    from paddle_tpu.models import gigachat3_5 as m
    cfg = m.GigaChat35Config(
        vocab_size=61, hidden_size=32, num_layers=5,
        full_attention_layers=(4,), num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=D, linear_value_head_dim=D, ffn_hidden=48,
        moe_ffn_hidden=24, first_k_dense_replace=1, n_routed_experts=8,
        experts_held=4, num_experts_per_tok=2, max_seq_len=64,
        rope_theta=10000.0, original_max_position_embeddings=16,
        dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    params = m.init_gigachat3_5_params(cfg, jax.random.PRNGKey(4))
    params = {k: v * 6.0 if k.endswith("_w") and k != "dn_conv_w" else v
              for k, v in params.items()}
    return m, cfg, params


def _kernel_in_interpreter(monkeypatch, m):
    _as_tpu(monkeypatch)
    monkeypatch.setattr(m, "gated_delta_tick", tick)


def _refuse_the_kernel(monkeypatch, m):
    def refuse(*a, **k):
        raise AssertionError("the kernel ran")
    monkeypatch.setattr(m, "gated_delta_tick", refuse)


def _filled_cache(m, cfg, slots, seed=5):
    cache = m.init_cache(cfg, slots, 64)
    state = jax.random.normal(jax.random.PRNGKey(seed),
                              cache["dn_state"].shape, jnp.float32) * 0.3
    return {**cache, "dn_state": state}


def _tick(m, cfg, params, cache, live):
    B = cache["dn_state"].shape[1]
    toks = jnp.asarray(np.arange(B)[:, None] % 7 + 1, jnp.int32)
    pos = jnp.asarray(np.arange(B) + 3, jnp.int32)
    return m.gigachat3_5_forward_cached(params, toks, cache, pos, cfg,
                                        live=jnp.asarray(live)[:, None])


def test_the_tick_takes_the_kernel_where_it_engages(monkeypatch):
    """The family's tick on a TPU (the kernel in the interpreter) gives the
    `jnp` tick's logits and pools to round-off, an idle slot's state bit
    for bit; off a TPU the kernel is never called and the logits and
    pools are the `jnp` form's bits."""
    m, cfg, params = _model()
    cache = _filled_cache(m, cfg, 3)
    live = np.asarray([True, False, True])
    _refuse_the_kernel(monkeypatch, m)
    want, wcache = _tick(m, cfg, params, cache, live)
    _kernel_in_interpreter(monkeypatch, m)
    got, gcache = _tick(m, cfg, params, cache, live)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(gcache["dn_state"]),
                               np.asarray(wcache["dn_state"]), atol=TOL,
                               rtol=0)
    np.testing.assert_array_equal(np.asarray(gcache["dn_state"][:, 1]),
                                  np.asarray(cache["dn_state"][:, 1]))
    # the first layer's convolution rows come before any state; the later
    # layers' inputs carry the first one's round-off
    np.testing.assert_array_equal(np.asarray(gcache["dn_conv"][0]),
                                  np.asarray(wcache["dn_conv"][0]))
    np.testing.assert_array_equal(np.asarray(gcache["stats"]),
                                  np.asarray(wcache["stats"]))
    for name in ("ckv", "kpe", "dn_conv"):
        np.testing.assert_allclose(np.asarray(gcache[name]),
                                   np.asarray(wcache[name]), atol=TOL, rtol=0)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_tick_counts_the_rows_the_kernel_stepped(platform, monkeypatch):
    """`span_counts` of a tick: the live rows x the linear layers whose
    state it stepped, and those the kernel stepped — all of them where
    the kernel engages, none where it does not; a prompt's counts have
    neither."""
    m, cfg, params = _model()
    if platform == "tpu":
        _kernel_in_interpreter(monkeypatch, m)
    cache = _filled_cache(m, cfg, 3)
    _, out = _tick(m, cfg, params, cache, np.asarray([True, False, True]))
    counts = m.span_counts(cfg, np.asarray(out["stats"]))
    assert counts["dn_state_rows"] == 2 * 4
    assert counts["dn_state_rows_kernel"] == (8 if platform == "tpu" else 0)
    _, mini = m.prefill_into_slot(params, cache, jnp.ones((1, 8), jnp.int32),
                                  jnp.int32(5), jnp.int32(0), cfg)
    prompt = m.span_counts(cfg, np.asarray(mini["stats"]))
    assert "dn_state_rows" not in prompt and "dn_state_rows_kernel" \
        not in prompt


def test_the_engine_serves_the_jnp_ticks_tokens_with_the_kernel(monkeypatch):
    """An engine whose tick takes the kernel (in the interpreter) serves
    the `jnp` engine's greedy tokens, and every `serving.decode_tick`
    span counts the active rows x 4 linear layers, all of them the
    kernel's."""
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans
    m, cfg, params = _model()
    prompts = [np.arange(n, dtype=np.int32) % 61 for n in (5, 11)]

    def serve():
        router = create_router(params, cfg, replicas=1, num_slots=3,
                               family="gigachat3_5", max_len=64)
        reqs = [router.submit(p, 4) for p in prompts]
        clear_profiler_spans()
        while router.has_work():
            router.step()
        ticks = [s.counts for s in get_profiler_spans()
                 if s.name == "serving.decode_tick"]
        router.close()
        return [list(r.tokens) for r in reqs], ticks

    want, plain = serve()
    assert plain and all(c["dn_state_rows_kernel"] == 0 for c in plain)
    _kernel_in_interpreter(monkeypatch, m)
    got, ticks = serve()
    assert got == want
    assert ticks
    for c in ticks:
        assert c["dn_state_rows"] == c["dn_state_rows_kernel"] \
            == c["active"] * 4 > 0


def test_the_tick_heads_a_grid_step_holds():
    assert gd._tick_heads(2) == 2 and gd._tick_heads(64) == 64
    assert gd._tick_heads(96) == 64 and gd._tick_heads(128) == 64
