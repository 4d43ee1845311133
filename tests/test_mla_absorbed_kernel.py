"""The tick's absorbed latent attention over live blocks
(kernels/latent_attention.py `absorbed_attention_live_blocks`: per row,
only the blocks of the stacked latent pools that hold a live position,
each `ckv` block read once for scores and output) against the two masked
einsums of `models/joyai_llm_flash._absorbed`, in the Pallas interpreter
on the CPU; what engages it (`absorbed_engages`) and that every call it
does not engage on returns the einsums' bits; and the engine's counts
where it runs.

Tolerance: the kernel's arithmetic is the einsums' (operands in the
pools' dtype on both dots, float32 scores, statistics and accumulation),
in blocks with a running softmax — the order of float32 summation
differs, and the probabilities are rounded to the pools' dtype BEFORE
they are normalised (the einsums round after). The output is a convex
combination of latent rows, so the difference is held to ULPS ulps of
the pools' dtype at the scale of the largest |ckv| a row can see
(measured: under 2 at bfloat16, under 8 at float32).

Reference analog: the masked single-step branch of
paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29, which
walks the cache up to the step's own length."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai_llm_flash as ref
from paddle_tpu.kernels import latent_attention as la
from paddle_tpu.kernels.decode_attention import work_list
from paddle_tpu.models import joyai_llm_flash as m

BLOCK = la.LATENT_BLOCK
L, S, C, R, H = 3, 2 * BLOCK, 128, 64, 8
LAYER = 1
ULPS = 16
QK = 192
# only its scale is read: sqrt(qk_nope_head_dim + qk_rope_head_dim = QK)
CFG = m.JoyaiLlmFlashConfig()
assert CFG.qk_head_dim == QK
# an idle row (length 0), then 1, block - 1, block, block + 1, full
RAGGED = dict(pos=[7, 0, BLOCK - 2, BLOCK - 1, BLOCK, S - 1],
              live=[False, True, True, True, True, True])


def _pools(dtype, batch, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)

    def draw(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return (draw(ks[0], (batch, H, C)), draw(ks[1], (batch, H, R)),
            draw(ks[2], (L, batch, S, C)), draw(ks[3], (L, batch, S, R)))


@functools.partial(jax.jit, static_argnames=("has_live",))
def _kernel(q_lat, q_pe, ckv, kpe, pos, layer, live, has_live=True):
    plan = work_list(pos, live if has_live else None, ckv.shape[1],
                     ckv.shape[2], BLOCK)
    return la.absorbed_attention_live_blocks(q_lat, q_pe, ckv, kpe, layer,
                                             plan, QK, interpret=True)


@jax.jit
def _einsums(q_lat, q_pe, ckv, kpe, pos, layer):
    """The family's two masked einsums (`_absorbed`'s attention between
    its two absorptions where no kernel runs), at a traced layer of the
    stacked pools."""
    return m._masked_einsums(q_lat, q_pe, ckv[layer], kpe[layer],
                             jnp.broadcast_to(pos, (ckv.shape[1],)), CFG)


def _tolerance(ckv, layer):
    return ULPS * float(jnp.finfo(ckv.dtype).eps) * float(
        jnp.abs(ckv[layer].astype(jnp.float32)).max())


@pytest.mark.parametrize("pos_kind", ["per_row", "scalar"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_matches_the_einsums_at_a_traced_layer(dtype, pos_kind):
    live = np.asarray(RAGGED["live"])
    q_lat, q_pe, ckv, kpe = _pools(dtype, len(live))
    layer = jnp.int32(LAYER)
    if pos_kind == "per_row":
        pos = jnp.asarray(RAGGED["pos"], jnp.int32)
        got = _kernel(q_lat, q_pe, ckv, kpe, pos, layer, jnp.asarray(live))
    else:
        # the whole batch at one position, no mask
        pos, live = jnp.int32(BLOCK + 5), np.ones_like(live)
        got = _kernel(q_lat, q_pe, ckv, kpe, pos, layer, None,
                      has_live=False)
    want = _einsums(q_lat, q_pe, ckv, kpe, pos, layer)
    assert got.shape == want.shape and got.dtype == jnp.float32
    err = np.abs(np.asarray(got) - np.asarray(want)).max(axis=(1, 2))
    assert (err[live] <= _tolerance(ckv, LAYER)).all(), err
    # a row that is no request reads nothing and comes back as zeros
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("length", [BLOCK - 1, BLOCK, BLOCK + 1],
                         ids=["one_short", "on_the_edge", "one_past"])
def test_a_length_at_a_block_edge_sees_its_last_position(length):
    """The row's last position carries a latent ten times the others':
    left out, or the one after it let in, the output moves by far more
    than the tolerance."""
    q_lat, q_pe, ckv, kpe = _pools(jnp.float32, 2, seed=1)
    pos = jnp.asarray([length - 1, length - 1], jnp.int32)
    ckv = ckv.at[LAYER, :, length - 1].mul(10.0)
    ckv = ckv.at[LAYER, :, length].mul(10.0)         # not seen
    got = _kernel(q_lat, q_pe, ckv, kpe, pos, jnp.int32(LAYER), None,
                  has_live=False)
    want = _einsums(q_lat, q_pe, ckv, kpe, pos, jnp.int32(LAYER))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= _tolerance(ckv, LAYER)


def test_a_row_of_length_one_returns_its_own_latent():
    q_lat, q_pe, ckv, kpe = _pools(jnp.bfloat16, 2)
    got = _kernel(q_lat, q_pe, ckv, kpe, jnp.zeros((2,), jnp.int32),
                  jnp.int32(LAYER), jnp.asarray([True, False]))
    own = np.asarray(ckv[LAYER, 0, 0].astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.broadcast_to(own, (H, C)))
    assert not np.asarray(got[1]).any()


@pytest.mark.parametrize("planted", [np.nan, np.inf, 1e30],
                         ids=["nan", "inf", "stale"])
def test_what_lies_past_a_rows_length_changes_nothing(planted):
    """The einsums give a dead position an exact 0 weight, which a nan or
    inf latent still poisons (0 * nan); the kernel never lets it reach a
    sum: planted `ckv` and `kpe` past each row's length — and a whole
    idle row, and every other layer — leave its bits alone."""
    pos = jnp.asarray(RAGGED["pos"], jnp.int32)
    live = jnp.asarray(RAGGED["live"])
    q_lat, q_pe, ckv, kpe = _pools(jnp.bfloat16, len(RAGGED["pos"]))
    clean = _kernel(q_lat, q_pe, ckv, kpe, pos, jnp.int32(LAYER), live)
    dead = (jnp.arange(S)[None, :] > pos[:, None]) | ~live[:, None]
    dead = dead[None, :, :, None] | (
        jnp.arange(L) != LAYER)[:, None, None, None]
    bad = jnp.asarray(planted, jnp.bfloat16)
    got = _kernel(q_lat, q_pe, jnp.where(dead, bad, ckv),
                  jnp.where(dead, bad, kpe), pos, jnp.int32(LAYER), live)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


# ------------------------------------------------- the seam in the model
def make_cfg(**kw):
    """The family at test widths whose latent fills whole lanes (128)."""
    base = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=H,
                q_lora_rank=24, kv_lora_rank=C, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, ffn_hidden=48,
                moe_ffn_hidden=24, first_k_dense_replace=1,
                n_routed_experts=8, experts_held=4, first_expert=2,
                num_experts_per_tok=2, max_seq_len=S, rope_theta=10000.0,
                dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    base.update(kw)
    return m.JoyaiLlmFlashConfig(**base)


def make_params(cfg, seed=0):
    params = m.init_joyai_llm_flash_params(cfg, jax.random.PRNGKey(seed),
                                           mtp=False)
    return {k: v * 6.0 if k.endswith("_w") else v
            for k, v in params.items()}


def _as_tpu(monkeypatch):
    """The seam as it decides on the chip, its kernel in the interpreter
    (a test steers what `is_tpu()` answers; the program has no option)."""
    import paddle_tpu.device as device
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(
        m, "absorbed_attention_live_blocks",
        functools.partial(la.absorbed_attention_live_blocks,
                          interpret=True))


def _refuse_the_kernel(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the kernel ran")
    monkeypatch.setattr(m, "absorbed_attention_live_blocks", refuse)


def _tick(cfg, params, cache, pos, tokens=1):
    B = cache["ckv"].shape[1]
    toks = jnp.ones((B, tokens), jnp.int32)
    return m.joyai_llm_flash_forward_cached(
        params, toks, cache, pos, cfg, live=jnp.ones((B, tokens), bool))


def _filled_cache(cfg, batch, positions, seed=2):
    cache = m.init_cache(cfg, batch, positions)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {**cache,
            "ckv": jax.random.normal(ks[0], cache["ckv"].shape, cfg.dtype),
            "kpe": jax.random.normal(ks[1], cache["kpe"].shape, cfg.dtype)}


FALLBACKS = {
    "off_tpu": dict(),
    "prompt": dict(tokens=8, tpu=True),
    "ambient_mesh": dict(mesh=True, tpu=True),
    "ragged_pool": dict(positions=S - 8, tpu=True),
    "narrow_latent": dict(kv_lora_rank=64, tpu=True),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_a_call_that_does_not_engage_returns_the_einsums_bits(
        case, monkeypatch):
    kw = FALLBACKS[case]
    cfg = make_cfg(kv_lora_rank=kw.get("kv_lora_rank", C))
    params = make_params(cfg)
    T = kw.get("tokens", 1)
    positions = kw.get("positions", S)
    cache = m.init_cache(cfg, 2, positions) if T > 1 \
        else _filled_cache(cfg, 2, positions)
    pos = 0 if T > 1 else jnp.asarray([3, BLOCK + 1], jnp.int32)
    want, wcache = _tick(cfg, params, cache, pos, T)
    if kw.get("tpu"):
        _as_tpu(monkeypatch)
        _refuse_the_kernel(monkeypatch)
    assert la.live_latent_plan(T, cache["ckv"], pos) is None \
        or kw.get("mesh")
    if kw.get("mesh"):
        from paddle_tpu.parallel.mesh import build_mesh, use_mesh
        with use_mesh(build_mesh({"tp": 2})):
            assert la.live_latent_plan(T, cache["ckv"], pos) is None
            got, gcache = _tick(cfg, params, cache, pos, T)
    else:
        got, gcache = _tick(cfg, params, cache, pos, T)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in ("ckv", "kpe", "stats"):
        np.testing.assert_array_equal(np.asarray(gcache[name]),
                                      np.asarray(wcache[name]))


def test_it_engages_by_shape_and_platform_alone(monkeypatch):
    """No option and no environment variable: the same call, a different
    answer from `is_tpu()` alone, and the rule reads nothing of the
    environment while it decides."""
    import os
    pool = jax.ShapeDtypeStruct((L, 4, S, C), jnp.bfloat16)
    assert not la.absorbed_engages(1, pool)              # the CPU suite
    _as_tpu(monkeypatch)
    with monkeypatch.context() as mp:
        class Closed(dict):
            def __getitem__(self, key):
                raise AssertionError(f"the rule read ${key}")
            get = __contains__ = __getitem__
        mp.setattr(os, "environ", Closed())
        mp.setattr(os, "getenv", Closed().get)
        assert la.absorbed_engages(1, pool)
        assert not la.absorbed_engages(2, pool)
        assert not la.absorbed_engages(1, jax.ShapeDtypeStruct(
            (L, 4, S + BLOCK // 2, C), jnp.bfloat16))
        assert not la.absorbed_engages(1, jax.ShapeDtypeStruct(
            (L, 4, S, C // 2), jnp.bfloat16))
    cfg = make_cfg()
    params = make_params(cfg)
    cache = _filled_cache(cfg, 4, S)
    pos = jnp.asarray([0, 5, BLOCK, S - 1], jnp.int32)
    got, gcache = _tick(cfg, params, cache, pos)
    monkeypatch.undo()
    want, wcache = _tick(cfg, params, cache, pos)
    scale = max(float(jnp.abs(want).max()), 1.0)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= 64 * np.finfo(np.float32).eps * scale
    assert (np.asarray(got) != np.asarray(want)).any()   # not the einsums
    # layer 0's rows are written before any attention runs; the count is
    # the work list's
    for name in ("ckv", "kpe"):
        np.testing.assert_array_equal(np.asarray(gcache[name][0]),
                                      np.asarray(wcache[name][0]))
    read, pool_positions = (int(v) for v in gcache["stats"][2:])
    assert pool_positions == 4 * S == int(wcache["stats"][2])
    assert read == (1 + 1 + 2 + 2) * BLOCK


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


def test_the_engine_serves_the_references_tokens_and_counts_what_it_read(
        monkeypatch):
    """An engine whose tick takes the kernel (in the interpreter): the
    greedy streams are the plain reference's argmax at every position
    that is no near-tie, and the einsum engine's; and on every
    `serving.decode_tick` span the whole live blocks of the rows that
    were requests, hand-worked here from the lengths each request had
    reached, over layers x slots x max_len."""
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.profiler import (clear_profiler_spans,
                                     get_profiler_spans)
    cfg = make_cfg()
    params = make_params(cfg, 5)
    # the second request crosses a block's edge while it decodes
    prompts = [_tokens(n, 10 + n) for n in (5, BLOCK - 2)]
    slots, gen = 3, 4

    def serve():
        router = create_router(params, cfg, replicas=1, num_slots=slots,
                               family="joyai_llm_flash", max_len=S)
        reqs = [router.submit(p, gen) for p in prompts]
        clear_profiler_spans()
        while router.has_work():
            router.step()
        ticks = [s.counts for s in get_profiler_spans()
                 if s.name == "serving.decode_tick"]
        router.close()
        return [list(r.tokens) for r in reqs], ticks

    want, plain = serve()
    pool = cfg.num_layers * slots * S
    assert plain and all(c["kv_positions_read"] == pool
                         == c["kv_positions_pool"] for c in plain)
    _as_tpu(monkeypatch)
    got, ticks = serve()
    assert got == want
    arch = dict(num_layers=cfg.num_layers,
                first_k_dense_replace=cfg.first_k_dense_replace,
                num_heads=cfg.num_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, layer_norm_eps=cfg.rms_norm_eps,
                num_experts_per_tok=cfg.num_experts_per_tok,
                first_expert=cfg.first_expert,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor)
    forward = jax.jit(lambda p, seq: ref.forward(p, seq, arch))
    for prompt, tokens in zip(prompts, got):
        # causal: padded to whole blocks of the reference's query rows
        n = len(prompt) + gen - 1
        seq = np.zeros(-(-n // ref.QUERY_ROWS) * ref.QUERY_ROWS, np.int32)
        seq[:n] = np.concatenate([prompt, tokens[:-1]])
        rows = np.asarray(forward(params, jnp.asarray(seq)))[
            len(prompt) - 1:n]
        top2 = np.sort(rows, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-4
        assert clear.sum() >= gen - 1
        assert (rows.argmax(-1) == np.asarray(tokens))[clear].all()
    assert all(c["kv_positions_pool"] == pool for c in ticks)
    # every request is admitted in the first step and decodes gen - 1
    # further tokens: at tick i a row holds its prompt + i positions and
    # writes one more; the third slot stays idle and reads nothing
    assert len(ticks) == gen - 1
    width = (C + cfg.qk_rope_head_dim) * 4
    for i, c in enumerate(ticks):
        blocks = sum(-(-(len(p) + i + 1) // BLOCK) for p in prompts)
        assert c["kv_positions_read"] == cfg.num_layers * blocks * BLOCK
        assert c["latent_bytes"] == c["kv_positions_read"] * width
