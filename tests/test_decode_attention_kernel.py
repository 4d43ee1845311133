"""The length-aware decode attention (kernels/decode_attention.py
`length_aware_attention`: per row, only the blocks of the stacked pool
that hold a live position) against `cached_attention(impl="dense")`, in
the Pallas interpreter on the CPU; what engages it (`length_aware`) and
that every call it does not engage on returns the einsum's bits; and the
engine's `kv_positions_read` / `kv_positions_pool` counts where it runs.

Tolerance: the kernel's arithmetic is the einsum's (float32 scores,
statistics, probabilities and context from the cache's own K and V), so
only the ORDER of float32 summation differs — a block at a time, with a
running softmax. The context is a convex combination of V rows, so the
difference is held to ULPS float32 ulps at the scale of the largest |V|
the row can see (measured: under 4).

Reference analog: the masked single-step branch of
paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29, which
walks the cache up to the step's own length."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import decode_attention as da

BLOCK = da.DECODE_BLOCK
L, S, KV, HD = 3, 2 * BLOCK, 8, 128
LAYER = 1
ULPS = 16
# an idle row (length 0), then 1, block - 1, block, block + 1, full
RAGGED = dict(pos=[7, 0, BLOCK - 2, BLOCK - 1, BLOCK, S - 1],
              live=[False, True, True, True, True, True])


def _pool(dtype, groups, batch, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (L, batch, S, KV, HD)
    kc = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    q = jax.random.normal(ks[2], (batch, 1, KV * groups, HD),
                          jnp.float32).astype(dtype)
    return q, kc, vc


@functools.partial(jax.jit, static_argnames=("has_live",))
def _kernel(q, kc, vc, pos, layer, live, has_live=True):
    plan = da.work_list(pos, live if has_live else None, *kc.shape[1:3])
    return da.length_aware_attention(q, kc, vc, layer, plan,
                                     interpret=True)


def _dense(q, kc, vc, pos, layer):
    return da.cached_attention(q, da.layer_view(kc, layer),
                               da.layer_view(vc, layer), pos, impl="dense")


def _tolerance(vc, layer):
    return ULPS * np.finfo(np.float32).eps * float(
        jnp.abs(vc[layer].astype(jnp.float32)).max())


@pytest.mark.parametrize("pos_kind", ["per_row", "scalar"])
@pytest.mark.parametrize("groups", [1, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_matches_dense_at_a_traced_layer(dtype, groups, pos_kind):
    live = np.asarray(RAGGED["live"])
    q, kc, vc = _pool(dtype, groups, len(live))
    layer = jnp.int32(LAYER)
    if pos_kind == "per_row":
        pos = jnp.asarray(RAGGED["pos"], jnp.int32)
        got = _kernel(q, kc, vc, pos, layer, jnp.asarray(live))
    else:
        # the whole batch at one position (models/decode.py), no mask
        pos, live = jnp.int32(BLOCK + 5), np.ones_like(live)
        got = _kernel(q, kc, vc, pos, layer, None, has_live=False)
    want = _dense(q, kc, vc, pos, layer)
    assert got.shape == want.shape and got.dtype == jnp.float32
    err = np.abs(np.asarray(got) - np.asarray(want)).max(axis=(1, 2, 3))
    assert (err[live] <= _tolerance(vc, LAYER)).all(), err
    # a row that is no request reads nothing and comes back as zeros
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("planted", [np.nan, np.inf, 1e30],
                         ids=["nan", "inf", "stale"])
def test_what_lies_past_a_rows_length_changes_nothing(planted):
    """The einsum gives a dead position an exact 0 weight, which a nan
    or inf V still poisons (0 * nan); the kernel never lets it reach a
    sum: planted K and V past each row's length — and a whole idle row,
    and every other layer — leave its bits alone."""
    pos = jnp.asarray(RAGGED["pos"], jnp.int32)
    live = jnp.asarray(RAGGED["live"])
    q, kc, vc = _pool(jnp.bfloat16, 1, len(RAGGED["pos"]))
    clean = _kernel(q, kc, vc, pos, jnp.int32(LAYER), live)
    dead = (jnp.arange(S)[None, :] > pos[:, None]) | ~live[:, None]
    dead = dead[None, :, :, None, None] | (
        jnp.arange(L) != LAYER)[:, None, None, None, None]
    bad = jnp.asarray(planted, jnp.bfloat16)
    got = _kernel(q, jnp.where(dead, bad, kc), jnp.where(dead, bad, vc),
                  pos, jnp.int32(LAYER), live)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def _as_tpu(monkeypatch):
    """The seam as it decides on the chip, its kernel in the interpreter
    (a test steers what `is_tpu()` answers; the program has no option)."""
    import paddle_tpu.device as device
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(
        da, "length_aware_attention",
        functools.partial(da.length_aware_attention, interpret=True))


FALLBACKS = {
    "off_tpu": dict(),
    "verify_pass": dict(tokens=3, tpu=True),
    "ambient_mesh": dict(mesh=True, tpu=True),
    "ragged_pool": dict(positions=S - 8, tpu=True),
    "narrow_head": dict(hd=64, tpu=True),
    "mixed": dict(impl="mixed", tpu=True),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_a_call_that_does_not_engage_returns_the_einsums_bits(
        case, monkeypatch):
    kw = FALLBACKS[case]
    T, hd = kw.get("tokens", 1), kw.get("hd", HD)
    positions = kw.get("positions", S)
    rng = np.random.default_rng(3)
    pool = (L, 2, positions, KV, hd)
    kc = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((2, T, KV, hd)), jnp.bfloat16)
    pos = jnp.asarray([3, BLOCK + 1], jnp.int32)
    if kw.get("tpu"):
        _as_tpu(monkeypatch)

        def refuse(*a, **k):
            raise AssertionError("the kernel ran")
        monkeypatch.setattr(da, "length_aware_attention", refuse)
    if kw.get("impl"):
        monkeypatch.setattr(da, "DECODE_ATTN_IMPL", kw["impl"])

    def call():
        plan = da.live_block_plan(T, kc, pos, jnp.ones((2, T), bool))
        assert plan is None
        return da.cached_attention(q, kc, vc, pos, layer=jnp.int32(LAYER),
                                   plan=plan)
    if kw.get("mesh"):
        from paddle_tpu.parallel.mesh import build_mesh, use_mesh
        with use_mesh(build_mesh({"tp": 2})):
            got = call()
    else:
        got = call()
    want = da.cached_attention(q, da.layer_view(kc, jnp.int32(LAYER)),
                               da.layer_view(vc, jnp.int32(LAYER)), pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_paged_view_never_reaches_the_kernel(monkeypatch):
    """The cached forwards hand the paged pool over as a gathered view
    with no `layer`: the einsum, on a TPU too."""
    _as_tpu(monkeypatch)
    rng = np.random.default_rng(4)
    pages = jnp.asarray(rng.standard_normal((L, 9, BLOCK // 4, KV, HD)),
                        jnp.bfloat16)
    table = jnp.asarray(rng.permutation(8).reshape(2, 4) + 1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 1, KV, HD)), jnp.bfloat16)
    pos = jnp.asarray([3, 70], jnp.int32)
    view = da.layer_view(pages, jnp.int32(LAYER), table)
    got = da.cached_attention(q, view, view, pos)
    monkeypatch.undo()
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(da.cached_attention(q, view, view, pos)))


def test_it_engages_by_shape_and_platform_alone(monkeypatch):
    pool = jax.ShapeDtypeStruct((L, 4, S, KV, HD), jnp.bfloat16)
    assert not da.length_aware(1, pool)              # the CPU suite
    _as_tpu(monkeypatch)
    assert da.length_aware(1, pool)
    assert not da.length_aware(2, pool)
    q, kc, vc = _pool(jnp.bfloat16, 1, 4)
    pos = jnp.asarray([0, 5, BLOCK, S - 1], jnp.int32)
    got = da.cached_attention(q, kc, vc, pos, layer=jnp.int32(LAYER),
                              plan=da.live_block_plan(1, kc, pos))
    want = _dense(q, kc, vc, pos, jnp.int32(LAYER))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= _tolerance(vc, LAYER)
    assert (np.asarray(got) != np.asarray(want)).any()   # not the einsum


def _families():
    from paddle_tpu.models import gpt, llama
    gcfg = gpt.GPTConfig(vocab_size=64, hidden_size=KV * HD, num_layers=2,
                         num_heads=KV, ffn_hidden=64, max_seq_len=S,
                         sequence_parallel=False, remat=False,
                         dtype=jnp.float32)
    lcfg = llama.LlamaConfig(vocab_size=64, hidden_size=2 * KV * HD,
                             num_layers=2, num_heads=2 * KV,
                             num_kv_heads=KV, ffn_hidden=64, max_seq_len=S,
                             dtype=jnp.float32, remat=False)
    return {
        "gpt": (gcfg, gpt.init_gpt_params, gpt.gpt_forward_cached,
                gpt.init_kv_cache),
        "llama": (lcfg, llama.init_llama_params,
                  llama.llama_forward_cached, llama.init_kv_cache)}


@pytest.mark.parametrize("pos_kind", ["per_row", "scalar"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_the_cached_forwards_reach_it_through_the_seam(
        family, pos_kind, monkeypatch):
    """One single-token step of each family's cached forward over a
    pool a prefill filled: the kernel's logits against the einsum's,
    and the pool written the same."""
    cfg, init, fwd, init_cache = _families()[family]
    params = init(cfg, jax.random.PRNGKey(1))
    B, T0 = 3, BLOCK + 9
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T0), 0, 64)
    _, cache = fwd(params, toks, init_cache(cfg, B, S), 0, cfg)
    pos = jnp.asarray([4, BLOCK - 1, T0], jnp.int32) \
        if pos_kind == "per_row" else jnp.int32(T0)
    step = toks[:, :1]
    want, wcache = fwd(params, step, cache, pos, cfg)
    _as_tpu(monkeypatch)
    got, gcache = fwd(params, step, cache, pos, cfg,
                      live=jnp.ones((B, 1), bool))
    scale = float(jnp.abs(want).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= 64 * np.finfo(np.float32).eps * max(scale, 1.0)
    assert (np.asarray(got) != np.asarray(want)).any()
    # layer 0's rows are written before any attention runs
    np.testing.assert_array_equal(np.asarray(gcache["k"][0]),
                                  np.asarray(wcache["k"][0]))


@pytest.mark.parametrize("positions,active,want", [
    ([0, 5, 127, 128, 300], [0, 1, 1, 1, 1], (0 + 1 + 1 + 2 + 3) * BLOCK),
    ([0, 0, 0, 0], [0, 0, 0, 0], 0),
    ([511, 511], [1, 1], 2 * 512),            # never past the row's end
    ([40], [1], BLOCK),
])
def test_kv_positions_read_is_whole_live_blocks(positions, active, want):
    p, a = np.asarray(positions, np.int32), np.asarray(active, bool)
    assert da.kv_positions_read(p, a, 512, True) == want
    assert da.kv_positions_read(p, a, 512, False) == 512 * len(p)


def test_the_engine_serves_the_same_tokens_and_counts_what_it_read(
        monkeypatch):
    """A dense engine whose tick takes the kernel against one that takes
    the einsum: the same greedy streams (float32, no ties at this
    size), and on every `serving.decode_tick` span the whole live
    blocks of the rows that were requests, hand-worked here from the
    lengths each request had reached, over layers x slots x max_len."""
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.profiler import (clear_profiler_spans,
                                     get_profiler_spans)
    cfg, init, _, _ = _families()["gpt"]
    params = init(cfg, jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 60, n).astype(np.int32)
               for n in (5, BLOCK - 2, BLOCK + 3)]
    slots, gen = 4, 4

    def serve():
        router = create_router(params, cfg, replicas=1, num_slots=slots,
                               max_len=S, spec_decode="off", multi_tick=1)
        reqs = [router.submit(p, gen) for p in prompts]
        clear_profiler_spans()
        while router.has_work():
            router.step()
        ticks = [s.counts for s in get_profiler_spans()
                 if s.name == "serving.decode_tick"]
        return [list(r.tokens) for r in reqs], ticks

    want, plain = serve()
    pool = cfg.num_layers * slots * S
    assert plain and all(c["kv_positions_read"] == pool
                         == c["kv_positions_pool"] for c in plain)
    _as_tpu(monkeypatch)
    got, ticks = serve()
    assert got == want
    assert all(c["kv_positions_pool"] == pool for c in ticks)
    # every request is admitted in the first step and decodes gen - 1
    # further tokens: at tick i a row holds its prompt + i positions and
    # writes one more; the fourth slot stays idle and reads nothing
    assert len(ticks) == gen - 1
    for i, c in enumerate(ticks):
        blocks = sum(-(-(len(p) + i + 1) // BLOCK) for p in prompts)
        assert c["kv_positions_read"] == cfg.num_layers * blocks * BLOCK
