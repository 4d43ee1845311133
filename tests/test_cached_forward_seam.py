"""The cached forwards carry the stacked KV pool whole through their
layer scan and write only the step's rows in place at [layer, ...]
(kernels/decode_attention.py `write_kv(..., layer)` /
`write_kv_paged(..., layer)` / `layer_view`). The oracle here is the
form they replace: the layers one by one, each over ITS OWN cache (the
pool sliced per layer as the scan's xs, restacked as its ys), with the
per-layer writes as they were (kept in this file, independent of the
seam's layer forms) and the seam's unchanged `cached_attention`. Same
values written, same values attended, so logits and pools must match
bit for bit in every write form, layout, family, truncation and unroll.
Both sides are jitted with the same scan unroll: on the CPU a
multiply-add rounds once or twice by whether XLA fuses the pair, which
follows the loop structure, not the seam.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.decode_attention import cached_attention
from paddle_tpu.kernels.quant_matmul import leaf_matmul
from paddle_tpu.models import gpt, llama

L, B, S, PS = 3, 3, 16, 4            # layers, rows, positions, page size
MP = S // PS                         # pages a row


# ---- the per-layer writes and the page gather as the xs/ys scan used them
def _old_write_kv(kc, k, pos):
    k = k.astype(kc.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
    n, T = k.shape[:2]
    if T == 1:
        return jax.vmap(
            lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0))
        )(kc, k, pos)
    qpos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, T))
    return kc.at[rows, qpos].set(k, mode="drop")


def _old_write_kv_paged(pages, table, k, pos):
    n, T = k.shape[:2]
    ps = pages.shape[1]
    offs = jnp.arange(T, dtype=jnp.int32)[None, :]
    qpos = (jnp.broadcast_to(pos + offs, (n, T)) if jnp.ndim(pos) == 0
            else pos[:, None] + offs)
    raw = qpos // ps
    page_id = jnp.take_along_axis(
        table, jnp.clip(raw, 0, table.shape[1] - 1), axis=1)
    page_id = jnp.where(raw < table.shape[1], page_id, 0)
    upd = k.astype(pages.dtype).reshape(n * T, *k.shape[2:])
    return pages.at[page_id.reshape(-1), (qpos % ps).reshape(-1)].set(upd)


def _old_gather_pages(pages, table):
    n, mp = table.shape
    v = jnp.take(pages, table.reshape(-1), axis=0)
    return v.reshape(n, mp * pages.shape[1], *pages.shape[2:])


def _attend(q, k, v, kc, vc, pos, pt):
    """Write one layer's k/v into ITS cache, attend over it."""
    if pt is None:
        kc, vc = _old_write_kv(kc, k, pos), _old_write_kv(vc, v, pos)
        return cached_attention(q, kc, vc, pos), kc, vc
    kc = _old_write_kv_paged(kc, pt, k, pos)
    vc = _old_write_kv_paged(vc, pt, v, pos)
    return (cached_attention(q, _old_gather_pages(kc, pt),
                             _old_gather_pages(vc, pt), pos), kc, vc)


def _over_layers(block, x, params, keys, cache, cfg, layers):
    """x through `block` layer by layer; -> (x, the restacked caches)."""
    n_l = layers or cfg.num_layers
    stacked = {k: params[k][:n_l] for k in keys if k in params}
    x, (ks, vs) = jax.lax.scan(
        lambda x, xs: block(x, *xs), x, (stacked, cache["k"], cache["v"]),
        unroll=min(cfg.decode_scan_unroll, n_l))
    return x, {"k": ks, "v": vs}


def _gpt_oracle(params, tokens, cache, pos, cfg, layers=None):
    n, T = tokens.shape
    pt = cache.get("pt")
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    if jnp.ndim(pos) == 0:
        wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], pos, T, 0)[None]
    else:
        wpe = jnp.take(params["wpe"], pos[:, None] + jnp.arange(T), axis=0,
                       mode="clip")
    x = x + wpe.astype(cfg.dtype)
    H, hd = cfg.num_heads, cfg.head_dim

    def block(x, p, kc, vc):
        a_in = gpt._ln(x, p["ln1_scale"], p["ln1_bias"], cfg.layer_norm_eps)
        qkv = leaf_matmul(a_in, p, "qkv_w") + p["qkv_b"].astype(x.dtype)
        q, k, v = (t.reshape(n, T, H, hd) for t in jnp.split(qkv, 3, -1))
        ctx, kc, vc = _attend(q, k, v, kc, vc, pos, pt)
        ctx = ctx.reshape(n, T, H * hd).astype(x.dtype)
        x = x + (leaf_matmul(ctx, p, "attn_out_w")
                 + p["attn_out_b"].astype(x.dtype))
        m_in = gpt._ln(x, p["ln2_scale"], p["ln2_bias"], cfg.layer_norm_eps)
        mh = leaf_matmul(m_in, p, "mlp_up_w")
        mh = jax.nn.gelu(mh + p["mlp_up_b"].astype(mh.dtype))
        m = leaf_matmul(mh, p, "mlp_down_w")
        return x + (m + p["mlp_down_b"].astype(m.dtype)), (kc, vc)

    x, out = _over_layers(block, x, params, gpt._BLOCK_KEYS_DENSE, cache,
                          cfg, layers)
    x = gpt._ln(x, params["ln_f_scale"], params["ln_f_bias"],
                cfg.layer_norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(x.dtype)), out


def _llama_oracle(params, tokens, cache, pos, cfg, layers=None):
    n, T = tokens.shape
    pt = cache.get("pt")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    s_cache = (cache["k"].shape[2] if pt is None
               else pt.shape[1] * cache["k"].shape[2])
    cos, sin = llama._rope_tables(s_cache, hd, cfg.rope_theta)
    if jnp.ndim(pos) == 0:
        cos = jax.lax.dynamic_slice_in_dim(cos, pos, T, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin, pos, T, axis=0)
    else:
        idx = pos[:, None] + jnp.arange(T)
        cos = jnp.take(cos, idx, axis=0, mode="clip")
        sin = jnp.take(sin, idx, axis=0, mode="clip")

    def block(x, p, kc, vc):
        h = llama._rmsnorm(x, p["attn_norm"], cfg.rms_eps)
        q = leaf_matmul(h, p, "q_w").reshape(n, T, H, hd)
        k = leaf_matmul(h, p, "k_w").reshape(n, T, KV, hd)
        v = leaf_matmul(h, p, "v_w").reshape(n, T, KV, hd)
        q = llama._apply_rope(q, cos, sin)
        k = llama._apply_rope(k, cos, sin)
        ctx, kc, vc = _attend(q, k, v, kc, vc, pos, pt)
        x = x + leaf_matmul(ctx.reshape(n, T, H * hd).astype(x.dtype),
                            p, "o_w")
        h = llama._rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
        gated = jax.nn.silu(leaf_matmul(h, p, "gate_w")) * \
            leaf_matmul(h, p, "up_w")
        return x + leaf_matmul(gated, p, "down_w"), (kc, vc)

    x, out = _over_layers(block, x, params, llama._BLOCK_KEYS, cache, cfg,
                          layers)
    x = llama._rmsnorm(x, params["norm_f"], cfg.rms_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(x.dtype)), out


def _family(name, unroll):
    if name == "gpt":
        cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=L,
                            num_heads=4, max_seq_len=S, dtype=jnp.float32,
                            decode_scan_unroll=unroll)
        params = gpt.init_gpt_params(cfg, jax.random.PRNGKey(1))
        return cfg, params, gpt.gpt_forward_cached, _gpt_oracle, 4
    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=L,
                            num_heads=4, num_kv_heads=2, ffn_hidden=64,
                            max_seq_len=S, dtype=jnp.float32,
                            decode_scan_unroll=unroll)
    params = llama.init_llama_params(cfg, jax.random.PRNGKey(1))
    return cfg, params, llama.llama_forward_cached, _llama_oracle, 2


# (write form, T, positions) — scalar = whole batch at one position
FORMS = {
    "scalar_prefill": (5, 0),
    "scalar_decode": (1, 7),
    "rows_t1": (1, [3, 0, 11]),
    # row 1 starts inside the cache and runs past its end: those rows
    # DROP (dense) / land on the scratch page (paged), never clamp
    "rows_verify": (3, [2, S - 2, 9]),
    "rows_chunk": (PS + 2, [0, 5, 2]),
}
CASES = [(fam, layout, form, None, 1)
         for fam in ("gpt", "llama") for layout in ("dense", "paged")
         for form in FORMS
         if not (layout == "paged" and form.startswith("scalar"))]
CASES += [(fam, layout, "rows_t1", layers, unroll)
          for fam in ("gpt", "llama") for layout in ("dense", "paged")
          for layers, unroll in ((2, 1), (None, 2), (None, L), (2, 2))]


@pytest.mark.parametrize(
    "fam,layout,form,layers,unroll", CASES,
    ids=["-".join(map(str, c)) for c in CASES])
def test_carried_pool_forward_matches_layerwise_oracle(fam, layout, form,
                                                       layers, unroll):
    cfg, params, fwd, oracle, kv_heads = _family(fam, unroll)
    T, pos = FORMS[form]
    pos = jnp.asarray(pos, jnp.int32)
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
    n_l = layers or L
    # a cache that already holds something everywhere: a write that
    # lands on the wrong row, or moves one it should not, shows
    if layout == "dense":
        shape = (n_l, B, S, kv_heads, cfg.head_dim)
        cache = {}
    else:
        shape = (n_l, B * MP + 1, PS, kv_heads, cfg.head_dim)
        cache = {"pt": jnp.arange(1, B * MP + 1,
                                  dtype=jnp.int32).reshape(B, MP)}
    cache["k"] = jnp.asarray(rng.randn(*shape), cfg.dtype)
    cache["v"] = jnp.asarray(rng.randn(*shape), cfg.dtype)

    want_lg, want = jax.jit(
        lambda p, t, c, ps: oracle(p, t, c, ps, cfg, layers)
    )(params, tokens, cache, pos)
    got_lg, got = jax.jit(
        lambda p, t, c, ps: fwd(p, t, c, ps, cfg, layers=layers)
    )(params, tokens, cache, pos)

    np.testing.assert_array_equal(np.asarray(got_lg), np.asarray(want_lg))
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[leaf]),
                                      np.asarray(want[leaf]))
        # only the step's rows changed (dense: rows past the end dropped)
        changed = np.any(np.asarray(got[leaf]) != np.asarray(cache[leaf]),
                         axis=(-1, -2))
        assert changed.sum() <= n_l * B * T
    if layout == "paged":
        np.testing.assert_array_equal(np.asarray(got["pt"]),
                                      np.asarray(cache["pt"]))
    assert sorted(got) == sorted(cache)


def test_layer_write_forms_equal_the_per_layer_forms():
    """`layer=` only prepends the pool's layer index: each write form on
    the stacked pool equals the same form on that layer's own cache, and
    leaves the other layers untouched."""
    from paddle_tpu.kernels.decode_attention import (
        gather_pages, layer_view, write_kv, write_kv_paged)
    rng = np.random.RandomState(3)
    pool = jnp.asarray(rng.randn(L, B, S, 2, 4), jnp.float32)
    pages = jnp.asarray(rng.randn(L, B * MP + 1, PS, 2, 4), jnp.float32)
    table = jnp.arange(1, B * MP + 1, dtype=jnp.int32).reshape(B, MP)
    for T, pos in FORMS.values():
        pos = jnp.asarray(pos, jnp.int32)
        k = jnp.asarray(rng.randn(B, T, 2, 4), jnp.float32)
        for l in range(L):
            got = np.asarray(write_kv(pool, k, pos, jnp.int32(l)))
            want = np.asarray(pool).copy()
            want[l] = np.asarray(_old_write_kv(pool[l], k, pos))
            np.testing.assert_array_equal(got, want)
            got = np.asarray(write_kv_paged(pages, table, k, pos,
                                            jnp.int32(l)))
            want = np.asarray(pages).copy()
            want[l] = np.asarray(_old_write_kv_paged(pages[l], table, k,
                                                     pos))
            np.testing.assert_array_equal(got, want)
    for l in range(L):
        np.testing.assert_array_equal(
            np.asarray(layer_view(pool, jnp.int32(l))), np.asarray(pool[l]))
        np.testing.assert_array_equal(
            np.asarray(layer_view(pages, jnp.int32(l), table)),
            np.asarray(gather_pages(pages[l], table)))
        np.testing.assert_array_equal(
            np.asarray(gather_pages(pages[l], table)),
            np.asarray(_old_gather_pages(pages[l], table)))
