"""Decode-path attention over a KV cache — the shared seam for every
cached forward (greedy decode, the continuous-batching serving engine).

Reference analog: the FusedMultiTransformer decode attention
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29, the
masked single-step branch) reached via
incubate/nn/layer/fused_transformer.py:1022. TPU-native collapse: at
T=1 the attention is a bandwidth-bound matvec over the cache — flash
tiling buys nothing — so the implementations here are dense masked
einsums; what stays selectable is the precision trade.

One implementation serves BOTH cache-position shapes:
- scalar `pos` — the whole batch sits at one position (whole-batch
  greedy decode, models/decode.py);
- per-row `pos` [B] — every row advances independently (the serving
  engine's slot pool, inference/serving.py: requests join and leave
  mid-decode, so slot i holds `pos[i]` tokens). T may exceed 1 here:
  the speculative verify pass (inference/spec_decode.py) runs the
  current token + gamma drafts as one [B, gamma+1] step — the mask
  stays per-query-position causal, and multi-token per-row writes
  drop (never clamp) positions past the cache end.

The cached forwards (models/gpt.py, models/llama.py) hold the cache as
ONE stacked pool [L, ...] per k/v and carry it whole through their
layer scan: `write_kv` / `write_kv_paged` with `layer=` write only the
step's new rows at [layer, ...], in place, and `layer_view` reads the
layer back for the attention — the pool is never sliced into per-layer
buffers nor restacked (a 3.2 GB pool moved ~5x a tick that way).

GQA is native: kc/vc carry KV heads; queries fold their group axis into
the einsum so repeated KV is never materialized (models/llama.py's
decode-bandwidth trade).

Implementation selection (the kernels/registry.py seam — env >
registry winner > default, same precedence as flash_attention._attn_impl):
- 'dense'  f32 scores AND f32 context accumulation (default: exactly
  the training forward's numerics, required for the serving engine's
  bit-parity guarantee against per-request greedy decode);
- 'mixed'  QK^T and P·V run in the cache dtype with an f32 softmax —
  halves decode HBM traffic for bf16 caches; opt in per backend via
  the registry or PADDLE_TPU_DECODE_ATTN_IMPL;
- 'paged'  the serving engine's block-pool cache layout (vLLM's
  PagedAttention, SOSP '23): K/V live in fixed-size pages
  [P, page_size, KV, hd] shared by every slot, and a per-slot page
  table [B, max_pages] maps logical cache positions to physical
  pages. `gather_pages` re-linearizes a slot's view (logical position
  p lands at view index p, so the attention math — and therefore the
  token stream — is BIT-IDENTICAL to 'dense'); `write_kv_paged`
  scatters the step's K/V through the table. The selector only
  changes the CACHE LAYOUT the serving engine allocates; the
  attention math of a gathered view is 'dense' (attn_math_impl).
  Kill switch: PADDLE_TPU_DECODE_ATTN_IMPL=dense.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

__all__ = ["write_kv", "cached_attention", "decode_attn_impl",
           "gather_pages", "write_kv_paged", "layer_view",
           "attn_math_impl", "cache_pspecs", "attended_tokens",
           "kv_view_extent"]


def cache_pspecs(paged: bool, tp_axis: str = "tp"):
    """PartitionSpecs for the decode-cache leaves on a tensor-parallel
    serving mesh (inference/serving.py `mesh=`). Both layouts are
    rank-5 with the KV-head axis at position 3 — dense
    [L, N, max_len, KV, hd] and paged [L, P, page_size, KV, hd] — so
    ONE spec head-shards either: every device holds every slot's (or
    page's) full position range for ITS heads, which keeps write_kv /
    write_kv_paged's scatters and gather_pages' page gather local
    (no resharding inside the tick). The page table is replicated —
    it indexes pages, not heads, and every shard needs the whole map.
    When tp does not divide the KV heads (deep-GQA, e.g. 2 KV heads on
    tp=4) the engine's shape-aware degrade (parallel.mesh.sharding_for
    with shape=) drops the head axis to replicated — the
    "replicated-or-head-sharded" choice, made per leaf."""
    from jax.sharding import PartitionSpec as P
    kv = P(None, None, None, tp_axis, None)
    specs = {"k": kv, "v": kv}
    if paged:
        specs["pt"] = P()
    return specs


def decode_attn_impl() -> str:
    """Selector: env PADDLE_TPU_DECODE_ATTN_IMPL > registry winner
    ('decode_attention', current backend class) > 'dense'. The env var
    is re-read per trace like the Pallas kill switches."""
    env = os.environ.get("PADDLE_TPU_DECODE_ATTN_IMPL")
    if env:
        return env
    from . import registry
    win = registry.winner("decode_attention",
                          backend=registry.backend_class(
                              jax.default_backend()))
    return win or "dense"


def attn_math_impl(impl: str | None = None) -> str:
    """The attention-math flavor for a given selector: 'paged' is a
    cache LAYOUT — its gathered per-slot view runs the 'dense' f32
    math (bit-parity with the dense pool is the whole point)."""
    impl = impl or decode_attn_impl()
    return "dense" if impl == "paged" else impl


def gather_pages(pages, table, layer=None):
    """Re-linearize per-slot cache views from the page pool.

    pages [P, page_size, KV, hd]; table [B, max_pages] int32 of
    physical page ids. -> [B, max_pages * page_size, KV, hd] where
    view index p holds the K/V written at logical position p (page
    p // page_size at offset p % page_size) — so `cached_attention`
    over the view is bit-identical to the dense [B, S, ...] cache.
    Unmapped table entries point at the reserved scratch page 0; the
    position mask keeps its garbage at an exact softmax 0. With
    `layer` (a traced index) `pages` is the stacked pool
    [L, P, page_size, KV, hd] and the pages are gathered straight from
    [layer, page] — the layer's pages are never sliced out first."""
    B, mp = table.shape
    v = pages.at[_at_layer(layer) + (table.reshape(-1),)].get(mode="fill")
    return v.reshape(B, mp * pages.shape[-3], *pages.shape[-2:])


def write_kv_paged(pages, table, k, pos, layer=None):
    """Scatter the step's k (or v) [B, T, KV, hd] into the page pool
    [P, page_size, KV, hd] through the per-slot table [B, max_pages].
    Token t of row b sits at logical position pos(+t) -> physical
    (table[b, p // ps], p % ps). Rows whose table maps to the scratch
    page (freed slots, positions past a slot's allocation) write
    garbage there — never attended. With `layer` (a traced index)
    `pages` is the stacked pool [L, P, page_size, KV, hd] and the rows
    land at [layer, page, offset] — the cached forwards' form: the
    scatter touches the step's B*T rows only and XLA keeps it in place
    on the donated pool carried through the layer scan."""
    B, T = k.shape[:2]
    ps = pages.shape[-3]
    qpos = _query_positions(pos, B, T)                 # [B, T]
    raw_idx = qpos // ps
    page_idx = jnp.clip(raw_idx, 0, table.shape[1] - 1)
    page_id = jnp.take_along_axis(table, page_idx, axis=1)      # [B, T]
    # positions past the table (bucket pad beyond max_len) go to the
    # scratch page, never clamp onto a real tail page
    page_id = jnp.where(raw_idx < table.shape[1], page_id, 0)
    off = qpos % ps
    upd = k.astype(pages.dtype).reshape(B * T, *k.shape[2:])
    return pages.at[_at_layer(layer) + (page_id.reshape(-1),
                                        off.reshape(-1))].set(upd)


def write_kv(kc, k, pos, layer=None):
    """Write the step's k (or v) [B, T, KV, hd] into the cache
    [B, S, KV, hd] at position(s) `pos` — scalar (one
    dynamic_update_slice) or [B] per-row (each slot writes at its own
    offset, the serving engine's in-place slot write; T == 1 clamps a
    position past the end onto the last slot, as a
    dynamic_update_slice would). Per-row multi-token writes (T > 1 —
    the speculative verify pass lands the current token + gamma drafts
    in one call) go through a scatter whose out-of-bounds rows DROP: a
    draft position past the cache end must vanish, not clamp onto (and
    corrupt) the row's tail.

    With `layer` (a traced index) `kc` is the stacked pool
    [L, B, S, KV, hd] and the same rows land at [layer, ...] — the
    cached forwards' form. Either way only the step's rows are
    written, so on a donated buffer (or one carried through the layer
    scan) XLA updates in place and the rest of the pool never moves."""
    k = k.astype(kc.dtype)
    at = _at_layer(layer)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(
            kc, k[(None,) * len(at)], at + (0, pos, 0, 0))
    B, T = k.shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)
    if T == 1:
        p = jnp.clip(pos, 0, kc.shape[-3] - 1)
        return kc.at[at + (rows, p)].set(k[:, 0],
                                         mode="promise_in_bounds")
    qpos = _query_positions(pos, B, T)                 # [B, T]
    return kc.at[at + (jnp.broadcast_to(rows[:, None], (B, T)),
                       qpos)].set(k, mode="drop")


def layer_view(pool, layer, table=None):
    """What layer `layer` (a traced index) of the stacked pool holds,
    as `cached_attention` wants it: the dense row block
    [B, S, KV, hd] read straight out of [L, B, S, KV, hd], or — with
    the page `table` — the layer's pages re-linearized per slot
    (`gather_pages`). A read that XLA fuses into its consumer: the
    cached forwards carry the pool whole and never restack it."""
    if table is None:
        return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    return gather_pages(pool, table, layer)


def _at_layer(layer):
    """Index prefix of the write forms: () for one layer's cache,
    (layer,) for the stacked pool."""
    return () if layer is None else (layer,)


def attended_tokens(positions, active):
    """In-jit telemetry tap: total cache tokens this tick's attention
    ADMITS (the `<= position` mask of `cached_attention`) — per active
    row, positions[b] cache slots plus the current token. This is the
    roofline-attribution observable (profiler/serving_telemetry
    `attended` field): the attention-math FLOPs and the *useful* KV
    bytes scale with it, while the implementation's KV read scales
    with the full view extent (`kv_view_extent`) — the gap between the
    two is the masked-waste column of tools/serving_attrib.py."""
    return jnp.sum(jnp.where(active, positions + 1, 0)).astype(jnp.int32)


def kv_view_extent(paged: bool, max_len: int, max_pages: int = 0,
                   page_size: int = 0) -> int:
    """Host-side: the per-row cache positions one decode-attention call
    actually READS — the dense pool attends its whole [*, max_len]
    row under the mask, and the paged gather materializes the full
    [*, max_pages * page_size] table view (unmapped entries hit the
    scratch page but their bytes still move). The cost-model's
    KV-gather phase prices against this, not against live tokens."""
    return max_pages * page_size if paged else max_len


def _query_positions(pos, B, T):
    """Absolute positions of the T queries per row -> [B, T]."""
    offs = jnp.arange(T, dtype=jnp.int32)[None, :]
    if jnp.ndim(pos) == 0:
        return jnp.broadcast_to(pos + offs, (B, T))
    return pos[:, None] + offs


def cached_attention(q, kc, vc, pos, impl: str | None = None):
    """Masked attention of q [B, T, H, hd] against the cache kc/vc
    [B, S, KV, hd]; query t of row b sits at absolute position
    `pos[b] + t` (pos scalar or [B]) and sees cache slots <= that
    position. Returns ctx [B, T, H, hd] float32 (callers cast).

    Slots above the row's own position are masked to -inf before the
    softmax, so stale cache contents (a freed slot's previous request,
    bucket-pad garbage beyond the true prompt length) contribute an
    exact 0.0 — the serving engine's correctness rests on this."""
    B, T, H, hd = q.shape
    S, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    impl = attn_math_impl(impl)
    if impl not in ("dense", "mixed"):
        raise ValueError(
            f"unknown decode_attention impl {impl!r} (dense|mixed|paged)")
    dot_dt = kc.dtype if impl == "mixed" else jnp.float32
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B, T, KV, G, hd).astype(dot_dt) * jnp.asarray(
        scale, dot_dt)
    s = jnp.einsum("btkgd,bskd->bkgts", qf, kc.astype(dot_dt))
    qpos = _query_positions(pos, B, T)                             # B,T
    # mask [B,1,1,T,S] broadcast over the (kv-head, group) axes
    mask = (jnp.arange(S, dtype=jnp.int32)[None, :]
            <= qpos[..., None])[:, None, None, :, :]
    s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgts,bskd->btkgd", p.astype(dot_dt)
                     if impl == "mixed" else p, vc.astype(dot_dt))
    return ctx.reshape(B, T, H, hd).astype(jnp.float32)
