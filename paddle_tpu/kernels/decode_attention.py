"""Decode-path attention over a KV cache — the shared seam for every
cached forward (greedy decode, the continuous-batching serving engine).

Reference analog: the FusedMultiTransformer decode attention
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu:29, the
masked single-step branch) reached via
incubate/nn/layer/fused_transformer.py:1022. TPU-native collapse: at
T=1 the attention is a bandwidth-bound matvec over the cache — flash
tiling buys nothing, what it reads is the whole cost — so the forms
here are dense masked einsums and, for the serving pool's single-token
step, a kernel that reads only what is live; what stays selectable is
the precision trade.

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
step's new rows at [layer, ...], in place, and the attention reads the
layer back out of the same pool (`layer_view`, or the kernel's own
[layer, row, block] addressing) — the pool is never sliced into
per-layer buffers nor restacked (a 3.2 GB pool moved ~5x a tick that
way).

GQA is native: kc/vc carry KV heads; queries fold their group axis into
the einsum so repeated KV is never materialized (models/llama.py's
decode-bandwidth trade).

Handed the STACKED pool, the layer and a plan
(`cached_attention(layer=, plan=)`, the cached forwards' form over the
dense pool), a single-token step on a TPU does not read the layer's
whole [B, S] view: `length_aware` says when `live_block_plan` makes the
plan and the Pallas kernel below runs, which fetches, per slot, only
the blocks of DECODE_BLOCK positions that slot has written — the
einsum reads every position of every slot and masks afterwards, and in
a serving pool most of them are dead. Same arithmetic class as 'dense';
only the order of summation differs.

The attention math is one of two, chosen by DECODE_ATTN_IMPL below
(`cached_attention(impl=)` overrides it for one call):
- 'dense'  f32 scores AND f32 context accumulation (exactly the
  training forward's numerics, required for the serving engine's
  bit-parity guarantee against per-request greedy decode);
- 'mixed'  QK^T and P·V run in the cache dtype with an f32 softmax —
  halves decode HBM traffic for bf16 caches.

The cache LAYOUT is not chosen here: it is the engine's `kv_layout=`.
Under "paged" (vLLM's PagedAttention, SOSP '23) K/V live in fixed-size
pages [P, page_size, KV, hd] shared by every slot, and a per-slot page
table [B, max_pages] maps logical cache positions to physical pages.
`gather_pages` re-linearizes a slot's view (logical position p lands at
view index p, so the attention math — and therefore the token stream —
is BIT-IDENTICAL to the dense pool's); `write_kv_paged` scatters the
step's K/V through the table.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.compile_cache import bytecode_cache
from .primitives import NEG_INF, cdiv, round_up

with bytecode_cache():      # every cached forward imports this module
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["write_kv", "cached_attention", "gather_pages",
           "write_kv_paged", "layer_view", "cache_pspecs",
           "attended_tokens", "kv_view_extent", "ring_positions",
           "ring_rows", "blocked_attention", "DECODE_BLOCK",
           "kv_positions_read", "length_aware", "length_aware_attention",
           "live_block_plan", "work_list"]

# The decode attention math, 'dense' | 'mixed' (module docstring).
# ROADMAP S5 decides between them in the GPT serving cells and keeps one.
DECODE_ATTN_IMPL = "dense"

# Positions a block of the length-aware kernel holds: what one DMA
# fetches, and the grain a slot's read is rounded up to.
DECODE_BLOCK = 128


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


def write_kv(kc, k, pos, layer=None, ring: bool = False):
    """Write the step's k (or v) [B, T, KV, hd] into the cache
    [B, S, KV, hd] (or, a latent's rows, [B, T, W] into [B, S, W]: held
    by position with no head axis) at position(s) `pos` — scalar (one
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
    scan) XLA updates in place and the rest of the pool never moves.

    `ring` is the window layers' form: the cache axis S is a ring and
    position p lands on row p mod S, so a slot keeps its last S
    positions and nothing more (`ring_positions` says which position a
    row holds). One call writes at most S positions: a longer run
    would land two positions on one row."""
    k = k.astype(kc.dtype)
    at = _at_layer(layer)
    if ring:
        B, T = k.shape[:2]
        S = kc.shape[-3]
        if T > S:
            raise ValueError(f"a ring of {S} rows cannot take {T} "
                             "positions in one write")
        rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                                (B, T))
        return kc.at[at + (rows, _query_positions(pos, B, T) % S)].set(
            k, mode="promise_in_bounds")
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(
            kc, k[(None,) * len(at)], at + (0, pos) + (0,) * (k.ndim - 2))
    B, T = k.shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)
    if T == 1:
        p = jnp.clip(pos, 0, kc.shape[len(at) + 1] - 1)
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
                   page_size: int = 0, context: int | None = None) -> int:
    """Host-side: the per-row cache positions one decode-attention call
    actually READS. The paged gather materializes the full
    [*, max_pages * page_size] table view (unmapped entries hit the
    scratch page but their bytes still move). The dense pool under the
    masked einsum — off a TPU, the verify pass, a `tp` mesh — attends
    its whole [*, max_len] row; under the length-aware kernel (a
    single-token step on a TPU, `length_aware`) a live row of `context`
    positions reads them rounded up to whole blocks of DECODE_BLOCK,
    and an idle row nothing: hand `context` where that kernel runs. The
    cost-model's KV-gather phase prices against this, not against live
    tokens."""
    if paged:
        return max_pages * page_size
    if context is None:
        return max_len
    return min(max_len, round_up(int(context), DECODE_BLOCK))


def _query_positions(pos, B, T):
    """Absolute positions of the T queries per row -> [B, T]."""
    offs = jnp.arange(T, dtype=jnp.int32)[None, :]
    if jnp.ndim(pos) == 0:
        return jnp.broadcast_to(pos + offs, (B, T))
    return pos[:, None] + offs


def ring_positions(qpos, ring: int):
    """The position each ring row holds as a query at `qpos` [...] sees
    it -> [..., ring]: the largest p <= qpos with p mod ring == row.
    Negative where the slot has not reached the row yet (what lies
    there is a previous occupant's, and the mask drops it)."""
    rows = jnp.arange(ring, dtype=jnp.int32)
    return qpos[..., None] - (qpos[..., None] - rows) % ring


def ring_rows(true_len, ring: int):
    """After a prompt of `true_len` positions: the position whose K/V
    ring row r must hold -> [ring] (the newest p < true_len with
    p mod ring == r; where there is none yet, r itself, which the mask
    drops until decode writes it). The engine's slot write gathers
    these out of a prefill's position-ordered cache."""
    return jnp.maximum(ring_positions(jnp.asarray(true_len - 1, jnp.int32),
                                      ring),
                       jnp.arange(ring, dtype=jnp.int32))


def cached_attention(q, kc, vc, pos, impl: str | None = None,
                     window: int | None = None, layer=None, plan=None):
    """Masked attention of q [B, T, H, hd] against the cache kc/vc
    [B, S, KV, hd]; query t of row b sits at absolute position
    `pos[b] + t` (pos scalar or [B]) and sees cache slots <= that
    position. Returns ctx [B, T, H, hd] float32 (callers cast).

    With `layer` (a traced index) kc/vc are the STACKED pools
    [L, B, S, KV, hd] as the cached forwards carry them. With a `plan`
    (`live_block_plan`, made once a forward where `length_aware`
    holds) a Pallas kernel reads each row's live blocks straight out of
    [layer, row] and nothing else — a row that is no request reads
    nothing and returns zeros; without one this is `layer_view` and the
    einsum.

    Slots above the row's own position are masked to -inf before the
    softmax, so stale cache contents (a freed slot's previous request,
    bucket-pad garbage beyond the true prompt length) contribute an
    exact 0.0 — the serving engine's correctness rests on this.

    With `window` the cache axis is a ring (`write_kv(ring=True)`): row
    r holds position `ring_positions(qpos)[r]`, and the query sees it
    iff that position is one the slot has written (>= 0) and lies in
    (qpos - window, qpos]. The operands then stay in the cache dtype
    with float32 accumulation and a float32 softmax (impl 'native'): a
    float32 copy of a 16k-position layer would not fit beside it."""
    B, T, H, hd = q.shape
    if plan is not None:
        return length_aware_attention(q, kc, vc, layer, plan)
    if layer is not None:
        kc, vc = layer_view(kc, layer), layer_view(vc, layer)
    S, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    if impl == "native":
        return _native_attention(q, kc, vc, pos, window)
    if window is not None:
        raise ValueError("a window mask needs impl='native'")
    impl = impl or DECODE_ATTN_IMPL
    if impl not in ("dense", "mixed"):
        raise ValueError(
            f"unknown decode_attention impl {impl!r} (dense|mixed)")
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


def length_aware(T: int, pool) -> bool:
    """Whether a call on the stacked dense pool [L, B, S, KV, hd] takes
    the length-aware kernel — by what the call can observe, no option:
    one query token a row (the verify pass and prefill keep the
    einsum), a TPU, no ambient mesh of several devices (GSPMD cannot
    partition a Mosaic kernel; a tensor-parallel engine traces its
    forwards under `parallel.mesh.use_mesh`), and a pool the kernel's
    tiles fit: S a whole number of blocks, whole sublanes of KV heads
    and whole lanes of hd."""
    from ..device import is_tpu
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    S, KV, hd = pool.shape[2:]
    return (T == 1 and is_tpu() and (mesh is None or mesh.size == 1)
            and S % DECODE_BLOCK == 0 and KV % 8 == 0 and hd % 128 == 0)


def kv_positions_read(positions, active, max_len: int,
                      live_blocks: bool) -> int:
    """Host-side: the cache positions ONE layer's decode attention may
    touch this tick, from the lengths the kernel is handed — per live
    row its `positions + 1` rounded up to whole blocks, nothing for an
    idle row (`live_blocks`: the kernel's work list); on the einsum
    path every position of every row, so the share of the pool reads
    100% where the kernel does not run."""
    if not live_blocks:
        return int(np.size(positions)) * int(max_len)
    n = np.where(active, np.minimum(np.asarray(positions) + 1, max_len), 0)
    return int(cdiv(n, DECODE_BLOCK).sum()) * DECODE_BLOCK


def work_list(pos, live, B: int, S: int, block: int = DECODE_BLOCK):
    """A live-block kernel's scalar operands, from the rows' positions
    (scalar or [B]) and `live` [B] (None: every row) -> (n [B] positions
    row b may see — `pos + 1`, 0 for a row that is no request —, slot
    and block [B * S // block] of work item t, total [1] items). Row
    b's blocks of `block` positions 0 .. cdiv(n[b], block) - 1, row by
    row; entries past `total` are never read."""
    nb = S // block
    n = jnp.minimum(jnp.broadcast_to(pos, (B,)).astype(jnp.int32) + 1, S)
    if live is not None:
        n = jnp.where(live, n, 0)
    blocks = cdiv(n, block)
    ends = jnp.cumsum(blocks)
    t = jnp.arange(B * nb, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1), B - 1
                       ).astype(jnp.int32)
    blk = jnp.clip(t - (ends - blocks)[slot], 0, nb - 1).astype(jnp.int32)
    return n, slot, blk, ends[-1:].astype(jnp.int32)


def live_block_plan(T: int, pool, pos, live=None):
    """What a cached forward hands `cached_attention(plan=)`, made ONCE
    ahead of its layer scan (inside it XLA would redo the small index
    arithmetic every layer): the kernel's work list where the step's
    attention is the length-aware kernel (`length_aware`, and the
    `dense` math it computes is the one selected), else None — the
    einsum. `live` [B, T] is the forwards' mask of real tokens."""
    if DECODE_ATTN_IMPL != "dense" or not length_aware(T, pool):
        return None
    return work_list(pos, None if live is None else live[:, 0],
                     pool.shape[1], pool.shape[2])


def _decode_kernel(layer_ref, len_ref, slot_ref, blk_ref, total_ref,
                   q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                   m_ref, l_ref, acc_ref):
    """One pass over the work list (slot_ref[t], blk_ref[t]), t <
    total: the (row, block) pairs that hold a live position, row by
    row. Block t+1 is in flight from the pool in HBM while block t is
    computed (two buffers); a row's running max, sum and context sit in
    scratch from its first block to its last, which writes the row
    out. Rows with nothing live keep the zeros written first."""
    layer, total = layer_ref[0], total_ref[0]
    G, block = q_ref.shape[1], kbuf.shape[1]

    def fetch(t, buf):
        at = (layer, slot_ref[t], pl.ds(blk_ref[t] * block, block))
        return (pltpu.make_async_copy(k_hbm.at[at], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[buf],
                                      sem.at[1, buf]))

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        for c in fetch(0, 0):
            c.start()

    def step(t, _):
        buf = t % 2
        b, j = slot_ref[t], blk_ref[t]
        n = len_ref[b]

        @pl.when(t + 1 < total)
        def _():
            for c in fetch(t + 1, 1 - buf):
                c.start()

        for c in fetch(t, buf):
            c.wait()

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        kf = kbuf[buf].astype(jnp.float32)              # [block, KV, hd]
        vf = vbuf[buf].astype(jnp.float32)
        seen = (j * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, kf.shape[1], 1), 0)) < n
        # what lies past the row's length is another request's, or
        # nothing yet: it must not reach the sums even as 0 * nan
        vf = jnp.where(seen, vf, 0.0)
        for g in range(G):
            s = jnp.sum(kf * q_ref[b, g][None], axis=-1, keepdims=True)
            s = jnp.where(seen, s, NEG_INF)             # [block, KV, 1]
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])
            shrink = jnp.exp(m_prev - m_new)            # [KV, 1]
            l_ref[g] = l_ref[g] * shrink + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * shrink + jnp.sum(p * vf, axis=0)
            m_ref[g] = m_new

        @pl.when((j + 1) * block >= n)
        def _():
            o_ref[b] = acc_ref[...] / l_ref[...]

    jax.lax.fori_loop(0, total, step, None)


def length_aware_attention(q, kc, vc, layer, plan, interpret: bool = False):
    """Single-token attention of q [B, 1, H, hd] against layer `layer`
    of the stacked pools kc/vc [L, B, S, KV, hd], reading per row only
    the blocks `plan` (`work_list`) lists for it -> ctx [B, 1, H, hd]
    float32. The arithmetic of 'dense': the cache's K and V widened to
    float32 in VMEM, float32 scores, softmax statistics, probabilities
    and context — in blocks with a running softmax, so only the order
    of summation differs. The pools stay where they are (HBM; `layer`
    and the plan are scalar-prefetch operands, the kernel addresses
    [layer, row, block] itself): handed `layer_view` XLA would first
    copy the layer out of the scan's carry."""
    B, _, H, hd = q.shape
    KV = kc.shape[3]
    G = H // KV
    qf = (q.reshape(B, KV, G, hd).astype(jnp.float32)
          * jnp.float32(1.0 / math.sqrt(hd))).swapaxes(1, 2)
    whole = pl.BlockSpec((B, G, KV, hd), lambda i, *_: (0, 0, 0, 0))
    ctx = pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct((B, G, KV, hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, DECODE_BLOCK, KV, hd), kc.dtype),
                pltpu.VMEM((2, DECODE_BLOCK, KV, hd), vc.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((G, KV, 1), jnp.float32),
                pltpu.VMEM((G, KV, 1), jnp.float32),
                pltpu.VMEM((G, KV, hd), jnp.float32)]),
        name="decode_attention_live_blocks",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *plan, qf, kc, vc)
    return ctx.swapaxes(1, 2).reshape(B, 1, H, hd)


def _native_attention(q, kc, vc, pos, window):
    """cached_attention's impl 'native': operands in the cache dtype,
    float32 accumulation and softmax; `window` None is the plain
    position mask over a position-ordered cache, else the ring mask."""
    B, T, H, hd = q.shape
    S, KV = kc.shape[1], kc.shape[2]
    qg = q.astype(kc.dtype).reshape(B, T, KV, H // KV, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, kc,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    qpos = _query_positions(pos, B, T)                             # B,T
    if window is None:
        mask = jnp.arange(S, dtype=jnp.int32)[None, :] <= qpos[..., None]
    else:
        held = ring_positions(qpos, S)                             # B,T,S
        mask = (held >= 0) & (qpos[..., None] - held < window)
    s = jnp.where(mask[:, None, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgts,bskd->btkgd", p.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(B, T, H, hd)


_MASKED = -1e30     # finite: a block a row sees nothing of leaves no nan


def blocked_attention(q, k, v, window: int | None = None,
                      block: int = 512, q_offset=0):
    """Causal attention of a prompt's queries against the prompt's keys,
    in blocks: k [B, T, KV, hd] and v [B, T, KV, vd] at positions
    0..T-1 (vd = hd everywhere but under latent attention, whose
    decompressed keys are wider than its values), q [B, Tq, H, hd]
    at positions q_offset.. (a traced multiple of the block; the whole
    prompt by default) -> ctx [B, Tq, H, vd] in q's dtype; the scores
    are scaled by the q/k width. Query i sees
    key j iff j <= i and, with `window`, i - j < window. A block of query rows walks the key
    blocks it can see with a running softmax (float32 max, sum and
    accumulator; operands in their own dtype), so the scores of a 16k
    prompt never exist at once, and key blocks wholly ahead of the
    rows or wholly outside the window are never touched."""
    B, Tq, H, hd = q.shape
    T, KV, vd = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    bs = min(block, Tq)
    if Tq % bs or T % bs:
        raise ValueError(f"{Tq} queries over {T} keys are no whole "
                         f"blocks of {bs}")
    nb = Tq // bs
    first_block = q_offset // bs
    qg = q.astype(k.dtype).reshape(B, nb, bs, KV, G, hd)
    offs = jnp.arange(bs, dtype=jnp.int32)
    scale = 1.0 / math.sqrt(hd)

    def rows_of(i):
        qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
        i = i + first_block
        qpos = i * bs + offs

        def keys_of(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * bs, bs, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bs, bs, axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * bs + offs
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask, s, _MASKED)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            shrink = jnp.exp(m - m_new)
            l = l * shrink + p.sum(axis=-1)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        first = 0 if window is None else \
            jnp.maximum(i * bs - (window - 1), 0) // bs
        init = (jnp.full((B, KV, G, bs), _MASKED, jnp.float32),
                jnp.zeros((B, KV, G, bs), jnp.float32),
                jnp.zeros((B, KV, G, bs, vd), jnp.float32))
        _, l, acc = jax.lax.fori_loop(first, i + 1, keys_of, init)
        ctx = (acc / l[..., None]).astype(q.dtype)    # B,KV,G,bs,vd
        return jnp.transpose(ctx, (0, 3, 1, 2, 4))    # B,bs,KV,G,vd

    out = jax.lax.map(rows_of, jnp.arange(nb, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(B, Tq, H, vd)
