"""Fused multi-tick decode: K serving ticks inside ONE jitted lax.scan.

Reference analog: the inference decoder loops of
incubate/nn/layer/fused_transformer.py:1022 dispatch the device once
per generated token — a dispatch + host-sync tax per token. The repo's
microbenches already amortize dispatch by chaining work inside one jit
(tools/bench_util.py::chained_ms); this module puts the same
amortization in the PRODUCT path: the engine's decode dispatch becomes
a lax.scan of K single-tick bodies, so the engine pays one dispatch +
one host pull per K tokens.

Early exit: the non-spec tick has no host in the loop, so the scan
must decide ON DEVICE when a slot stops emitting. Each step threads an
`alive` mask through the carry and retires a slot when it (a) samples
its request's EOS id, (b) exhausts its max_new_tokens budget, (c)
crosses the engine's max_len position ceiling, or (d) trips the
in-jit isfinite quarantine — exactly the four host-side finish rules
(`ServingEngine._maybe_finish` + the poisoned path), so the device's
per-slot progression is bit-identical to what K separate host-mediated
ticks would have done. Retired rows keep computing (fixed shape) but
their writes route to the frozen position (dense — write-then-attend
masks the garbage exactly like inactive rows) or the scratch page
(paged, `oor_pos`), their columns pad with MT_PAD, and their
positions/gen indices freeze.

The pull grows from [N] to the [N, K] emission matrix (or
[N, K*(gamma+1)] when composed with speculative decode — the scan
body is then spec_decode._spec_core per step): column order is
emission order, MT_PAD (-2, the spec sentinel space: -1 stays the
quarantine verdict) marks "no token", and the host replays the
columns through the same `_emit_token` seam the spec path uses, so
exactly-once terminals, traces, and SLO samples all attribute K
tokens per pull.

Invariants preserved: `sampling` stays the only static flag (<= 2
decode traces — K, gamma, max_len are baked per engine, and the jit
cache key grows the K dim: engines with different K compile distinct
executables); per-slot PRNG streams fold (request id, gen index) per
step exactly like the single-tick path, so sampled streams are
bit-identical; donation and cache pinning are unchanged.

Selection: the engine's `multi_tick=` argument (K; 0 / "auto" is 1, the
single-tick shape).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .spec_decode import SPEC_PAD as MT_PAD   # same sentinel space

__all__ = ["MT_PAD", "resolve_multi_tick", "multi_tick_scan",
           "multi_tick_spec_scan"]


def resolve_multi_tick(knob=0) -> int:
    """The engine's `multi_tick=` argument as the ticks-per-dispatch K:
    0 / None / 'auto' is 1 (the single-tick shape), an int K >= 1 is
    itself, a negative one raises."""
    k = 0 if knob in (None, "auto") else int(knob)
    if k < 0:
        raise ValueError(f"multi_tick must be >= 0 (0 = auto); got {knob}")
    return max(k, 1)


# ---------------------------------------------------------- scan bodies
def multi_tick_scan(params, cache, state, base_key, poison, eos_ids,
                    max_new, *, fwd, cfg, max_top_k, sampling, guard,
                    k_ticks, max_len, oor_pos=None, cache_pin=None,
                    tele=False):
    """K fused non-spec decode ticks (the multi-tick replacement for
    serving._decode_tick — same state tuple / donation / static
    `sampling` flag). `eos_ids` [N] int32 (-1 = no EOS check) and
    `max_new` [N] int32 are the per-slot early-exit inputs the host
    uploads alongside the dirty state rebuild; `max_len` is the baked
    position ceiling. Returns the [N, K] emission matrix (column j =
    the token step j emitted, -1 the quarantine verdict, MT_PAD after
    a slot retires), the updated cache, and the advanced state."""
    from .serving import _pin_cache, _sample, _slot_keys

    toks, positions, active, temps, top_ks, req_ids, gen_idx = state

    def step(carry, _):
        cur, pos, gi, alive, cache = carry
        # retired/inactive rows: frozen position (dense; write-then-
        # attend masks the garbage like single-tick inactive rows) or
        # the scratch page (paged)
        fpos = pos if oor_pos is None else jnp.where(alive, pos, oor_pos)
        logits, cache = fwd(params, cur[:, None], cache, fpos, cfg)
        lg = logits[:, 0].astype(jnp.float32)
        if guard:
            lg = lg * poison[:, None]
        if sampling:
            keys = _slot_keys(base_key, req_ids, gi)
            nxt = _sample(lg, temps, top_ks, keys, max_top_k)
        else:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        nxt = jnp.where(alive, nxt, 0).astype(jnp.int32)
        bad = jnp.zeros_like(alive)
        if guard:
            row_ok = jnp.all(jnp.isfinite(lg), axis=-1)
            bad = alive & ~row_ok
            nxt = jnp.where(bad, -1, nxt)
        col = jnp.where(alive, nxt, MT_PAD)
        inc = alive.astype(jnp.int32)
        pos2, gi2 = pos + inc, gi + inc
        # device-side finish rules, mirroring _maybe_finish + the
        # poisoned path: EOS / length budget / position ceiling /
        # quarantine all retire the row for the rest of the scan
        dead = (bad | ((eos_ids >= 0) & (nxt == eos_ids))
                | (gi2 >= max_new) | (pos2 >= max_len))
        cur2 = jnp.where(alive, nxt, cur)
        if not tele:
            return (cur2, pos2, gi2, alive & ~dead, cache), col
        from ..kernels.decode_attention import attended_tokens
        from ..profiler.serving_telemetry import pack_tick_fields
        trow = pack_tick_fields(
            tokens=jnp.sum(alive & ~bad), active=jnp.sum(alive),
            poisoned=jnp.sum(bad),
            attended=attended_tokens(pos, alive))
        return (cur2, pos2, gi2, alive & ~dead, cache), (col, trow)

    carry0 = (toks, positions, gen_idx, active, cache)
    carry, ys = jax.lax.scan(step, carry0, None, length=k_ticks)
    cur, pos, gi, _alive, cache = carry
    # `active` stays the HOST-owned mask (single-tick contract): the
    # host mirrors the retirements itself via _finish/_clear_slot
    new_state = (cur, pos, active, temps, top_ks, req_ids, gi)
    if not tele:
        return ys.T, _pin_cache(cache, cache_pin), new_state
    cols, trows = ys
    # one TICK_FIELDS row per DISPATCH: counts sum over the K steps;
    # `active` (index 1) reports the slots alive at dispatch start,
    # not slot-steps
    trow = trows.sum(axis=0).at[1].set(trows[0, 1])
    return cols.T, trow, _pin_cache(cache, cache_pin), new_state


def multi_tick_spec_scan(params, cache, state, base_key, poison,
                         draft_poison, eos_ids, max_new, *, fwd, cfg,
                         max_top_k, sampling, guard, gamma, draft_layers,
                         k_ticks, max_len, oor_pos=None, cache_pin=None,
                         tele=False):
    """K fused speculative rounds: lax.scan over spec_decode._spec_core
    with the same alive-mask early exit as multi_tick_scan — a slot
    retires when any token it actually emitted in a block is its EOS,
    when the block's advance exhausts its budget or crosses max_len,
    or when the quarantine flags column 0. Returns the
    [N, K*(gamma+1)] emission matrix (K blocks of gamma+1 columns; a
    retired slot's later blocks are all MT_PAD, which is the host's
    stop marker), the updated cache, and the advanced state."""
    from .serving import _pin_cache
    from .spec_decode import _spec_core

    toks, positions, active, temps, top_ks, req_ids, gen_idx = state
    n = toks.shape[0]
    cols_idx = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]

    def step(carry, _):
        cur, pos, gi, alive, cache = carry
        emit, cache, new_tok, adv, m = _spec_core(
            params, cache, cur, pos, alive, temps, top_ks, req_ids, gi,
            base_key, poison, draft_poison, fwd=fwd, cfg=cfg,
            max_top_k=max_top_k, sampling=sampling, guard=guard,
            gamma=gamma, draft_layers=draft_layers, oor_pos=oor_pos)
        # dead rows emit a full-PAD block (the core pads cols >= 1 but
        # parks 0 in column 0 for inactive rows; the host needs PAD
        # there to know the slot retired in an earlier block)
        block = jnp.where(alive[:, None], emit, MT_PAD)
        pos2, gi2 = pos + adv, gi + adv
        flagged = alive & (emit[:, 0] < 0)
        emitted = (cols_idx <= m[:, None]) & alive[:, None]
        hit_eos = jnp.any(emitted & (eos_ids[:, None] >= 0)
                          & (emit == eos_ids[:, None]), axis=1)
        dead = (flagged | hit_eos | (gi2 >= max_new)
                | (pos2 >= max_len))
        cur2 = jnp.where(alive, new_tok, cur)
        if not tele:
            return (cur2, pos2, gi2, alive & ~dead, cache), block
        from ..kernels.decode_attention import attended_tokens
        from ..profiler.serving_telemetry import pack_tick_fields
        greedy = (alive & (temps <= 0.0)) if sampling else alive
        trow = pack_tick_fields(
            tokens=jnp.sum(jnp.where(alive & ~flagged, adv, 0)),
            active=jnp.sum(alive),
            poisoned=jnp.sum(flagged),
            attended=attended_tokens(pos, alive),
            spec_proposed=gamma * jnp.sum(greedy),
            spec_accepted=jnp.sum(jnp.where(greedy & ~flagged, m, 0)))
        return (cur2, pos2, gi2, alive & ~dead, cache), (block, trow)

    carry0 = (toks, positions, gen_idx, active, cache)
    carry, ys = jax.lax.scan(step, carry0, None, length=k_ticks)
    cur, pos, gi, _alive, cache = carry
    new_state = (cur, pos, active, temps, top_ks, req_ids, gi)
    if not tele:
        blocks = ys
        emit = jnp.transpose(blocks, (1, 0, 2)).reshape(n, -1)
        return emit, _pin_cache(cache, cache_pin), new_state
    blocks, trows = ys
    emit = jnp.transpose(blocks, (1, 0, 2)).reshape(n, -1)
    trow = trows.sum(axis=0).at[1].set(trows[0, 1])
    return emit, trow, _pin_cache(cache, cache_pin), new_state
