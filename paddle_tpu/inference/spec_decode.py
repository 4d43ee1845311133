"""Speculative decoding inside the serving tick: self-draft propose +
one-pass verify (Leviathan et al. 2023, "Fast Inference from
Transformers via Speculative Decoding"; Chen et al. 2023,
"Accelerating LLM Decoding with Speculative Sampling").

Reference analog: the inference decoder loops of
incubate/nn/layer/fused_transformer.py:1022 emit ONE token per full
forward — the latency wall PR 4's serving tick inherited. Here each
tick runs a cheap DRAFT pass that proposes `gamma` tokens and ONE
full-depth VERIFY pass that scores all gamma+1 positions, so a tick
emits between 1 and gamma+1 tokens while every emitted token is still
the TARGET model's token (bit-identical greedy streams — the property
every kernel in this repo ships behind).

Self-draft (the default and only built-in draft): the first
`draft_layers` layers of the existing stacked lax.scan, sharing the
target's params AND its KV cache/pages — the stacked-params layout
makes truncated depth a static slice (`forward_cached(...,
layers=K)`), and the draft needs no cache of its own because the
verify pass rewrites every drafted position at full depth anyway. The
draft's working cache is a throwaway first-K-layers view, discarded at
the end of the tick (a separate small draft model would need its own
prefill/cache lifecycle; the seam is `draft_layers` — depth IS the
draft-quality knob here).

The whole propose+verify runs as ONE jitted tick (`spec_tick`) with
the same state tuple, donation, and trace ceiling as the non-spec
`_decode_tick`, preserving the PR 4-6 invariants:

- ONE host pull per tick — the pull is the [N, gamma+1] emission
  matrix instead of an [N] vector; column 0 is always a real token
  (or the -1 quarantine sentinel), accepted tokens follow, and PAD
  (-2) fills the rest, so the host derives the per-slot acceptance
  count with no extra download.
- zero recompiles after warmup — gamma/draft_layers are baked per
  engine; `sampling` stays the only static flag (<= 2 traces).
- exactly-once — host bookkeeping mirrors the device advance
  (positions += accepted+1) and the quarantine/finish paths reuse the
  non-spec seams unchanged.

Correctness of greedy acceptance (why emitted streams are
bit-identical to non-spec decode): the verify pass writes K/V for all
gamma+1 positions BEFORE attending (kernels/decode_attention.py write-
then-attend order), and the position mask admits cache slots <= the
query's own position only, so verify row i sees exactly the cache the
incremental path would have — including nothing of rows > i. Every
emitted token is `argmax` of a verify row whose input prefix matched
the true stream, i.e. exactly the token the one-token-per-tick path
would have produced. Rejected rows' K/V is stale garbage past the new
position: masked until the next tick's writes overwrite it in order
(dense), or rolled back page-by-page by the engine (paged — see
ServingEngine._rollback_spec_pages).

Mixed spec/non-spec batches: sampled slots (temperature > 0) ride the
SAME tick — their token samples from verify row 0 (the exact logits
the non-spec tick computes, under the same fold_in PRNG stream) and
their acceptance is forced to 0, so greedy slots speculate while
sampled slots advance one reproducible token. Rejection-sampled
multi-token speculation for temperature > 0 is deliberately out of
scope: greedy acceptance is exact and bit-verifiable; a sampled
acceptance rule would change sampled streams vs the non-spec engine.

Draft-failure degradation: a non-finite draft logit row forces that
slot's acceptance to 0 — the slot degrades to non-spec decode for the
tick (verify row 0 is still the target's own healthy logits). Only
TARGET-model non-finite logits quarantine (the -1 sentinel), and only
over rows the slot actually emits. `testing/faults.py draft_nan`
injects the draft lane; tools/chaos_serving.py asserts the degrade.

Selection: the engine's `spec_decode=` argument ("auto" | "off" |
"spec"; auto is off). At run time `ServingEngine.set_spec_drafts` (the
brownout controller's lever) turns the drafts of a spec-built engine off
and back on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["SPEC_PAD", "resolve_spec", "spec_tick"]

# emission-matrix pad sentinel: -1 is the quarantine verdict, real ids
# are never negative — -2 marks "no token emitted in this column"
SPEC_PAD = -2


def resolve_spec(knob: str) -> bool:
    """The engine's `spec_decode=` argument ('auto' | 'off' | 'spec') as
    a bool; anything else raises. 'auto' is off."""
    if knob not in ("auto", "off", "spec"):
        raise ValueError(f"spec_decode {knob!r} (auto|off|spec)")
    return knob == "spec"


def _spec_core(params, cache, toks, positions, active, temps, top_ks,
               req_ids, gen_idx, base_key, poison, draft_poison, *,
               fwd, cfg, max_top_k, sampling, guard, gamma, draft_layers,
               oor_pos=None):
    """One propose+verify round over explicit per-slot arrays — the
    body `spec_tick` wraps for the single-dispatch path and
    inference/multi_tick.py scans K times with an early-exit alive
    mask threaded through `active`. Returns (emit [N, gamma+1], cache,
    new_tok [N], adv [N], m [N]): the emission matrix, the rewritten
    cache, the last accepted token, the per-slot position/gen advance
    (m + 1 for active rows, 0 otherwise), and the raw acceptance
    count."""
    from .serving import _sample, _slot_keys
    from ..models.decode import greedy_accept

    n = toks.shape[0]

    # ---- draft: gamma greedy steps through the first draft_layers
    # layers on a THROWAWAY view of the cache (the verify pass is the
    # only authoritative writer; the view exists so draft step i+1 can
    # attend draft step i's K/V within this tick)
    dcache = {"k": cache["k"][:draft_layers],
              "v": cache["v"][:draft_layers]}
    if "pt" in cache:
        dcache["pt"] = cache["pt"]
    d_tok = toks
    draft_cols = []
    draft_ok = jnp.ones((n,), bool)
    for i in range(gamma):
        dpos = positions + i
        fpos = (dpos if oor_pos is None
                else jnp.where(active, dpos, oor_pos))
        lg_d, dcache = fwd(params, d_tok[:, None], dcache, fpos, cfg,
                           layers=draft_layers)
        row = lg_d[:, 0].astype(jnp.float32) * draft_poison[:, None]
        draft_ok &= jnp.all(jnp.isfinite(row), axis=-1)
        d_tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
        draft_cols.append(d_tok)
    del dcache                                # discarded by design
    draft = jnp.stack(draft_cols, axis=1)     # [N, gamma]

    # ---- verify: ONE full-depth pass over [cur, d1..dgamma]; its
    # writes land at positions pos..pos+gamma through the same
    # write-then-attend seam as prefill, so row i attends exactly the
    # incremental path's cache (the position mask zeroes rows > i)
    vt = jnp.concatenate([toks[:, None], draft], axis=1)
    fpos = (positions if oor_pos is None
            else jnp.where(active, positions, oor_pos))
    logits, cache = fwd(params, vt, cache, fpos, cfg)
    lg = logits.astype(jnp.float32)           # [N, gamma+1, V]
    if guard:
        lg = lg * poison[:, None, None]
    tgt = jnp.argmax(lg, axis=-1).astype(jnp.int32)   # [N, gamma+1]

    # ---- acceptance: leading drafts matching the target's argmax;
    # a poisoned draft degrades to 0 (non-spec for this tick)
    m = greedy_accept(draft, tgt)
    m = jnp.where(draft_ok, m, 0)
    if sampling:
        # sampled slots take verify row 0 — the exact logits (and the
        # exact fold_in key stream) of the non-spec tick — and never
        # accept drafts, so their streams stay bit-identical
        keys = _slot_keys(base_key, req_ids, gen_idx)
        first = _sample(lg[:, 0], temps, top_ks, keys, max_top_k)
        m = jnp.where(temps > 0.0, 0, m)
        emit0 = jnp.where(temps > 0.0, first, tgt[:, 0]).astype(jnp.int32)
    else:
        emit0 = tgt[:, 0]
    cols = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
    emit = jnp.where(cols <= m[:, None], tgt, SPEC_PAD)
    emit = emit.at[:, 0].set(jnp.where(active, emit0, 0))
    emit = jnp.where(active[:, None] | (cols == 0), emit, SPEC_PAD)
    if guard:
        # quarantine ONLY over rows the slot emits: rejected drafts'
        # rows may hold garbage-token logits and must not evict
        row_ok = jnp.all(jnp.isfinite(lg), axis=-1)   # [N, gamma+1]
        bad = jnp.any(~row_ok & (cols <= m[:, None]), axis=1)
        emit = emit.at[:, 0].set(
            jnp.where(active & bad, -1, emit[:, 0]))

    adv = jnp.where(active, m + 1, 0).astype(jnp.int32)
    last = jnp.take_along_axis(emit, m[:, None], axis=1)[:, 0]
    new_tok = jnp.where(active, last, toks).astype(jnp.int32)
    return emit, cache, new_tok, adv, m


def spec_tick(params, cache, state, base_key, poison, draft_poison, *,
              fwd, cfg, max_top_k, sampling, guard, gamma, draft_layers,
              oor_pos=None, cache_pin=None, tele=False):
    """THE speculative mixed step (the spec-mode replacement for
    serving._decode_tick, same state tuple / donation / static
    `sampling` flag). Per active slot: gamma truncated-depth draft
    steps propose tokens, one full-depth verify pass scores all
    gamma+1 positions, and the greedy acceptance rule
    (models/decode.greedy_accept) picks how many to emit. Returns the
    [N, gamma+1] emission matrix (column 0 = the always-emitted token
    or the -1 quarantine sentinel; SPEC_PAD beyond the accepted
    prefix), the updated cache, and the advanced state. The math
    lives in `_spec_core` so the fused multi-tick scan
    (inference/multi_tick.py) can run the same round K times per
    dispatch with an early-exit mask.

    `draft_poison` [N] is the draft-lane fault multiplier (all-ones in
    production; testing.faults draft_nan sets one lane to nan INSIDE
    the jit): a non-finite draft row forces acceptance 0 — the slot
    degrades to non-spec decode, never quarantine, because verify row
    0 is the target's own logits. `poison` is the TARGET lane, handled
    exactly as in the non-spec tick.

    Tensor-parallel serving (ServingEngine mesh=): the draft's
    first-K-layers throwaway cache view inherits the pool's head
    sharding (a leading-axis slice never moves the KV-head axis), the
    verify pass writes through the same sharded seam, and `cache_pin`
    pins the returned pool leaves to their input NamedShardings
    exactly like the non-spec tick (serving._pin_cache) — donation
    aliases, zero recompiles, still one [N, gamma+1] pull per mesh."""
    from .serving import _pin_cache

    toks, positions, active, temps, top_ks, req_ids, gen_idx = state
    emit, cache, new_tok, adv, m = _spec_core(
        params, cache, toks, positions, active, temps, top_ks, req_ids,
        gen_idx, base_key, poison, draft_poison, fwd=fwd, cfg=cfg,
        max_top_k=max_top_k, sampling=sampling, guard=guard, gamma=gamma,
        draft_layers=draft_layers, oor_pos=oor_pos)
    new_state = (new_tok, positions + adv, active, temps, top_ks,
                 req_ids, gen_idx + adv)
    if not tele:
        return emit, _pin_cache(cache, cache_pin), new_state
    # in-tick telemetry row riding the emission-matrix pull (zero extra
    # transfers — profiler/serving_telemetry). DEVICE-side truth: a
    # mid-block host finish may drop tail tokens from the stream, but
    # the device did the work these fields price. Proposed counts
    # greedy slots only (sampled slots never speculate — same rule as
    # the host acceptance ledger); accepted sums the kept drafts.
    from ..kernels.decode_attention import attended_tokens
    from ..profiler.serving_telemetry import pack_tick_fields
    flagged = active & (emit[:, 0] < 0)
    greedy = (active & (temps <= 0.0)) if sampling else active
    trow = pack_tick_fields(
        tokens=jnp.sum(jnp.where(active & ~flagged, adv, 0)),
        active=jnp.sum(active),
        poisoned=jnp.sum(flagged),
        attended=attended_tokens(positions, active),
        spec_proposed=gamma * jnp.sum(greedy),
        spec_accepted=jnp.sum(jnp.where(greedy & ~flagged, m, 0)))
    return emit, trow, _pin_cache(cache, cache_pin), new_state
