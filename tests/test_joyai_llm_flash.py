"""The joyai_llm_flash family (JoyAI-LLM-Flash) against its plain reference
(benchmark/reference/joyai_llm_flash.py, which imports nothing of the
program), at small sizes on the CPU in float32 with seeded weights: the two
attention paths over the latent pools, `noaux_tc` routing and the share, the
multi-token-prediction module, the counts on the spans, and the options the
family refuses.

TOLERANCE. Logits here are O(1) and everything is float32. The program and
the reference sum in different orders (a running softmax over key blocks,
the absorbed contraction over the latent instead of per-head keys, grouped
matmuls over sorted rows), which moves a logit by a few 1e-6; 3e-5 leaves
room for that and none for a fault: the selection bias or the scaling
factor left out, split-half RoPE, RoPE on the un-rotated part or an
un-normalised latent each move logits by 1e-2 and more at these sizes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai_llm_flash as ref
from paddle_tpu.inference.router import create_router
from paddle_tpu.inference.serving import (REFUSABLE, ServingEngine,
                                          UnsupportedOptionError,
                                          family_for)
from paddle_tpu.kernels import decode_attention
from paddle_tpu.kernels import latent_attention as la
from paddle_tpu.models import joyai_llm_flash as m
from paddle_tpu.parallel import moe
from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans

TOL = 3e-5
GEN = 10
FAMILY = "joyai_llm_flash"


def make_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, ffn_hidden=48,
                moe_ffn_hidden=24, first_k_dense_replace=1,
                n_routed_experts=16, experts_held=4, first_expert=4,
                num_experts_per_tok=4, max_seq_len=64, rope_theta=10000.0,
                dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    base.update(kw)
    return m.JoyaiLlmFlashConfig(**base)


def make_params(cfg, seed=0):
    """Seeded weights, the matmuls scaled up so that logits are O(1) and
    the router's scores spread."""
    params = m.init_joyai_llm_flash_params(cfg, jax.random.PRNGKey(seed))
    return {k: v * 6.0 if k.endswith("_w") else v
            for k, v in params.items()}


def arch_of(cfg):
    return dict(num_layers=cfg.num_layers,
                first_k_dense_replace=cfg.first_k_dense_replace,
                num_heads=cfg.num_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, layer_norm_eps=cfg.rms_norm_eps,
                num_experts_per_tok=cfg.num_experts_per_tok,
                first_expert=cfg.first_expert,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor)


@pytest.fixture(scope="module")
def setup():
    cfg = make_cfg()
    return cfg, make_params(cfg)


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _bucket(n):
    b = 8
    while b < n:
        b *= 2
    return b


class _Static:
    """A hashable wrapper so that a config can be a static jit argument
    (the engine closes over it instead)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def __hash__(self):
        return hash(repr(self.cfg))

    def __eq__(self, other):
        return repr(self.cfg) == repr(other.cfg)

    def __getattr__(self, name):
        return getattr(self.cfg, name)


_prefill = jax.jit(m.prefill_into_slot, static_argnums=5)
_step = jax.jit(m.joyai_llm_flash_forward_cached, static_argnums=4)


@functools.partial(jax.jit, static_argnums=1)
def _whole_prompt(params, cfg, tokens):
    """Prompts from position 0 into an empty cache of their own length ->
    their logits."""
    return m.joyai_llm_flash_forward_cached(
        params, tokens, m.init_cache(cfg, *tokens.shape), 0, cfg)[0]


@functools.lru_cache(maxsize=None)
def _reference(arch_items):
    arch = dict(arch_items)
    return jax.jit(lambda params, seq: ref.forward(params, seq, arch))


def reference_logits(cfg, params, seq):
    """The reference's logits [len(seq), V], one program a model."""
    fn = _reference(tuple(sorted(arch_of(cfg).items())))
    return np.asarray(fn(params, jnp.asarray(seq)))


def prefill(cfg, params, cache, slot, prompt, pad_with=0):
    padded = np.full((1, _bucket(len(prompt))), pad_with, np.int32)
    padded[0, :len(prompt)] = prompt
    return _prefill(params, cache, jnp.asarray(padded),
                    jnp.int32(len(prompt)), jnp.int32(slot), _Static(cfg))


def serve_logits(cfg, params, cache, slot, prompt, forced, others=None):
    """Prefill `prompt` into `slot` of the pools, then decode with `forced`
    fed back token by token (teacher forcing) through the per-row-position
    tick -> (logits at the last prompt position and after each forced
    token, the pools). `others` {slot: (position, token)} are rows decoding
    beside it; every other row is idle."""
    slots = cache["ckv"].shape[1]
    first, cache = prefill(cfg, params, cache, slot, prompt)
    rows = [np.asarray(first[0])]
    pos = np.zeros(slots, np.int32)
    toks = np.zeros((slots, 1), np.int32)
    live = np.zeros((slots, 1), bool)
    live[slot] = True
    for s, (p, t) in (others or {}).items():
        pos[s], toks[s, 0], live[s] = p, t, True
    for i, tok in enumerate(forced):
        pos[slot], toks[slot, 0] = len(prompt) + i, tok
        logits, cache = _step(params, jnp.asarray(toks), cache,
                              jnp.asarray(pos), _Static(cfg),
                              jnp.asarray(live))
        rows.append(np.asarray(logits[slot, 0]))
        for s in (others or {}):
            pos[s] += 1
    return np.stack(rows), cache


def reference_rows(cfg, params, prompt, forced):
    seq = np.concatenate([prompt, forced]).astype(np.int32)
    return reference_logits(cfg, params, seq)[len(prompt) - 1:]


# ------------------------------------------------------- parity, logits
@pytest.mark.parametrize("length", [8, 16, 32])
def test_the_prompt_path_matches_the_reference(setup, length):
    """(a) a whole prompt through the decompressed path, every position's
    logits: one, two and four chunks of the FFN, one block of attention."""
    cfg, params = setup
    tokens = _tokens(length, 3)
    got = _whole_prompt(params, _Static(cfg), jnp.asarray(tokens[None]))
    want = reference_logits(cfg, params, tokens)
    assert np.abs(want).max() > 0.3            # logits worth comparing
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL, rtol=0)


@pytest.mark.parametrize("prompt_len", [5, 16, 21])
def test_prefill_then_ticks_match_the_reference(setup, prompt_len):
    """(b) the bucketed prefill into a slot, then 10 ticks of the absorbed
    path through the latent pool, against the reference's one full
    decompressed forward."""
    cfg, params = setup
    prompt, forced = _tokens(prompt_len, 1), _tokens(GEN, 2)
    got, cache = serve_logits(cfg, params, m.init_cache(cfg, 3, 64), 1,
                              prompt, forced)
    want = reference_rows(cfg, params, prompt, forced)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert cache["ckv"].shape == (4, 3, 64, 16)
    assert cache["kpe"].shape == (4, 3, 64, 4)         # no head axis


@pytest.mark.parametrize("dense,layers", [(0, 2), (2, 3), (1, 2)])
def test_other_stacks_match_the_reference(dense, layers):
    """No dense layer, two of them before one expert layer, and the
    shortest stack that has both kinds (one scan a kind, or the body
    itself where a kind has one layer)."""
    cfg = make_cfg(num_layers=layers, first_k_dense_replace=dense)
    params = make_params(cfg, 3)
    prompt, forced = _tokens(13, 4), _tokens(4, 5)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, 64), 0, prompt,
                          forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


def test_the_uncut_model_matches_the_uncut_reference():
    cfg = make_cfg(experts_held=None, first_expert=0)
    params = make_params(cfg, 6)
    assert params["exp_gate_w"].shape[1] == cfg.n_routed_experts
    prompt, forced = _tokens(11, 7), _tokens(4, 8)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, 64), 1, prompt,
                          forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


def test_absorbed_equals_decompressed_on_the_same_cache(setup):
    """(c) one query a row against the same latent rows: the absorbed form
    (W_kvb's halves on the query and the output, attention over the latent)
    and the textbook form over decompressed per-head keys and values agree
    to float32 round-off."""
    cfg, params = setup
    H, C = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rng = np.random.default_rng(11)
    B, S = 3, 24
    q_nope = jnp.asarray(rng.standard_normal((B, H, dn)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((B, H, dr)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    kpe = jnp.asarray(rng.standard_normal((B, S, dr)), jnp.float32)
    pos = jnp.asarray([0, 7, 23], jnp.int32)
    lp = {k: params[k][2] for k in ("k_b_w", "v_b_w")}
    got = np.asarray(m._absorbed(lp, q_nope, q_pe, ckv[None], kpe[None], 0,
                                 pos, None, cfg))
    hi = jax.lax.Precision.HIGHEST
    k_nope = jnp.einsum("bsc,ch->bsh", ckv, lp["k_b_w"],
                        precision=hi).reshape(B, S, H, dn)
    v = jnp.einsum("bsc,ch->bsh", ckv, lp["v_b_w"],
                   precision=hi).reshape(B, S, H, dv)
    s = (jnp.einsum("bhn,bshn->bhs", q_nope, k_nope, precision=hi)
         + jnp.einsum("bhr,bsr->bhs", q_pe, kpe, precision=hi)) \
        / math.sqrt(dn + dr)
    seen = jnp.arange(S)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    want = np.asarray(jnp.einsum("bhs,bshv->bhv", p, v, precision=hi))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)


def test_the_tick_and_the_prompt_path_agree_on_the_next_position(setup):
    """(c) again, through the model: position n of a prompt of n + 1 tokens
    (decompressed) and the tick after a prefill of n (absorbed)."""
    cfg, params = setup
    tokens = _tokens(16, 12)
    whole = _whole_prompt(params, _Static(cfg), jnp.asarray(tokens[None]))
    ticked, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, 64), 0,
                             tokens[:15], tokens[15:])
    np.testing.assert_allclose(ticked[1], np.asarray(whole[0, 15]),
                               atol=1e-5, rtol=0)


def test_a_run_that_continues_a_cache_is_refused(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="whole prompt"):
        m.joyai_llm_flash_forward_cached(
            params, jnp.zeros((1, 4), jnp.int32), m.init_cache(cfg, 1, 16),
            jnp.asarray([3], jnp.int32), cfg)
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        make_cfg(first_k_dense_replace=5)


# ---------------------------------------------------------------- routing
def test_the_bias_changes_the_choice_and_not_the_weights():
    """(d) `noaux_tc`: the k are chosen by score + bias, weighted by the
    scores alone, normalised, times the scaling factor."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    bias = jnp.asarray(0.5 * rng.standard_normal(16), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(logits))
    plain_c, plain_w = moe.sigmoid_topk(logits, 4)
    choice, weight = moe.sigmoid_topk(logits, 4, bias=bias, scale=2.5)
    choice, weight = np.asarray(choice), np.asarray(weight)
    changed = [set(a) != set(b) for a, b in zip(np.asarray(plain_c), choice)]
    assert np.mean(changed) > 0.5                  # the bias matters here
    order = np.argsort(-(scores + np.asarray(bias)), axis=-1,
                       kind="stable")[:, :4]
    np.testing.assert_array_equal(choice, order)
    picked = np.take_along_axis(scores, choice, axis=-1)
    np.testing.assert_allclose(
        weight, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weight.sum(-1), 2.5, rtol=1e-6)
    raw = np.asarray(moe.sigmoid_topk(logits, 4, normalize=False,
                                      bias=bias)[1])
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    # a zero bias and no scale is the plain selection, bit for bit
    c0, w0 = moe.sigmoid_topk(logits, 4, bias=jnp.zeros(16))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(plain_c))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(plain_w))


def test_routing_falls_as_the_references_ties_included(setup):
    """(d) the program's router against the reference's on the same rows,
    among them exact ties (equal logits, equal bias: the lower index
    wins)."""
    cfg, _ = setup
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    w = rng.standard_normal((8, 16)).astype(np.float32)
    w[:, 9] = w[:, 3]                              # experts 3 and 9 tie
    w[:, 12] = w[:, 5]
    bias = (0.3 * rng.standard_normal(16)).astype(np.float32)
    bias[9], bias[12] = bias[3], bias[5]
    arch = arch_of(cfg)
    want_c, want_w = ref.route(u, jnp.asarray(w), jnp.asarray(bias), arch)
    logits = jnp.dot(u, jnp.asarray(w), precision=jax.lax.Precision.HIGHEST)
    got_c, got_w = moe.sigmoid_topk(
        logits, cfg.num_experts_per_tok, bias=jnp.asarray(bias),
        scale=cfg.routed_scaling_factor)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-6)
    both = np.asarray(got_c)
    tied = [(3 in row) != (9 in row) for row in both]
    assert any(tied) and all(9 not in row or 3 in row for row in both)


@pytest.mark.parametrize("rows", [6, 80, 160])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """(e) the parts the 4 shares of 4 experts give, with the shared expert
    (which every chip computes alike) counted once, add up to the uncut
    reference's expert layer — in the dense form (6 and 80 rows) and the
    sorted one (160)."""
    whole = make_cfg(experts_held=None, first_expert=0)
    params = make_params(whole, 9)
    j = 1                                           # an expert layer
    h = jnp.asarray(np.random.default_rng(2).standard_normal((rows, 32)),
                    jnp.float32)
    p = {k: params[k][j] for k in ref.EXPERTS}
    want = np.asarray(ref._experts(h, p, arch_of(whole), "float32", True,
                                   True))
    shared = np.asarray(ref._mlp(h, p["shared_gate_w"], p["shared_up_w"],
                                 p["shared_down_w"], "float32"))
    total, loads = np.zeros_like(want), []
    for first in range(0, 16, 4):
        cfg = make_cfg(first_expert=first)
        part = dict(params)
        for name in ("exp_gate_w", "exp_up_w", "exp_down_w"):
            part[name] = params[name][:, first:first + 4]
        out, load = m._experts(part, j, h, jnp.ones((rows,), bool), cfg)
        total += np.asarray(out) - shared
        loads.append(np.asarray(load))
    assert np.concatenate(loads).sum() == rows * whole.num_experts_per_tok
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)


# ---------------------------------------------- pools, slots and batches
def test_padding_and_idle_rows_leave_every_pool_bit_identical(setup):
    """(f) whatever a prompt is padded with, and whatever an idle row holds
    as its token and position, no pool moves by a bit: padding lands as
    zeros, an idle row writes nothing."""
    cfg, params = setup
    prompt = _tokens(11, 13)
    cache = m.init_cache(cfg, 3, 64)
    a = prefill(cfg, params, cache, 1, prompt, pad_with=0)[1]
    b = prefill(cfg, params, cache, 1, prompt, pad_with=55)[1]
    for name in ("ckv", "kpe"):
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
        assert not np.asarray(a[name][:, 1, 11:]).any()    # padding: zeros
        assert np.asarray(a[name][:, 1, :11]).any()
        assert not np.asarray(a[name][:, [0, 2]]).any()    # other slots
    live = jnp.asarray([[False], [True], [False]])
    outs = []
    for idle_tok, idle_pos in ((0, 0), (71, 40)):
        toks = jnp.asarray([[idle_tok], [5], [idle_tok]], jnp.int32)
        pos = jnp.asarray([idle_pos, 11, idle_pos], jnp.int32)
        outs.append(_step(params, toks, a, pos, _Static(cfg), live))
    for name in ("ckv", "kpe"):
        got = np.asarray(outs[0][1][name])
        np.testing.assert_array_equal(got, np.asarray(outs[1][1][name]))
        np.testing.assert_array_equal(got[:, [0, 2]],
                                      np.asarray(a[name][:, [0, 2]]))
        assert got[:, 1, 11].any()                 # the live row's write
    np.testing.assert_array_equal(np.asarray(outs[0][0][1]),
                                  np.asarray(outs[1][0][1]))
    # counts leave the idle rows out: one live row, 3 expert layers x 4
    stats = m.span_counts(cfg, np.asarray(outs[0][1]["stats"]))
    assert 0 <= stats["expert_tokens"] <= 3 * 4
    assert stats["kv_positions_read"] == stats["kv_positions_pool"] \
        == 4 * 3 * 64
    assert stats["latent_bytes"] == 4 * 3 * 64 * (16 + 4) * 4


def test_a_reused_slot_reads_nothing_of_its_last_occupant(setup):
    cfg, params = setup
    long, short = _tokens(30, 14), _tokens(6, 15)
    forced = _tokens(5, 16)
    cache = m.init_cache(cfg, 2, 64)
    _, cache = serve_logits(cfg, params, cache, 1, long, _tokens(8, 17))
    got, _ = serve_logits(cfg, params, cache, 1, short, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, short, forced), atol=TOL, rtol=0)


@pytest.mark.parametrize("company", ["alone", "crowd"])
def test_a_stream_is_slot_and_batch_invariant(setup, company):
    """(f) a request's logits do not depend on its slot, on how many slots
    the pools have, or on who decodes beside it."""
    cfg, params = setup
    prompt, forced = _tokens(9, 18), _tokens(6, 19)
    base, _ = serve_logits(cfg, params, m.init_cache(cfg, 1, 64), 0, prompt,
                           forced)
    cache = m.init_cache(cfg, 4, 64)
    others = None
    if company == "crowd":
        _, cache = prefill(cfg, params, cache, 0, _tokens(20, 20))
        _, cache = prefill(cfg, params, cache, 3, _tokens(5, 21))
        others = {0: (20, 7), 3: (5, 9)}
    got, _ = serve_logits(cfg, params, cache, 2, prompt, forced, others)
    np.testing.assert_allclose(got, base, atol=1e-5, rtol=0)


# ------------------------------------------------ multi-token prediction
@functools.partial(jax.jit, static_argnums=4)
def _mtp(params, seqs, next_tokens, positions, cfg):
    hidden, _ = m._hidden(params, seqs, m.init_cache(cfg, *seqs.shape), 0,
                          cfg)
    return m.mtp_logits(params, hidden, next_tokens, positions, cfg)


def test_mtp_logits_match_the_reference(setup):
    """(g) the module over whole sequences, two of them, against the
    reference's one at a time; and it is a second head: the main logits do
    not move with it."""
    cfg, params = setup
    assert set(params) >= {"mtp_norm_e", "mtp_norm_h", "mtp_eh_w",
                           "mtp_norm_f", "mtp_q_a_w", "mtp_router_bias",
                           "mtp_exp_gate_w"}
    seqs = np.stack([_tokens(17, 22), _tokens(17, 23)])
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    got = np.asarray(_mtp(params, jnp.asarray(seqs[:, :16]),
                          jnp.asarray(seqs[:, 1:17]), positions,
                          _Static(cfg)))
    arch = arch_of(cfg)
    ref_mtp = jax.jit(lambda p, seq, nxt, positions: ref.mtp_logits(
        p, ref.hidden(p, seq, arch), nxt, positions, arch))
    for b in range(2):
        want = np.asarray(ref_mtp(params, jnp.asarray(seqs[b, :16]),
                                  jnp.asarray(seqs[b, 1:17]),
                                  jnp.arange(16)))
        assert np.abs(want).max() > 0.3
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)
    main = _serving_params(params)
    a = _whole_prompt(main, _Static(cfg), jnp.asarray(seqs[:1, :16]))
    b = _whole_prompt(params, _Static(cfg), jnp.asarray(seqs[:1, :16]))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------- what the other families' calls compute
def _old_sigmoid_topk(router_logits, k, normalize=True):
    """parallel/moe.sigmoid_topk as it stood before this family (66c46c9)."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    weight, choice = jax.lax.top_k(scores, k)
    if normalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return choice.astype(jnp.int32), weight


def _old_blocked_attention(q, k, v, window=None, block=512, q_offset=0):
    """kernels/decode_attention.blocked_attention as it stood before this
    family (66c46c9): one `hd` for q, k and v."""
    masked = -1e30
    B, Tq, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block, Tq)
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
            mx, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * bs, bs, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bs, bs, axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * bs + offs
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask, s, masked)
            m_new = jnp.maximum(mx, s.max(axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            shrink = jnp.exp(mx - m_new)
            l = l * shrink + p.sum(axis=-1)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        first = 0 if window is None else \
            jnp.maximum(i * bs - (window - 1), 0) // bs
        init = (jnp.full((B, KV, G, bs), masked, jnp.float32),
                jnp.zeros((B, KV, G, bs), jnp.float32),
                jnp.zeros((B, KV, G, bs, hd), jnp.float32))
        _, l, acc = jax.lax.fori_loop(first, i + 1, keys_of, init)
        ctx = (acc / l[..., None]).astype(q.dtype)
        return jnp.transpose(ctx, (0, 3, 1, 2, 4))

    out = jax.lax.map(rows_of, jnp.arange(nb, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(B, Tq, H, hd)


@pytest.mark.parametrize("normalize", [True, False])
def test_sigmoid_topk_computes_what_it_did_for_cohere2_moe(normalize):
    """(h) the call models/cohere2_moe.py makes — no bias, no scale — bit
    for bit, and as the same program."""
    logits = jnp.asarray(
        np.random.default_rng(3).standard_normal((200, 128)), jnp.float32)
    old = _old_sigmoid_topk(logits, 8, normalize)
    new = moe.sigmoid_topk(logits, 8, normalize)
    for a, b in zip(old, new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(lambda x: _old_sigmoid_topk(x, 8, normalize))(
        logits)) == str(jax.make_jaxpr(
            lambda x: moe.sigmoid_topk(x, 8, normalize))(logits))


@pytest.mark.parametrize("shape", [
    # (Tq, T, H, KV, hd, window, block, q_offset)
    (16, 32, 8, 2, 8, 12, 8, 16),       # cohere2_moe: a chunk, a window
    (32, 32, 8, 2, 8, None, 8, 0),      # cohere2_moe: a full layer
    (32, 32, 5, 1, 16, None, 16, 0),    # jamba: multi-query, whole prompt
], ids=["cohere2_window_chunk", "cohere2_full", "jamba_mqa"])
def test_blocked_attention_computes_what_it_did_for_the_others(shape):
    """(h) the calls models/cohere2_moe.py and models/jamba.py make — one
    width for q, k and v — bit for bit, and as the same program."""
    Tq, T, H, KV, hd, window, block, q_offset = shape
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, Tq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, T, KV, hd)), jnp.float32)
    kw = dict(window=window, block=block, q_offset=q_offset)
    old = _old_blocked_attention(q, k, v, **kw)
    new = decode_attention.blocked_attention(q, k, v, **kw)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    assert str(jax.make_jaxpr(
        lambda *a: _old_blocked_attention(*a, **kw))(q, k, v)) == str(
        jax.make_jaxpr(lambda *a: decode_attention.blocked_attention(
            *a, **kw))(q, k, v))


def test_blocked_attention_takes_a_value_width_of_its_own():
    """Latent attention's decompressed form: 12-wide q/k (scaled by 12),
    8-wide v, against dense scores."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 32, 4, 12)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 32, 4, 12)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 32, 4, 8)), jnp.float32)
    got = decode_attention.blocked_attention(q, k, v, block=8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(12)
    mask = jnp.arange(32)[None, :] <= jnp.arange(32)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    assert got.shape == (2, 32, 4, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_write_kv_takes_a_pool_with_no_head_axis():
    """Latent rows [B, T, W] into [L, B, S, W]: a scalar position (a
    prompt), per-row positions (the tick; past the end clamps, as for
    K/V), beside the K/V form unchanged."""
    pool = jnp.zeros((2, 3, 8, 4))
    rows = jnp.arange(3 * 2 * 4, dtype=jnp.float32).reshape(3, 2, 4) + 1
    out = np.asarray(decode_attention.write_kv(pool, rows, 5, 1))
    np.testing.assert_array_equal(out[1, :, 5:7], np.asarray(rows))
    assert not out[0].any() and not out[1, :, :5].any()
    out = np.asarray(decode_attention.write_kv(
        pool, rows[:, :1], jnp.asarray([0, 3, 99]), 0))
    for b, p in enumerate((0, 3, 7)):
        np.testing.assert_array_equal(out[0, b, p], np.asarray(rows[b, 0]))
    assert np.count_nonzero(out.any(-1)) == 3


# ----------------------------------------------------------- the engine
def _serve(router, prompts, max_new=GEN, **kw):
    reqs = [router.submit(p, max_new, **kw) for p in prompts]
    steps = 0
    while router.has_work():
        router.step()
        steps += 1
        assert steps < 500
    return reqs


def _serving_params(params):
    return {k: v for k, v in params.items() if not k.startswith("mtp_")}


def test_the_engine_serves_it_with_every_option_at_its_default(setup):
    """submit()/step() through create_router: bucketed prefill, the decode
    tick, slots reused (5 requests over 2 slots), greedy tokens equal to
    the reference's argmax wherever that is not a near-tie; with the
    multi-token-prediction module in the tree and without it."""
    cfg, params = setup
    router = create_router(_serving_params(params), cfg, replicas=1,
                           family=FAMILY, num_slots=2, max_len=64)
    eng = router.replicas[0].eng
    assert not eng.paged and not eng.spec and eng.mt_k == 1 \
        and not eng.quant and eng.mesh is None
    prompts = [_tokens(n, 20 + n) for n in (5, 21, 8, 3, 30)]
    reqs = _serve(router, prompts)
    for prompt, req in zip(prompts, reqs):
        assert req.finish_reason == "length" and len(req.tokens) == GEN
        rows = reference_rows(cfg, params, prompt, req.tokens[:-1])
        top2 = np.sort(rows, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-4
        assert clear.sum() >= GEN - 2
        assert (rows.argmax(-1) == np.asarray(req.tokens))[clear].all()
    ledger = eng.memory_ledger()
    assert ledger["kv_pool_device"] == sum(
        eng._cache[k].nbytes for k in ("ckv", "kpe")) \
        == 4 * 2 * 64 * (16 + 4) * 4
    with_mtp = create_router(params, cfg, replicas=1, family=FAMILY,
                             num_slots=2, max_len=64)
    again = _serve(with_mtp, prompts[:2])
    assert [r.tokens for r in again] == [r.tokens for r in reqs[:2]]
    # sampled decoding: a stream is its request's, whoever shares the tick
    alone = _serve(router, prompts[:1], temperature=0.8)[0]
    assert len(alone.tokens) == GEN and alone.tokens != reqs[0].tokens
    router.close()
    with_mtp.close()


def test_counts_ride_the_one_pull_onto_the_spans(setup):
    cfg, params = setup
    router = create_router(_serving_params(params), cfg, replicas=1,
                           family=FAMILY, num_slots=2, max_len=64)
    eng = router.replicas[0].eng
    _serve(router, [_tokens(21, 30)], max_new=3)         # warm
    pulls = [0]
    orig = eng._pull

    def counted(value, stall_s=0.0):
        pulls[0] += 1
        return orig(value, stall_s)
    eng._pull = counted
    clear_profiler_spans()
    _serve(router, [_tokens(21, 31), _tokens(5, 32)], max_new=4)
    spans = get_profiler_spans()
    ticks = [s for s in spans if s.name == "serving.decode_tick"]
    prefills = [s for s in spans if s.name == "serving.prefill"]
    assert ticks and len(prefills) == 2
    assert pulls[0] == len(ticks) + len(prefills)        # one pull each
    k, expert_layers = cfg.num_experts_per_tok, cfg.expert_layers
    for s in prefills:
        c = s.counts
        assert 0 < c["expert_tokens"] <= c["true_len"] * k * expert_layers
        assert 0 < c["expert_max_load"] <= c["true_len"]
        assert "kv_positions_read" not in c              # a tick's counts
        b = c["bucket"]
        assert c["attn_pairs"] == cfg.num_layers * b * (b + 1) // 2
        assert c["attn_pairs_kernel"] == 0
    for s in ticks:
        c = s.counts
        assert c["expert_tokens"] <= c["active"] * k * expert_layers
        assert c["expert_max_load"] <= c["active"]
        # the einsums read every position of every slot: the honest 100%
        # (where the kernel runs, `kv_read_layer` is its work list's
        # blocks: tests/test_mla_absorbed_kernel.py)
        assert c["kv_positions_read"] == c["kv_positions_pool"] \
            == cfg.num_layers * 2 * 64
        assert c["latent_bytes"] == c["kv_positions_read"] * 20 * 4
    router.close()


def _as_tpu(monkeypatch):
    """The tick as it decides on the chip, its kernel in the interpreter
    (a test steers what `is_tpu()` answers; the program has no option)."""
    import paddle_tpu.device as device
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(m, "absorbed_attention_live_blocks",
                        functools.partial(la.absorbed_attention_live_blocks,
                                          interpret=True))


def test_kv_read_layer_is_the_work_lists_blocks_where_the_kernel_runs(
        monkeypatch):
    """`kv_read_layer` of the "stats" leaf: slots x positions under the
    einsums, the work list's `total x block` under the kernel — from
    `live` and the positions, an idle row nothing —, `kv_pool_layer`
    slots x positions either way; `span_counts` multiplies by the layers
    and the bytes of a position."""
    block = la.LATENT_BLOCK
    cfg = make_cfg(kv_lora_rank=128, num_layers=2, max_seq_len=2 * block)
    params = _serving_params(make_params(cfg))
    cache = m.init_cache(cfg, 4, 2 * block)
    pos = jnp.asarray([3, block - 1, block, 9], jnp.int32)
    live = jnp.asarray([True, True, True, False])[:, None]
    toks = jnp.ones((4, 1), jnp.int32)

    def tick():             # traced anew: is_tpu() decides what it takes
        forward = functools.partial(m.joyai_llm_flash_forward_cached, cfg=cfg)
        return jax.jit(forward)(params, toks, cache, pos, live=live)[1]
    plain = tick()
    assert [int(v) for v in plain["stats"][2:]] == [8 * block, 8 * block]
    _as_tpu(monkeypatch)
    cache = tick()
    assert [int(v) for v in cache["stats"][2:]] == [4 * block, 8 * block]
    counts = m.span_counts(cfg, cache["stats"])
    assert counts["kv_positions_read"] == 2 * 4 * block
    assert counts["kv_positions_pool"] == 2 * 8 * block
    assert counts["latent_bytes"] == 2 * 4 * block * (128 + 4) * 4


def test_the_named_scopes_are_in_both_programs(setup):
    """`mla_prefill` in a prompt's program, `mla_absorbed` in the tick's,
    the three of the expert layer in both: what groups
    `breakdown.device_ops` by hand."""
    cfg, params = setup
    tick = jax.jit(lambda p, t, c, pos: m.joyai_llm_flash_forward_cached(
        p, t, c, pos, cfg)).lower(
        params, jnp.zeros((2, 1), jnp.int32), m.init_cache(cfg, 2, 16),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    prompt = jax.jit(lambda p, t, c: m.joyai_llm_flash_forward_cached(
        p, t, c, 0, cfg)).lower(
        params, jnp.zeros((1, 16), jnp.int32),
        m.init_cache(cfg, 1, 16)).as_text(debug_info=True)
    for scope in ("moe_router", "moe_experts", "shared_expert", "lm_head"):
        assert scope in tick and scope in prompt, scope
    assert "mla_absorbed" in tick and "mla_prefill" not in tick
    assert "mla_prefill" in prompt and "mla_absorbed" not in prompt


def test_the_kernel_sits_in_the_ticks_own_scope(monkeypatch):
    """Where the tick's attention is the Pallas kernel it keeps the
    einsums' scope: `mla_absorbed/mla_absorbed_live_blocks`."""
    _as_tpu(monkeypatch)
    cfg = make_cfg(kv_lora_rank=128, num_layers=2)
    params = _serving_params(make_params(cfg))
    tick = jax.jit(lambda p, t, c, pos: m.joyai_llm_flash_forward_cached(
        p, t, c, pos, cfg)).trace(
        params, jnp.zeros((2, 1), jnp.int32),
        m.init_cache(cfg, 2, la.LATENT_BLOCK),
        jnp.zeros((2,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "mla_absorbed/mla_absorbed_live_blocks" in tick
    assert "bhs,bsc->bhc" not in tick                 # no einsum beside it


@pytest.mark.parametrize("option,kw", [
    ("kv_layout='paged'", {"kv_layout": "paged"}),
    ("prefill_chunk", {"prefill_chunk": 16}),
    ("spec_decode", {"spec_decode": "spec"}),
    ("multi_tick", {"multi_tick": 4}),
    ("quant", {"quant": "int8"}),
    ("host_kv_bytes", {"host_kv_bytes": 1 << 20}),
    ("mesh", {"mesh": "tp"}),
])
def test_each_refused_engine_option_raises_its_typed_error(setup, option,
                                                           kw):
    cfg, params = setup
    if "mesh" in kw:
        from paddle_tpu.parallel.mesh import build_mesh
        kw = {"mesh": build_mesh({"tp": 1}, devices=jax.devices()[:1])}
    with pytest.raises(UnsupportedOptionError) as e:
        ServingEngine(_serving_params(params), cfg, family=FAMILY,
                      num_slots=2, max_len=64, **kw)
    assert e.value.option == option and e.value.family == FAMILY
    assert option in REFUSABLE and isinstance(e.value, ValueError)


def test_migration_and_the_journal_are_refused(setup, tmp_path):
    cfg, params = setup
    params = _serving_params(params)
    eng = ServingEngine(params, cfg, family=FAMILY, num_slots=2, max_len=64)
    req = eng.submit(_tokens(5, 40), 4)
    eng.step()
    for call in (lambda: eng.snapshot_request(req),
                 lambda: eng.restore_request({}),
                 lambda: eng.detach_request(req)):
        with pytest.raises(UnsupportedOptionError) as e:
            call()
        assert e.value.option == "migration"
    for kw, option in (({"journal_dir": str(tmp_path)}, "journal_dir"),
                       ({"roles": ["prefill", "decode"], "replicas": 2},
                        "migration")):
        with pytest.raises(UnsupportedOptionError) as e:
            create_router(params, cfg, family=FAMILY, num_slots=2,
                          max_len=64, **{"replicas": 1, **kw})
        assert e.value.option == option


def test_the_family_is_registered_lazily():
    """Asked for by name, imported only then: `import paddle_tpu` and the
    engine's module bring nothing of it."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving as s; "
            "assert 'paddle_tpu.models.joyai_llm_flash' not in sys.modules; "
            "assert 'paddle_tpu.models.cohere2_moe' not in sys.modules; "
            "f = s.family_for('joyai_llm_flash'); "
            "assert 'paddle_tpu.models.joyai_llm_flash' in sys.modules; "
            "assert set(f.refuses) == set(s.REFUSABLE); print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                         "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
    fam = family_for(FAMILY)
    assert fam.counts is m.span_counts and fam.prefill is m.prefill_into_slot
    assert fam.serving_specs is None
