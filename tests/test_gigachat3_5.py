"""The gigachat3_5 family (GigaChat3.5-432B-A28B) against its plain
reference (benchmark/reference/gigachat3_5.py, which imports nothing of the
program), at small sizes on the CPU in float32 with seeded weights: the
gated delta rule in both its forms, the two kinds of state in one cache,
latent attention with YaRN, the clamped experts and the share, the counts
on the spans, and the options the family refuses.

TOLERANCE. Logits here are O(1) and everything is float32. The program and
the reference sum in different orders — the delta rule in chunks through a
triangular solve against one step a position, the output from S^T q before
the write against S_t^T q after it, a running softmax over key blocks, the
absorbed contraction over the latent, grouped matmuls over sorted rows —
which moves a logit by a few 1e-6; 3e-5 leaves room for that and none for
a fault: the decay, beta or the q/k norms left out, another head map, the
YaRN factor left out, padding that advances the state and float8 operands
each move logits by 1e-3 and more at these sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gigachat3_5 as ref
from paddle_tpu.inference.router import create_router
from paddle_tpu.inference.serving import (REFUSABLE, ServingEngine,
                                          UnsupportedOptionError)
from paddle_tpu.kernels import gated_delta as gd
from paddle_tpu.models import gigachat3_5 as m
from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans
from paddle_tpu.quantization.serving import COMPUTE_LEAVES

TOL = 3e-5
GEN = 8
FAMILY = "gigachat3_5"
MAX_LEN = 160


def make_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=32, num_layers=5,
                full_attention_layers=(4,), num_heads=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=8, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=8, ffn_hidden=48, moe_ffn_hidden=24,
                first_k_dense_replace=1, n_routed_experts=16, experts_held=4,
                first_expert=4, num_experts_per_tok=4, max_seq_len=MAX_LEN,
                rope_theta=10000.0, original_max_position_embeddings=16,
                dtype=jnp.float32, param_dtype=jnp.float32, prefill_chunk=8)
    base.update(kw)
    return m.GigaChat35Config(**base)


def make_params(cfg, seed=0, scale=6.0):
    """Seeded weights, the matrices scaled up so that logits are O(1) and
    the router's scores and the gates spread."""
    params = m.init_gigachat3_5_params(cfg, jax.random.PRNGKey(seed))
    return {k: v * scale if k.endswith("_w") and k != "dn_conv_w" else v
            for k, v in params.items()}


def arch_of(cfg):
    keys = ("num_layers", "full_attention_layers", "first_k_dense_replace",
            "num_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "rope_theta", "rope_factor",
            "original_max_position_embeddings", "beta_fast", "beta_slow",
            "mscale_all_dim", "use_mla_scaling_factor",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps",
            "swiglu_limit", "num_experts_per_tok", "first_expert",
            "norm_topk_prob", "routed_scaling_factor")
    return {**{k: getattr(cfg, k) for k in keys},
            "layer_norm_eps": cfg.rms_norm_eps}


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
_step = jax.jit(m.gigachat3_5_forward_cached, static_argnums=4)


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


@functools.lru_cache(maxsize=None)
def _reference(arch_items, told_items):
    arch, told = dict(arch_items), dict(told_items)
    return jax.jit(lambda params, seq: ref.forward(params, seq, arch, **told))


def reference_logits(cfg, params, seq, **told):
    """The reference's logits [len(seq), V], one program per model and
    fault: the sequence padded to whole blocks of 128 positions (causal:
    the padding changes no row before it)."""
    padded = np.zeros((-(-len(seq) // 128) * 128,), np.int32)
    padded[:len(seq)] = seq
    fn = _reference(tuple(sorted(arch_of(cfg).items())),
                    tuple(sorted(told.items())))
    return np.asarray(fn(params, jnp.asarray(padded)))[:len(seq)]


def reference_rows(cfg, params, prompt, forced, **told):
    seq = np.concatenate([prompt, forced]).astype(np.int32)
    return reference_logits(cfg, params, seq, **told)[len(prompt) - 1:]


# ------------------------------------------------------- parity, logits
@pytest.mark.parametrize("length", [8, 16, 32])
def test_the_prompt_path_matches_the_reference(setup, length):
    """A whole prompt through the chunked delta rule and the decompressed
    latent attention, every position's logits."""
    cfg, params = setup
    tokens = _tokens(length, 3)
    got, _ = m.gigachat3_5_forward_cached(
        params, jnp.asarray(tokens[None]), m.init_cache(cfg, 1, length), 0,
        cfg)
    want = reference_logits(cfg, params, tokens)
    assert np.abs(want).max() > 0.3            # logits worth comparing
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL, rtol=0)


@pytest.mark.parametrize("prompt_len", [5, 16, 21, 100])
def test_prefill_then_ticks_match_the_reference(setup, prompt_len):
    """The bucketed prefill into a slot (100 tokens: a bucket of 128, two
    chunks of the delta rule), then ticks through the latent pool and the
    delta-rule state, against the reference's one full forward with its
    sequential recurrence."""
    cfg, params = setup
    prompt, forced = _tokens(prompt_len, 1), _tokens(GEN, 2)
    got, cache = serve_logits(cfg, params, m.init_cache(cfg, 3, MAX_LEN), 1,
                              prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)
    assert cache["ckv"].shape == (1, 3, MAX_LEN, 16)
    assert cache["dn_state"].shape == (4, 3, 4, 8, 8)      # no position axis
    assert cache["dn_state"].dtype == jnp.float32
    assert cache["dn_conv"].shape == (4, 3, 3, 2 * 2 * 8 + 4 * 8)


@pytest.mark.parametrize("layers,full,dense", [
    (2, (1,), 1), (9, (4, 8), 1), (8, (3, 7), 3), (4, (0,), 0)])
def test_other_stacks_match_the_reference(layers, full, dense):
    """The two-layer cut of the tiny benchmark cell, two periods of the
    expert layers (one scan over them), the published head of the stack
    (three dense linear layers as a scan, then latent, linear x3, latent),
    and no dense layer at all."""
    cfg = make_cfg(num_layers=layers, full_attention_layers=full,
                   first_k_dense_replace=dense)
    params = make_params(cfg, 3)
    prompt, forced = _tokens(13, 4), _tokens(4, 5)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, MAX_LEN), 0,
                          prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


def test_the_uncut_model_matches_the_uncut_reference():
    cfg = make_cfg(experts_held=None, first_expert=0)
    params = make_params(cfg, 6)
    assert params["exp_gate_w"].shape[1] == cfg.n_routed_experts
    prompt, forced = _tokens(11, 7), _tokens(4, 8)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, MAX_LEN), 1,
                          prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


# ------------------------------------------------------ the delta rule
def _delta_inputs(T, H=3, dk=16, dv=8, seed=0):
    rng = np.random.default_rng(seed)

    def l2(x):
        return x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)
    f32 = np.float32
    return (l2(rng.standard_normal((T, H, dk))).astype(f32) / 4,
            l2(rng.standard_normal((T, H, dk))).astype(f32),
            rng.standard_normal((T, H, dv)).astype(f32),
            (-0.3 * np.exp(rng.standard_normal((T, H)))).astype(f32),
            (1 / (1 + np.exp(-rng.standard_normal((T, H))))).astype(f32),
            rng.standard_normal((H, dk, dv)).astype(f32))


@pytest.mark.parametrize("T,length", [(150, 150), (150, 97), (63, 63),
                                      (128, 70), (97, 1)])
def test_the_chunked_rule_equals_the_step_by_step_one(T, length):
    """Chunks of 64 (the last one partial where T is no multiple of it),
    from a non-zero state, the positions at or past `length` padding: the
    outputs up to `length` and the state after position length - 1 are the
    one-step form's — to float32 round-off over a state of entries near
    1 carried through 150 steps in two orders of summation (1e-5)."""
    q, k, v, g, beta, s0 = _delta_inputs(T)
    s, outs = jnp.asarray(s0)[None], []
    for t in range(T):
        o, s = gd.gated_delta_step(s, q[None, t], k[None, t], v[None, t],
                                   g[None, t], beta[None, t],
                                   live=jnp.asarray([t < length]))
        outs.append(np.asarray(o[0]))
    o, s_end = gd.gated_delta_chunked(q, k, v, g, beta, s0, length)
    assert gd.CHUNK == 64
    np.testing.assert_allclose(np.asarray(o)[:length],
                               np.stack(outs)[:length], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(s_end), np.asarray(s[0]),
                               atol=1e-5, rtol=0)


def test_the_step_is_the_published_update_and_leaves_an_idle_row_alone():
    """S' = e^g S; S_t = S' + k (beta (v - S'^T k))^T; o = S_t^T q, written
    out per row, against the one-step form that reads S once."""
    q, k, v, g, beta, s0 = _delta_inputs(2, seed=4)
    s = np.stack([s0, 2 * s0])
    o, new = gd.gated_delta_step(jnp.asarray(s), q, k, v, g, beta,
                                 live=jnp.asarray([True, False]))
    decayed = np.exp(g[0])[:, None, None] * s[0]
    pred = np.einsum("hkv,hk->hv", decayed, k[0])
    want = decayed + k[0][:, :, None] * (beta[0][:, None]
                                         * (v[0] - pred))[:, None, :]
    np.testing.assert_allclose(np.asarray(new[0]), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o[0]),
                               np.einsum("hkv,hk->hv", want, q[0]),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[1]), s[1])


# ---------------------------------------------- pools, slots and batches
def test_padding_and_idle_rows_leave_the_recurrent_pools_bit_identical(
        setup):
    """Whatever a prompt is padded with, and whatever an idle row holds as
    its token and position, no pool moves by a bit: the delta-rule state
    and the convolution rows stop at the last real token, an idle row's
    stay as they were."""
    cfg, params = setup
    prompt = _tokens(11, 13)
    cache = m.init_cache(cfg, 3, MAX_LEN)
    a = prefill(cfg, params, cache, 1, prompt, pad_with=0)[1]
    b = prefill(cfg, params, cache, 1, prompt, pad_with=55)[1]
    for name in ("ckv", "kpe", "dn_state", "dn_conv"):
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
        assert np.asarray(a[name][:, 1]).any()
        assert not np.asarray(a[name][:, [0, 2]]).any()    # other slots
    # the conv rows are the last three real positions' inputs
    one = m.init_cache(cfg, 1, 16)
    _, short = prefill(cfg, params, one, 0, prompt[:8])
    assert not np.array_equal(np.asarray(short["dn_conv"][:, 0]),
                              np.asarray(a["dn_conv"][:, 1]))
    live = jnp.asarray([[False], [True], [False]])
    outs = []
    for idle_tok, idle_pos in ((0, 0), (71, 40)):
        toks = jnp.asarray([[idle_tok], [5], [idle_tok]], jnp.int32)
        pos = jnp.asarray([idle_pos, 11, idle_pos], jnp.int32)
        outs.append(_step(params, toks, a, pos, _Static(cfg), live))
    for name in ("ckv", "kpe", "dn_state", "dn_conv"):
        got = np.asarray(outs[0][1][name])
        np.testing.assert_array_equal(got, np.asarray(outs[1][1][name]))
        np.testing.assert_array_equal(got[:, [0, 2]],
                                      np.asarray(a[name][:, [0, 2]]))
        assert not np.array_equal(got[:, 1], np.asarray(a[name][:, 1]))
    np.testing.assert_array_equal(np.asarray(outs[0][0][1]),
                                  np.asarray(outs[1][0][1]))
    stats = m.span_counts(cfg, np.asarray(outs[0][1]["stats"]))
    assert stats["state_bytes"] == 2 * m.slot_state_bytes(cfg)  # one row


def test_admission_replaces_the_rows_whole(setup):
    """A slot that served a long request is admitted a short one: its
    delta-rule state and convolution rows are the short prompt's alone,
    bit for bit what an empty cache gives, and its logits the
    reference's."""
    cfg, params = setup
    long, short = _tokens(30, 14), _tokens(6, 15)
    forced = _tokens(5, 16)
    cache = m.init_cache(cfg, 2, MAX_LEN)
    _, used = serve_logits(cfg, params, cache, 1, long, _tokens(8, 17))
    _, again = prefill(cfg, params, used, 1, short)
    _, fresh = prefill(cfg, params, m.init_cache(cfg, 2, MAX_LEN), 1, short)
    for name in ("dn_state", "dn_conv"):
        np.testing.assert_array_equal(np.asarray(again[name][:, 1]),
                                      np.asarray(fresh[name][:, 1]))
    got, _ = serve_logits(cfg, params, used, 1, short, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, short, forced), atol=TOL, rtol=0)


@pytest.mark.parametrize("company", ["alone", "crowd"])
def test_a_stream_is_slot_and_batch_invariant(setup, company):
    cfg, params = setup
    prompt, forced = _tokens(9, 18), _tokens(6, 19)
    base, _ = serve_logits(cfg, params, m.init_cache(cfg, 1, MAX_LEN), 0,
                           prompt, forced)
    cache = m.init_cache(cfg, 4, MAX_LEN)
    others = None
    if company == "crowd":
        _, cache = prefill(cfg, params, cache, 0, _tokens(20, 20))
        _, cache = prefill(cfg, params, cache, 3, _tokens(5, 21))
        others = {0: (20, 7), 3: (5, 9)}
    got, _ = serve_logits(cfg, params, cache, 2, prompt, forced, others)
    np.testing.assert_allclose(got, base, atol=TOL, rtol=0)


def test_a_run_that_continues_a_cache_or_batches_prompts_is_refused(setup):
    cfg, params = setup
    cache = m.init_cache(cfg, 2, MAX_LEN)
    with pytest.raises(ValueError, match="ONE whole prompt"):
        m.gigachat3_5_forward_cached(params, jnp.zeros((1, 8), jnp.int32),
                                     cache, jnp.asarray([3, 0]), cfg)
    with pytest.raises(ValueError, match="ONE whole prompt"):
        m.gigachat3_5_forward_cached(params, jnp.zeros((2, 8), jnp.int32),
                                     cache, 0, cfg)


# ------------------------------------------------------ experts, positions
@pytest.mark.parametrize("rows", [6, 160])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """The parts the 16 shares of one expert each give, with the shared
    expert (which every chip computes alike) counted once, add up to the
    uncut reference's expert layer — in the dense form (6 rows) and the
    sorted one (160), every gated product clamped."""
    whole = make_cfg(experts_held=None, first_expert=0)
    params = make_params(whole, 9)
    j = 1                                           # an expert layer
    h = jnp.asarray(np.random.default_rng(2).standard_normal((rows, 32)),
                    jnp.float32)
    p = {k: params[k][j] for k in ref.EXPERTS}
    arch = arch_of(whole)
    want = np.asarray(ref._experts(h, p, arch, "float32"))
    shared = np.asarray(ref._mlp(h, p["shared_gate_w"], p["shared_up_w"],
                                 p["shared_down_w"], arch, "float32"))
    total, loads = np.zeros_like(want), []
    for first in range(16):
        cfg = make_cfg(experts_held=1, first_expert=first)
        part = dict(params)
        for name in ("exp_gate_w", "exp_up_w", "exp_down_w"):
            part[name] = params[name][:, first:first + 1]
        out, load = m.mla._experts(part, j, h, jnp.ones((rows,), bool), cfg,
                                   cfg.swiglu_limit)
        total += np.asarray(out) - shared
        loads.append(np.asarray(load))
    assert np.concatenate(loads).sum() == rows * whole.num_experts_per_tok
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)


def test_the_clamp_bites_and_matches_the_reference():
    """With the FFN's matrices scaled until the gated products pass 10 the
    clamp is what the logits follow: the program matches the reference,
    and the reference without the clamp lies far from both."""
    cfg = make_cfg()
    params = make_params(cfg, 11)
    params = {k: v * 8.0 if k.endswith(("gate_w", "up_w")) else v
              for k, v in params.items()}
    prompt, forced = _tokens(12, 22), _tokens(4, 23)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 1, MAX_LEN), 0,
                          prompt, forced)
    want = reference_rows(cfg, params, prompt, forced)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    loose = make_cfg(swiglu_limit=None)
    free = reference_rows(loose, params, prompt, forced)
    assert np.abs(free - want).max() > 0.1


def test_yarn_frequencies_and_the_softmax_factor_are_the_published_ones():
    """At the published widths (64 rotated dims, theta 1e5, factor 8 over
    32,768): the 14 fastest pairs keep theta's frequency, pairs 24 .. 31 are
    divided by 8, a ramp between; mscale^2 = (0.1 ln 8 + 1)^2."""
    cfg = m.GigaChat35Config()
    inv = m.yarn_inv_freq(cfg)
    theta = 1e5 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:14], theta[:14], rtol=1e-6)
    np.testing.assert_allclose(inv[24:], theta[24:] / 8, rtol=1e-6)
    assert ((inv[14:24] < theta[14:24]) & (inv[14:24] > theta[14:24] / 8)
            ).all()
    np.testing.assert_allclose(inv, ref.yarn_frequencies(arch_of(cfg)),
                               rtol=1e-6)
    assert cfg.query_scale == pytest.approx((0.1 * np.log(8) + 1) ** 2)
    assert cfg.query_scale == pytest.approx(1.4591, abs=1e-4)


@pytest.mark.parametrize("fault", [
    {"decay": False}, {"beta": False}, {"qk_norm": False},
    {"head_map": "modulo"}, {"mscale": False}, {"precision": "fp8"}],
    ids=["no_decay", "no_beta", "qk_not_normed", "head_modulo",
         "no_mscale", "fp8"])
def test_a_planted_fault_moves_the_logits_past_the_tolerance(setup, fault):
    cfg, params = setup
    prompt, forced = _tokens(21, 24), _tokens(GEN, 25)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 1, MAX_LEN), 0,
                          prompt, forced)
    wrong = reference_rows(cfg, params, prompt, forced, **fault)
    assert np.abs(got - wrong).max() > 30 * TOL


def test_the_gate_scale_is_invisible_behind_the_post_norm(setup):
    """The output gate's scale 2 multiplies the whole mixer output, which
    N2 normalises again: left out, only the norm's eps (1e-6 against an
    output of mean square near 1e-2 at these widths) sees it — it moves the
    logits a fiftieth of what beta left out does, a fault a comparison of
    logits cannot tell from round-off at the published widths."""
    cfg, params = setup
    prompt = _tokens(21, 26)

    def moved(**fault):
        return np.abs(reference_logits(cfg, params, prompt)
                      - reference_logits(cfg, params, prompt, **fault)).max()
    assert moved(gate_scale=False) < moved(beta=False) / 50


# ----------------------------------------------------------- the engine
def _serve(router, prompts, max_new=GEN, **kw):
    reqs = [router.submit(p, max_new, **kw) for p in prompts]
    steps = 0
    while router.has_work():
        router.step()
        steps += 1
        assert steps < 500
    return reqs


def test_the_engine_serves_it_with_every_option_at_its_default(setup):
    """submit()/step() through create_router: bucketed prefill, the decode
    tick, slots reused (5 requests over 2 slots), greedy tokens equal to
    the reference's argmax wherever that is not a near-tie."""
    cfg, params = setup
    router = create_router(params, cfg, replicas=1, family=FAMILY,
                           num_slots=2, max_len=MAX_LEN)
    eng = router.replicas[0].eng
    assert not eng.paged and not eng.spec and eng.mt_k == 1 \
        and not eng.quant and eng.mesh is None
    prompts = [_tokens(n, 20 + n) for n in (5, 21, 8, 3, 70)]
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
        eng._cache[k].nbytes for k in ("ckv", "kpe", "dn_state", "dn_conv"))
    router.close()


def test_counts_ride_the_one_pull_onto_the_spans(setup):
    cfg, params = setup
    router = create_router(params, cfg, replicas=1, family=FAMILY,
                           num_slots=2, max_len=MAX_LEN)
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
        assert "state_bytes" not in c                    # a tick's counts
        b = c["bucket"]
        assert c["scan_chunks"] == 4 * -(-b // gd.CHUNK)
        assert c["attn_pairs"] == b * (b + 1) // 2 and c["attn_pairs_kernel"] \
            == 0
    per_row = 4 * (4 * 8 * 8 * 4 + 3 * 64 * 4)           # f32 pools here
    assert m.slot_state_bytes(cfg) == per_row
    for s in ticks:
        c = s.counts
        assert c["expert_tokens"] <= c["active"] * k * expert_layers
        assert c["state_bytes"] == 2 * c["active"] * per_row
        # the einsums read every position of every slot
        assert c["kv_positions_read"] == c["kv_positions_pool"] \
            == 2 * MAX_LEN
        assert c["kv_bytes"] == c["kv_positions_read"] * (16 + 4) * 4
    router.close()


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
        ServingEngine(params, cfg, family=FAMILY, num_slots=2,
                      max_len=MAX_LEN, **kw)
    assert e.value.option == option and e.value.family == FAMILY
    assert option in REFUSABLE and isinstance(e.value, ValueError)


def test_migration_and_the_journal_are_refused(setup, tmp_path):
    cfg, params = setup
    eng = ServingEngine(params, cfg, family=FAMILY, num_slots=2,
                        max_len=MAX_LEN)
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
                          max_len=MAX_LEN, **{"replicas": 1, **kw})
        assert e.value.option == option


def test_the_family_is_registered_lazily():
    """Asked for by name, imported only then."""
    import os
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving as s; "
            "assert 'paddle_tpu.models.gigachat3_5' not in sys.modules; "
            "f = s.family_for('gigachat3_5'); "
            "assert 'paddle_tpu.models.gigachat3_5' in sys.modules; "
            "assert set(f.refuses) == set(s.REFUSABLE); print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_the_rounded_leaves_and_the_float32_ones_cover_the_tree():
    """Every leaf is rounded to the compute dtype at build, kept float32
    (A_log, dt_bias, the routing bias) or a norm scale / the router, whose
    arithmetic is float32 — and the delta-rule state is float32 under a
    bf16 compute dtype."""
    cfg = make_cfg(dtype=jnp.bfloat16)
    params = m.init_gigachat3_5_params(cfg, jax.random.PRNGKey(0))
    rest = {k for k in params if "norm" in k} | {"router_w"}
    assert set(COMPUTE_LEAVES[FAMILY]) | set(m.F32_LEAVES) | rest \
        == set(params)
    assert not set(COMPUTE_LEAVES[FAMILY]) & (set(m.F32_LEAVES) | rest)
    pools = m.init_cache(cfg, 2, 16)
    assert pools["dn_state"].dtype == jnp.float32
    assert pools["dn_conv"].dtype == pools["ckv"].dtype == jnp.bfloat16


def test_param_shapes_count_the_published_cut():
    """The benchmark cell's cut (layers 0 and 4-7, 16 of 256 experts,
    16,032 rows) holds 4,731,722,752 parameters, a slot 16.8 MB of state."""
    cfg = m.GigaChat35Config(num_layers=5, full_attention_layers=(4,),
                             first_k_dense_replace=1, experts_held=16,
                             vocab_size=16032)
    shapes = m.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_731_722_752
    assert cfg.layer_types == ("linear_attention",) * 4 + ("full_attention",)
    assert m.slot_state_bytes(cfg) == 4 * (64 * 128 * 128 * 4
                                           + 3 * 16384 * 2)
    published = m.GigaChat35Config()
    assert published.layers_of(m.FULL) == 10 and published.dense_layers == 3
    assert functools.reduce(lambda a, b: a and b, (
        published.layer_types[i] == m.FULL for i in range(3, 40, 4)))
