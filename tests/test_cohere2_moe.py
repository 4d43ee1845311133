"""The cohere2_moe family (Command A+) against its plain reference
(tests/cohere2_moe_reference.py), at small sizes on the CPU in float32
with seeded weights: the cached forward over the two pools, the ring,
the dropless expert layer and its share, the rotary convention, the
counts on the spans, and the options the family refuses.

TOLERANCE. Logits here are O(1) and everything is float32. The program
and the reference sum in different orders (a running softmax over key
blocks, grouped matmuls over sorted rows, heads folded into groups),
which moves a logit by a few 1e-7; 2e-5 leaves room for that and none
for a fault: a dropped token, split-half RoPE, a missing window mask or
RoPE on a full layer each move logits by 1e-2 and more at these sizes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cohere2_moe_reference as ref
from paddle_tpu.inference.router import create_router
from paddle_tpu.inference.serving import (REFUSABLE, ServingEngine,
                                          UnsupportedOptionError,
                                          family_for)
from paddle_tpu.models import cohere2_moe as m
from paddle_tpu.parallel import moe
from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans

TOL = 2e-5
WINDOW = 8
GEN = 12


def make_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
                num_kv_heads=2, head_dim=8, ffn_hidden=48, max_seq_len=64,
                sliding_window=WINDOW, num_experts=16, experts_held=4,
                first_expert=4, experts_per_token=4, num_shared_experts=2,
                dtype=jnp.float32, param_dtype=jnp.float32,
                prefill_chunk=8)
    base.update(kw)
    return m.Cohere2MoeConfig(**base)


def make_params(cfg, seed=0):
    """Seeded weights, the matmuls scaled up so that logits are O(1) and
    the router's scores spread."""
    params = m.init_cohere2_moe_params(cfg, jax.random.PRNGKey(seed))
    return {k: v * 6.0 if k.endswith("_w") else v
            for k, v in params.items()}


def ref_kw(cfg):
    return dict(layer_types=cfg.layer_types, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, window=cfg.sliding_window,
                theta=cfg.rope_theta, eps=cfg.layer_norm_eps,
                per_token=cfg.experts_per_token,
                first_expert=cfg.first_expert,
                logit_scale=cfg.logit_scale)


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


def serve_logits(cfg, params, cache, slot, prompt, forced):
    """Prefill `prompt` into `slot` of the pools, then decode with
    `forced` fed back token by token (teacher forcing) through the
    per-row-position path every other slot rides too -> (logits at the
    last prompt position and after each forced token, the pools)."""
    slots = cache["k"].shape[1]
    padded = np.zeros((1, _bucket(len(prompt))), np.int32)
    padded[0, :len(prompt)] = prompt
    first, cache = jax.jit(m.prefill_into_slot, static_argnums=5)(
        params, cache, jnp.asarray(padded), jnp.int32(len(prompt)),
        jnp.int32(slot), _Static(cfg))
    rows = [np.asarray(first[0])]
    step = jax.jit(m.cohere2_moe_forward_cached, static_argnums=4)
    pos = np.zeros(slots, np.int32)
    toks = np.zeros((slots, 1), np.int32)
    for i, tok in enumerate(forced):
        pos[slot], toks[slot, 0] = len(prompt) + i, tok
        logits, cache = step(params, jnp.asarray(toks), cache,
                             jnp.asarray(pos), _Static(cfg))
        rows.append(np.asarray(logits[slot, 0]))
    return np.stack(rows), cache


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


def reference_rows(cfg, params, prompt, forced):
    seq = np.concatenate([prompt, forced]).astype(np.int32)
    logits = ref.forward(params, jnp.asarray(seq), **ref_kw(cfg))
    return np.asarray(logits[len(prompt) - 1:])


# ------------------------------------------------------- parity, logits
@pytest.mark.parametrize("prompt_len", [5, WINDOW, 21])
def test_prefill_then_decode_matches_the_reference(setup, prompt_len):
    """Shorter than, equal to and longer than the window; 12 decoded
    tokens, so every ring wraps at least once; sliding and full layers."""
    cfg, params = setup
    prompt, forced = _tokens(prompt_len, 1), _tokens(GEN, 2)
    got, _ = serve_logits(cfg, params, m.init_kv_cache(cfg, 3, 64), 1,
                          prompt, forced)
    want = reference_rows(cfg, params, prompt, forced)
    assert np.abs(want).max() > 0.3            # logits worth comparing
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("layer_types", [
    (m.FULL, m.FULL), (m.SLIDING, m.SLIDING),
    (m.SLIDING, m.FULL, m.SLIDING, m.FULL, m.SLIDING, m.FULL)])
def test_other_layer_patterns_match_the_reference(layer_types):
    """All full, all sliding, and a period of two scanned three times."""
    cfg = make_cfg(num_layers=len(layer_types), layer_types=layer_types)
    params = make_params(cfg, 3)
    prompt, forced = _tokens(13, 4), _tokens(GEN, 5)
    got, _ = serve_logits(cfg, params, m.init_kv_cache(cfg, 2, 64), 0,
                          prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


def test_uncut_model_matches_the_uncut_reference():
    cfg = make_cfg(experts_held=None, first_expert=0)
    params = make_params(cfg, 6)
    assert params["gate_w"].shape[1] == cfg.num_experts
    prompt, forced = _tokens(11, 7), _tokens(4, 8)
    got, _ = serve_logits(cfg, params, m.init_kv_cache(cfg, 1, 64), 0,
                          prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


# ------------------------------------------------------------- the ring
def test_a_long_prompt_keeps_its_last_window_positions(setup):
    cfg, params = setup
    prompt = _tokens(21, 9)
    _, cache = serve_logits(cfg, params, m.init_kv_cache(cfg, 2, 64), 1,
                            prompt, [])
    ordered = m.init_kv_cache(cfg, 1, 32, ring=False)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = prompt
    _, ordered = m.cohere2_moe_forward_cached(
        params, jnp.asarray(padded), ordered, 0, cfg)
    assert cache["k_win"].shape[2] == WINDOW
    for row in range(WINDOW):
        position = max(p for p in range(21) if p % WINDOW == row)
        assert position >= 21 - WINDOW
        np.testing.assert_allclose(cache["k_win"][:, 1, row],
                                   ordered["k_win"][:, 0, position],
                                   atol=1e-6, rtol=0)
    np.testing.assert_allclose(cache["k"][:, 1, :21],
                               ordered["k"][:, 0, :21], atol=1e-6, rtol=0)
    assert not np.asarray(cache["k_win"][:, 0]).any()    # slot 0 untouched


def test_a_reused_slot_reads_nothing_of_its_last_occupant(setup):
    cfg, params = setup
    long_prompt, short_prompt = _tokens(21, 10), _tokens(5, 11)
    forced = _tokens(GEN, 12)
    _, used = serve_logits(cfg, params, m.init_kv_cache(cfg, 2, 64), 0,
                           long_prompt, _tokens(GEN, 13))
    assert np.asarray(used["k_win"][:, 0]).all()          # a full ring
    again, _ = serve_logits(cfg, params, used, 0, short_prompt, forced)
    fresh, _ = serve_logits(cfg, params, m.init_kv_cache(cfg, 2, 64), 0,
                            short_prompt, forced)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(
        again, reference_rows(cfg, params, short_prompt, forced),
        atol=TOL, rtol=0)


def test_ring_writes_and_positions():
    from paddle_tpu.kernels.decode_attention import (ring_positions,
                                                     ring_rows, write_kv)
    held = np.asarray(ring_positions(jnp.asarray([2, 8, 21]), 8))
    assert held[0].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert held[1].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert held[2].tolist() == [16, 17, 18, 19, 20, 21, 14, 15]
    assert np.asarray(ring_rows(21, 8)).tolist() == \
        [16, 17, 18, 19, 20, 13, 14, 15]
    assert np.asarray(ring_rows(3, 8)).tolist() == list(range(8))
    pool = jnp.zeros((2, 3, 4, 1, 1))
    new = jnp.arange(3, dtype=jnp.float32).reshape(3, 1, 1, 1) + 1
    out = write_kv(pool, new, jnp.asarray([0, 5, 11]), 1, ring=True)
    assert np.asarray(out[1, :, :, 0, 0]).tolist() == \
        [[1, 0, 0, 0], [0, 2, 0, 0], [0, 0, 0, 3]]
    assert not np.asarray(out[0]).any()
    with pytest.raises(ValueError, match="ring"):
        write_kv(pool, jnp.zeros((3, 5, 1, 1)), 0, 1, ring=True)


def test_blocked_attention_matches_dense_scores():
    from paddle_tpu.kernels.decode_attention import blocked_attention
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 32, 4, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 32, 2, 8))
    i, j = np.arange(32)[:, None], np.arange(32)[None, :]
    for window in (None, 5, 8, 11):
        mask = (j <= i) if window is None else (j <= i) & (i - j < window)
        kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        s = jnp.einsum("bihd,bjhd->bhij", q, kk) / np.sqrt(8)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        want = jnp.einsum("bhij,bjhd->bihd", p, vv)
        for block in (4, 8, 32):
            got = blocked_attention(q, k, v, window=window, block=block)
            np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
        part = blocked_attention(q[:, 16:24], k, v, window=window, block=8,
                                 q_offset=jnp.int32(16))
        np.testing.assert_allclose(part, want[:, 16:24], atol=2e-6, rtol=0)


# -------------------------------------------------------------- routing
def _expert_weights(held, d=16, f=24, seed=0):
    key = jax.random.PRNGKey(seed)
    return [0.3 * jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, shape in enumerate([(held, d, f), (held, d, f),
                                       (held, f, d)])]


def _by_loop(x, choice, weight, gate_w, up_w, down_w, first):
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for c, w in zip(np.asarray(choice[t]), np.asarray(weight[t])):
            if first <= c < first + gate_w.shape[0]:
                out[t] += w * np.asarray(ref.expert(
                    x[t], gate_w[c - first], up_w[c - first],
                    down_w[c - first]))
    return out


@pytest.mark.parametrize("rows", [7, 100])      # the dense and sorted forms
def test_no_token_is_dropped_whatever_the_imbalance(rows):
    assert 7 <= moe.DENSE_FORM_ROWS < 100
    gate_w, up_w, down_w = _expert_weights(8)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 16))
    # every token chooses the same 4 experts, all of them held here, and
    # experts 6..9 are chosen by none
    choice = jnp.tile(jnp.asarray([[2, 3, 4, 5]], jnp.int32), (rows, 1))
    _, weight = moe.sigmoid_topk(
        jax.random.normal(jax.random.PRNGKey(2), (rows, 16)), 4)
    got, load = moe.dropless_experts(x, choice, weight, gate_w, up_w,
                                     down_w, first=2)
    np.testing.assert_allclose(
        got, _by_loop(x, choice, weight, gate_w, up_w, down_w, 2),
        atol=1e-5, rtol=0)
    assert load.tolist() == [rows] * 4 + [0] * 4
    # and none held here: nothing is added, nothing breaks
    got, load = moe.dropless_experts(x, choice + 20, weight, gate_w, up_w,
                                     down_w, first=2)
    assert not np.asarray(got).any() and not np.asarray(load).any()


def test_weights_sum_to_one_over_the_chosen():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (50, 128))
    choice, weight = moe.sigmoid_topk(logits, 8)
    np.testing.assert_allclose(weight.sum(-1), 1.0, atol=1e-6)
    assert weight.dtype == jnp.float32 and (np.asarray(weight) > 0).all()
    scores = np.asarray(jax.nn.sigmoid(logits))
    for t in range(50):
        assert set(np.asarray(choice[t]).tolist()) == \
            set(np.argsort(-scores[t])[:8].tolist())


@pytest.mark.parametrize("rows", [5, 90])
def test_a_token_does_not_depend_on_its_company(rows):
    """The capacity layer's fault (ROADMAP R2): there, who else is in the
    step decides whether a token keeps its expert."""
    gate_w, up_w, down_w = _expert_weights(4, seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (rows, 16))
    choice, weight = moe.sigmoid_topk(
        jax.random.normal(jax.random.PRNGKey(6), (rows, 8)), 3)
    together, _ = moe.dropless_experts(x, choice, weight, gate_w, up_w,
                                       down_w, first=2)
    for t in (0, rows // 2, rows - 1):
        alone, _ = moe.dropless_experts(x[t:t + 1], choice[t:t + 1],
                                        weight[t:t + 1], gate_w, up_w,
                                        down_w, first=2)
        np.testing.assert_allclose(together[t], alone[0], atol=1e-5, rtol=0)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 experts each: their routed parts, with the shared
    experts and the attention counted once, are the reference's uncut
    layer (model-configs guide, section 4)."""
    cfg = make_cfg(num_layers=1, layer_types=(m.SLIDING,),
                   experts_held=None, first_expert=0)
    params = make_params(cfg, 14)
    lp = {k: v[0] for k, v in params.items() if k not in ("wte", "norm_f")}
    x = jax.random.normal(jax.random.PRNGKey(15), (19, cfg.hidden_size))
    h = ref.layer_norm(x, lp["norm"], cfg.layer_norm_eps)
    kw = ref_kw(cfg)
    with jax.default_matmul_precision("highest"):
        attn = ref.attention(h, lp, m.SLIDING, num_heads=cfg.num_heads,
                             num_kv_heads=cfg.num_kv_heads,
                             window=cfg.sliding_window, theta=cfg.rope_theta)
        uncut = x + attn + ref.experts(h, lp, per_token=kw["per_token"],
                                       first_expert=0)
        shared_once = ref.experts(
            h, {**lp, "gate_w": lp["gate_w"][:0], "up_w": lp["up_w"][:0],
                "down_w": lp["down_w"][:0]}, per_token=kw["per_token"],
            first_expert=0)
    total = x + attn + shared_once
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        cut = dataclasses.replace(cfg, experts_held=2,
                                  first_expert=2 * share)
        lp_cut = {**lp, **{k: lp[k][held]
                           for k in ("gate_w", "up_w", "down_w")}}
        part, load = m._experts(lp_cut, h, jnp.ones((19,), bool), cut)
        total = total + (part - shared_once)
        assert load.shape == (2,)
    np.testing.assert_allclose(total, uncut, atol=TOL, rtol=0)


# ----------------------------------------------------------------- rope
def test_rope_is_interleaved_and_full_layers_have_none():
    x = jax.random.normal(jax.random.PRNGKey(7), (6, 2, 8))
    inter, split = ref.rope_interleaved(x, 50000.0), \
        ref.rope_split_half(x, 50000.0)
    assert np.abs(np.asarray(inter - split)).max() > 0.1
    cos, sin = m._rope_angles(jnp.arange(6)[None, :], 8, 50000.0)
    ours = m._apply_rope(x[None], cos, sin)[0]
    np.testing.assert_allclose(ours, inter, atol=1e-6, rtol=0)

    prompt = _tokens(9, 16)

    def logits(layer_types, theta):
        cfg = make_cfg(num_layers=2, layer_types=layer_types,
                       rope_theta=theta)
        return serve_logits(cfg, make_params(cfg, 17),
                            m.init_kv_cache(cfg, 1, 64), 0, prompt,
                            _tokens(3, 18))[0]

    # no rotation on a full layer: the rotary base changes nothing there
    np.testing.assert_array_equal(logits((m.FULL, m.FULL), 50000.0),
                                  logits((m.FULL, m.FULL), 100.0))
    assert np.abs(logits((m.SLIDING, m.FULL), 50000.0)
                  - logits((m.SLIDING, m.FULL), 100.0)).max() > 1e-3


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
    router = create_router(params, cfg, replicas=1, family="cohere2_moe",
                           num_slots=2, max_len=64)
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
    # sampled decoding: a stream is its request's, whoever shares the tick
    alone = _serve(router, prompts[:1], temperature=0.8)[0]
    router2 = create_router(params, cfg, replicas=1, family="cohere2_moe",
                            num_slots=2, max_len=64)
    _serve(router2, prompts[:1])               # same request id as `alone`
    crowd = _serve(router2, prompts, temperature=0.8)
    assert all(len(r.tokens) == GEN for r in crowd)
    assert alone.tokens != reqs[0].tokens      # sampled, not greedy
    ledger = eng.memory_ledger()
    assert ledger["kv_pool_device"] == sum(
        eng._cache[k].nbytes for k in ("k", "v", "k_win", "v_win"))
    router.close()
    router2.close()


def test_counts_ride_the_one_pull_onto_the_spans(setup):
    cfg, params = setup
    router = create_router(params, cfg, replicas=1, family="cohere2_moe",
                           num_slots=2, max_len=64)
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
    assert pulls[0] == len(ticks) + len(prefills)        # one pull each
    layers, k = cfg.num_layers, cfg.experts_per_token
    for s in prefills:
        c = s.counts
        n = c["true_len"]
        assert c["expert_choices"] == n * k * layers
        assert 0 < c["expert_tokens"] <= c["expert_choices"]
        assert c["expert_max_load"] <= n and c["experts_idle"] >= 0
        assert c["kv_positions_uniform"] == layers * n * (n + 1) // 2
        assert c["kv_positions_full"] == n * (n + 1) // 2
        assert c["kv_positions_window"] == 3 * sum(
            min(i + 1, WINDOW) for i in range(n))
    for s in ticks:
        c = s.counts
        assert c["expert_choices"] == c["active"] * k * layers
        assert c["kv_positions_window"] <= 3 * WINDOW * c["active"]
        assert c["kv_positions_window"] + c["kv_positions_full"] \
            <= c["kv_positions_uniform"]
    # both slots decoding past the window: 3 window layers admit 8 each
    both = [s.counts for s in ticks if s.counts["active"] == 2]
    assert both and both[0]["kv_positions_window"] < 3 * WINDOW * 2
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
        ServingEngine(params, cfg, family="cohere2_moe", num_slots=2,
                      max_len=64, **kw)
    assert e.value.option == option and e.value.family == "cohere2_moe"
    assert option in REFUSABLE and isinstance(e.value, ValueError)


def test_migration_and_the_journal_are_refused(setup, tmp_path):
    cfg, params = setup
    eng = ServingEngine(params, cfg, family="cohere2_moe", num_slots=2,
                        max_len=64)
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
            create_router(params, cfg, family="cohere2_moe", num_slots=2,
                          max_len=64, **{"replicas": 1, **kw})
        assert e.value.option == option


def test_family_table_and_unknown_family():
    fam = family_for("cohere2_moe")
    assert fam.counts is m.span_counts and fam.prefill is m.prefill_into_slot
    assert set(fam.refuses) == set(REFUSABLE)
    assert family_for("gpt").refuses == () and family_for("llama").counts is None
    with pytest.raises(ValueError, match=r"gpt\|llama\|cohere2_moe"):
        family_for("mamba")
