"""The jamba family (AI21-Jamba2-3B) against its plain reference
(tests/jamba_reference.py), at small sizes on the CPU in float32 with
seeded weights: the cached forward over both kinds of state, the chunked
selective scan, padding, slot reuse, idle rows, other layer patterns, the
engine at its defaults, the counts on the spans, and the options the
family refuses.

TOLERANCE. Everything is float32, the matmuls at the highest precision,
and logits are O(1). The program and the reference sum in different
orders (a running softmax over key blocks, heads folded into one K/V
group, the convolution's taps): that moves a logit by a few 1e-7. 2e-5 leaves room for that
and none for a fault: a state not carried, a padded position that
advances the state, a convolution window off by one row or a missing
inner norm each move logits by 1e-3 and more at these sizes (the last
test of the scan's section plants two of them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jamba_reference as ref
from paddle_tpu.inference.router import create_router
from paddle_tpu.inference.serving import (REFUSABLE, ServingEngine,
                                          UnsupportedOptionError,
                                          family_for)
from paddle_tpu.kernels.selective_scan import (selective_scan,
                                               selective_state_update)
from paddle_tpu.models import jamba as m
from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans
from paddle_tpu.quantization.serving import (COMPUTE_LEAVES,
                                             round_serving_params)

TOL = 2e-5
GEN = 10
BUCKET = 16             # serve_logits pads to powers of two from 8


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def make_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
                num_kv_heads=1, head_dim=8, ffn_hidden=48, max_seq_len=64,
                attn_layer_period=4, attn_layer_offset=1, mamba_d_state=4,
                mamba_dt_rank=6, dtype=jnp.float32,
                param_dtype=jnp.float32)
    base.update(kw)
    return m.JambaConfig(**base)


def make_params(cfg, seed=0):
    """Seeded weights, the matrices scaled up so that logits are O(1) and
    the recurrence's share of a mixer's output is large."""
    params = m.init_jamba_params(cfg, jax.random.PRNGKey(seed))
    return {k: v * 6.0 if k.endswith("_w") and k != "conv_w" else v
            for k, v in params.items()}


def ref_kw(cfg):
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                period=cfg.attn_layer_period, offset=cfg.attn_layer_offset,
                eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def setup():
    cfg = make_cfg()
    assert cfg.layer_types == (m.MAMBA, m.ATTENTION, m.MAMBA, m.MAMBA)
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


def _decode_on(cfg, params, cache, slot, position, forced, others=None):
    """`slot` decodes `forced`, fed back token by token from `position` on
    through the per-row-position tick every slot rides -> (logits after
    each, the pools). `others` maps other slots to (position, token) rows
    that decode alongside, LIVE; every slot else is idle."""
    slots = cache["k"].shape[1]
    step = jax.jit(m.jamba_forward_cached, static_argnums=4)
    pos, toks = np.zeros(slots, np.int32), np.zeros((slots, 1), np.int32)
    live = np.zeros((slots, 1), bool)
    live[slot] = True
    for other, (p, t) in (others or {}).items():
        pos[other], toks[other, 0], live[other] = p, t, True
    rows = []
    for i, tok in enumerate(forced):
        pos[slot], toks[slot, 0] = position + i, tok
        logits, cache = step(params, jnp.asarray(toks), cache,
                             jnp.asarray(pos), _Static(cfg),
                             live=jnp.asarray(live))
        rows.append(np.asarray(logits[slot, 0]))
        for other in (others or {}):
            pos[other] += 1
    return np.stack(rows) if rows else np.zeros((0, cfg.vocab_size)), cache


def serve_logits(cfg, params, cache, slot, prompt, forced, others=None,
                 bucket=None):
    """Prefill `prompt` into `slot` of the pools (padded to its bucket),
    then `_decode_on` with `forced` -> (logits at the last prompt position
    and after each forced token, the pools)."""
    padded = np.zeros((1, bucket or _bucket(len(prompt))), np.int32)
    padded[0, :len(prompt)] = prompt
    first, cache = jax.jit(m.prefill_into_slot, static_argnums=5)(
        params, cache, jnp.asarray(padded), jnp.int32(len(prompt)),
        jnp.int32(slot), _Static(cfg))
    rest, cache = _decode_on(cfg, params, cache, slot, len(prompt), forced,
                             others)
    return np.concatenate([np.asarray(first), rest]), cache


def reference_rows(cfg, params, prompt, forced):
    seq = np.concatenate([prompt, forced]).astype(np.int32)
    logits = ref.forward(params, jnp.asarray(seq), **ref_kw(cfg))
    return np.asarray(logits[len(prompt) - 1:])


# ------------------------------------------------------- parity, logits
@pytest.mark.parametrize("prompt_len", [1, 2, 3, BUCKET, BUCKET + 1, 21])
def test_prefill_then_decode_matches_the_reference(setup, prompt_len):
    """Shorter than the convolution (1, 2, 3 of d_conv 4), a bucket's
    edge (no padding), one past it (15 padded positions) and a length
    that is no multiple of anything; 10 decoded tokens after each."""
    cfg, params = setup
    prompt, forced = _tokens(prompt_len, 1), _tokens(GEN, 2)
    got, _ = serve_logits(cfg, params, m.init_cache(cfg, 3, 64), 1, prompt,
                          forced)
    want = reference_rows(cfg, params, prompt, forced)
    assert np.abs(want).max() > 0.3            # logits worth comparing
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("period,offset,layers", [
    (2, 1, 2),        # [mamba, attention]
    (2, 0, 4),        # attention first
    (3, 7, 3),        # no attention at all
    (1, 0, 2),        # attention everywhere
    (3, 1, 6),        # two periods of three: the scan over periods
])
def test_other_periods_and_offsets_match_the_reference(period, offset,
                                                       layers):
    cfg = make_cfg(num_layers=layers, attn_layer_period=period,
                   attn_layer_offset=offset)
    assert cfg.layer_types == ref.layer_types(layers, period, offset)
    params = make_params(cfg, 3)
    cache = m.init_cache(cfg, 2, 64)
    assert cache["k"].shape[0] == cfg.layers_of(m.ATTENTION)
    assert cache["ssm"].shape[0] == cfg.layers_of(m.MAMBA)
    prompt, forced = _tokens(11, 4), _tokens(6, 5)
    got, _ = serve_logits(cfg, params, cache, 0, prompt, forced)
    np.testing.assert_allclose(
        got, reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)


def test_a_run_continues_from_the_state_its_cache_holds(setup):
    """T > 1 twice over: the second run starts from the first's recurrent
    state and convolution rows, and reads its keys out of the pool."""
    cfg, params = setup
    seq = _tokens(29, 6)
    cache = m.init_cache(cfg, 1, 64)
    a, cache = m.jamba_forward_cached(params, jnp.asarray(seq[None, :13]),
                                      cache, 0, cfg)
    b, cache = m.jamba_forward_cached(params, jnp.asarray(seq[None, 13:]),
                                      cache, jnp.asarray([13], jnp.int32),
                                      cfg)
    want = ref.forward(params, jnp.asarray(seq), **ref_kw(cfg))
    np.testing.assert_allclose(np.concatenate([a[0], b[0]]), want, atol=TOL,
                               rtol=0)


def test_a_run_of_several_sequences_is_refused(setup):
    """A run of more than one token is the engine's prefill of ONE
    sequence; nothing batches runs (the options that would are refused),
    so the forward keeps no batched scan and says so."""
    cfg, params = setup
    with pytest.raises(ValueError, match="ONE sequence"):
        m.jamba_forward_cached(params, jnp.zeros((2, 4), jnp.int32),
                               m.init_cache(cfg, 2, 16), 0, cfg)


def test_attention_has_one_kv_head_and_no_positions(setup):
    """The pool holds ONE K/V head; and an attention layer alone carries
    no order: the last position's logits do not change when the tokens
    before it change places (one layer: under the causal mask a second
    one would see prefixes that differ)."""
    cfg, _ = setup
    assert m.init_cache(cfg, 2, 64)["k"].shape == (1, 2, 64, 1, 8)
    only = make_cfg(num_layers=1, attn_layer_period=1, attn_layer_offset=0)
    params = make_params(only, 7)
    seq = _tokens(9, 8)
    swapped = seq.copy()
    swapped[[1, 5]] = seq[[5, 1]]
    logits = [m.jamba_forward_cached(
        params, jnp.asarray(s[None]), m.init_cache(only, 1, 16), 0, only)[0]
        for s in (seq, swapped)]
    np.testing.assert_allclose(logits[0][0, -1], logits[1][0, -1],
                               atol=1e-6, rtol=0)
    # and with Mamba layers in the stack they do
    cfg, params = setup
    logits = [m.jamba_forward_cached(
        params, jnp.asarray(s[None]), m.init_cache(cfg, 1, 16), 0, cfg)[0]
        for s in (seq, swapped)]
    assert np.abs(np.asarray(logits[0][0, -1] - logits[1][0, -1])).max() \
        > 1e-3


# ------------------------------------------------------ padding, slots
def test_a_padded_prompt_leaves_the_unpadded_prompts_state(setup):
    cfg, params = setup
    prompt, forced = _tokens(13, 9), _tokens(4, 10)
    rows, caches = zip(*(serve_logits(cfg, params, m.init_cache(cfg, 2, 64),
                                      0, prompt, forced, bucket=b)
                         for b in (13, 16, 32)))
    for other, cache in zip(rows[1:], caches[1:]):
        np.testing.assert_allclose(other, rows[0], atol=2e-6, rtol=0)
        for kind in ("ssm", "conv"):
            np.testing.assert_allclose(cache[kind], caches[0][kind],
                                       atol=2e-6, rtol=0)
    # the convolution rows are the last three REAL inputs, not padding's
    assert np.asarray(caches[2]["conv"][:, 0]).any()


def test_a_reused_slot_reads_nothing_of_its_last_occupant(setup):
    """State AND keys: a long request, then a short one into its slot."""
    cfg, params = setup
    long_prompt, short_prompt = _tokens(21, 11), _tokens(3, 12)
    forced = _tokens(GEN, 13)
    _, used = serve_logits(cfg, params, m.init_cache(cfg, 2, 64), 0,
                           long_prompt, _tokens(GEN, 14))
    assert np.asarray(used["ssm"][:, 0]).any() \
        and np.asarray(used["k"][:, 0, 20]).any()
    again, _ = serve_logits(cfg, params, used, 0, short_prompt, forced)
    fresh, _ = serve_logits(cfg, params, m.init_cache(cfg, 2, 64), 0,
                            short_prompt, forced)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(
        again, reference_rows(cfg, params, short_prompt, forced),
        atol=TOL, rtol=0)


@pytest.mark.parametrize("company", ["idle", "live", "admitted"])
def test_a_request_does_not_depend_on_its_company(setup, company):
    """Slot 1's logits with slot 0 and 2 idle, decoding live beside it,
    or admitted between its ticks: always the reference's."""
    cfg, params = setup
    prompt, forced = _tokens(9, 15), _tokens(GEN, 16)
    cache = m.init_cache(cfg, 3, 64)
    others = parked = None
    if company != "idle":
        _, cache = serve_logits(cfg, params, cache, 0, _tokens(17, 17),
                                _tokens(2, 18))
        _, cache = serve_logits(cfg, params, cache, 2, _tokens(5, 19), [])
        if company == "live":
            others = {0: (19, 5), 2: (5, 7)}
        else:
            parked = {k: np.asarray(cache[k][:, 2]) for k in ("ssm", "conv")}
    first, cache = serve_logits(cfg, params, cache, 1, prompt, forced[:5],
                                others=others)
    if company == "admitted":
        # another request is admitted into slot 0 between slot 1's ticks
        _, cache = serve_logits(cfg, params, cache, 0, _tokens(30, 20), [])
    rest, cache = _decode_on(cfg, params, cache, 1, len(prompt) + 5,
                             forced[5:])
    np.testing.assert_allclose(
        np.concatenate([first, rest]),
        reference_rows(cfg, params, prompt, forced), atol=TOL, rtol=0)
    if parked:
        # slot 2 sat idle through ten ticks and an admission: its rows
        # stand as its prefill left them
        for kind, rows in parked.items():
            np.testing.assert_array_equal(rows, cache[kind][:, 2])


# ------------------------------------------------------------- the scan
def _scan_inputs(T, Di=24, N=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (T, Di)),
            jax.nn.softplus(jax.random.normal(k[1], (T, Di))),
            -jnp.exp(jax.random.normal(k[2], (N, Di))),
            jax.random.normal(k[3], (T, N)), jax.random.normal(k[4], (T, N)),
            jax.random.normal(k[5], (Di,)), jax.random.normal(k[6], (N, Di)))


def _sequential(x, delta, A, B, C, D, s0, length):
    s, ys = s0, []
    for t in range(length):
        y, s = selective_state_update(s[None], x[t][None], delta[t][None],
                                      A, B[t][None], C[t][None], D)
        s = s[0]
        ys.append(y[0])
    return jnp.stack(ys), s


@pytest.mark.parametrize("T,length,chunk", [
    (37, 37, 8),        # no multiple of the chunk
    (37, 30, 8),        # padded: positions 30.. must not move the state
    (32, 32, 8),        # whole chunks
    (5, 1, 64),         # one real position in one short chunk
    (40, 40, 1),        # a chunk a position
])
def test_the_chunked_scan_equals_the_sequential_one(T, length, chunk):
    args = _scan_inputs(T)                      # s0 is not zero
    want_y, want_s = _sequential(*args, length)
    y, s = selective_scan(*args, jnp.int32(length), chunk)
    assert y.shape == (T, 24)
    np.testing.assert_allclose(y[:length], want_y, atol=5e-6, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=5e-6, rtol=0)
    zero = args[:-1] + (jnp.zeros_like(args[-1]),)
    y0, _ = selective_scan(*zero, jnp.int32(length), chunk)
    assert np.abs(np.asarray(y0[:length] - want_y)).max() > 1e-2


def test_the_state_update_leaves_an_idle_row_alone():
    x, delta, A, B, C, D, s0 = _scan_inputs(3)
    s = jnp.stack([s0, 2 * s0, 3 * s0])
    live = jnp.asarray([True, False, True])
    y, new = selective_state_update(s, x, delta, A, B, C, D, live)
    np.testing.assert_array_equal(new[1], s[1])
    for r in (0, 2):
        want = jnp.exp(delta[r][None] * A) * s[r] \
            + (delta[r] * x[r])[None] * B[r][:, None]
        np.testing.assert_allclose(new[r], want, atol=1e-6, rtol=0)
        np.testing.assert_allclose(
            y[r], (C[r][:, None] * want).sum(0) + D * x[r], atol=1e-5,
            rtol=0)


@pytest.mark.parametrize("fault", ["padding_advances", "window_shifted"])
def test_a_planted_fault_moves_the_logits_past_the_tolerance(setup, fault,
                                                             monkeypatch):
    """What the tolerance is for: the same comparison with the scan told
    the bucket for the length, or the convolution rows taken one late."""
    cfg, params = setup
    prompt, forced = _tokens(11, 21), _tokens(4, 22)
    want = reference_rows(cfg, params, prompt, forced)
    if fault == "padding_advances":
        real = m.selective_scan
        monkeypatch.setattr(
            m, "selective_scan",
            lambda *a, chunk: real(*a[:-1], jnp.int32(a[0].shape[0]),
                                   chunk=chunk))
    else:
        real = jax.lax.dynamic_slice_in_dim
        monkeypatch.setattr(
            jax.lax, "dynamic_slice_in_dim",
            lambda a, n, size, axis=0: real(a, n + 1, size, axis)
            if size == cfg.mamba_d_conv - 1 else real(a, n, size, axis))
    jax.clear_caches()          # the faulted trace is no other test's
    try:
        got, _ = serve_logits(cfg, params, m.init_cache(cfg, 1, 64), 0,
                              prompt, forced)
    finally:
        jax.clear_caches()
    assert np.abs(got[1:] - want[1:]).max() > 50 * TOL


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
    tick, slots reused (6 requests over 2 slots, prompts shorter than the
    convolution among them), greedy tokens equal to the reference's
    argmax wherever that is not a near-tie."""
    cfg, params = setup
    router = create_router(params, cfg, replicas=1, family="jamba",
                           num_slots=2, max_len=64)
    eng = router.replicas[0].eng
    assert not eng.paged and not eng.spec and eng.mt_k == 1 \
        and not eng.quant and eng.mesh is None
    prompts = [_tokens(n, 20 + n) for n in (5, 21, 1, 3, 30, 16)]
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
    assert len(alone.tokens) == GEN and alone.tokens != reqs[0].tokens
    ledger = eng.memory_ledger()
    assert ledger["kv_pool_device"] == sum(
        eng._cache[k].nbytes for k in ("k", "v", "ssm", "conv"))
    assert eng._cache["ssm"].dtype == jnp.float32
    assert ledger["total"] == ledger["weights"] + ledger["kv_pool_device"]
    router.close()


def test_counts_ride_the_one_pull_onto_the_spans(setup):
    cfg, params = setup
    router = create_router(params, cfg, replicas=1, family="jamba",
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
    assert ticks and len(prefills) == 2
    assert pulls[0] == len(ticks) + len(prefills)        # one pull each
    mamba = cfg.layers_of(m.MAMBA)
    for s in prefills:
        assert s.counts["scan_chunks"] == mamba * -(
            -s.counts["bucket"] // m.SCAN_CHUNK)
        assert "state_bytes" not in s.counts
    state = m.slot_state_bytes(cfg)
    assert state == mamba * (4 * 64 * 4 + 3 * 64 * 4)
    for s in ticks:
        c = s.counts
        assert "scan_chunks" not in c
        # both slots' rows are read and written, live or not; the masked
        # einsum reads every position of every slot
        assert c["state_bytes"] == 2 * 2 * state
        assert c["kv_positions_read"] == c["kv_positions_pool"] == 2 * 64
        assert c["kv_bytes"] == 2 * 64 * 2 * 8 * 4
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
        ServingEngine(params, cfg, family="jamba", num_slots=2, max_len=64,
                      **kw)
    assert e.value.option == option and e.value.family == "jamba"
    assert option in REFUSABLE and isinstance(e.value, ValueError)


def test_migration_and_the_journal_are_refused(setup, tmp_path):
    cfg, params = setup
    eng = ServingEngine(params, cfg, family="jamba", num_slots=2,
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
            create_router(params, cfg, family="jamba", num_slots=2,
                          max_len=64, **{"replicas": 1, **kw})
        assert e.value.option == option


def test_the_family_table_and_the_leaves_rounded_at_build(setup):
    fam = family_for("jamba")
    assert fam.counts is m.span_counts and fam.prefill is m.prefill_into_slot
    assert fam.forward_cached is m.jamba_forward_cached
    assert set(fam.refuses) == set(REFUSABLE) and fam.serving_specs is None
    with pytest.raises(ValueError, match="jamba"):
        family_for("mamba")
    # every leaf is either rounded to the compute dtype at build or kept
    # float32: a float32 tree handed to a bf16 engine, and a bf16 tree
    cfg, params = setup
    assert set(COMPUTE_LEAVES["jamba"]) | set(m.F32_LEAVES) == set(params)
    assert not set(COMPUTE_LEAVES["jamba"]) & set(m.F32_LEAVES)
    bf16 = make_cfg(dtype=jnp.bfloat16)
    rounded = round_serving_params(params, "jamba", bf16)
    for name, leaf in rounded.items():
        assert leaf.dtype == (jnp.float32 if name in m.F32_LEAVES
                              else jnp.bfloat16), name
    assert round_serving_params(rounded, "jamba", bf16) is rounded
    stored = m.init_jamba_params(make_cfg(param_dtype=jnp.bfloat16),
                                 jax.random.PRNGKey(0))
    assert {n for n, v in stored.items() if v.dtype == jnp.float32} \
        == set(m.F32_LEAVES)
    assert round_serving_params(stored, "jamba", bf16) is stored
