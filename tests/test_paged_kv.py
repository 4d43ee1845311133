"""Paged KV cache tests (inference/serving.py paged layout +
kernels/decode_attention.py gather_pages/write_kv_paged).

Reference analog: vLLM's PagedAttention block manager (SOSP '23) and
SGLang's RadixAttention prefix cache, realized TPU-native: fixed-size
pages + device page tables with all gather/scatter inside the jitted
tick, host-side refcounted allocation, prompt-prefix-hash sharing with
copy-on-write, and chunked prefill interleaved with decode.

The load-bearing guarantees:
- paged token streams are BIT-IDENTICAL to the dense slot pool (and
  therefore to per-request greedy decode) for gpt AND llama/GQA,
  with and without prefix sharing, COW, and chunked prefill;
- COW isolation: a writer diverging into a shared page never perturbs
  the sharer's stream;
- refcount/free accounting stays exact across join/evict/cancel
  churn (every page in exactly one of free/cached/live, table refs
  == refcounts, reservations conserved);
- pool exhaustion queues (or raises the typed PoolExhaustedError for
  never-fits requests) — no wedged slot, every request resolves;
- the trace ceilings hold: decode <= 2, prefill one per (chunk
  bucket, sampling mode).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.serving import (ServingEngine,
                                          PoolExhaustedError)
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
from paddle_tpu.models import llama as llama_mod

MAXLEN = 64
PS = 8          # test page size


def _gpt_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, ffn_hidden=64, max_seq_len=128,
                     sequence_parallel=False, remat=False,
                     dtype=jnp.float32)


def _llama_cfg():
    return llama_mod.LlamaConfig(vocab_size=64, hidden_size=32,
                                 num_layers=2, num_heads=4,
                                 num_kv_heads=2, max_seq_len=128,
                                 dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = _gpt_cfg()
    return cfg, init_gpt_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def llama_setup():
    cfg = _llama_cfg()
    return cfg, llama_mod.init_llama_params(cfg, jax.random.PRNGKey(0))


def _prompts(lens, seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, L).astype(np.int32) for L in lens]


def _dense(params, cfg, family="gpt", **kw):
    kw.setdefault("num_slots", 3)
    return ServingEngine(params, cfg, family=family, max_len=MAXLEN, **kw)


def _paged(params, cfg, family="gpt", **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("page_size", PS)
    return ServingEngine(params, cfg, family=family, max_len=MAXLEN,
                         kv_layout="paged", **kw)


def _check_pool(eng):
    """The refcount/free accounting invariant: every page is in
    exactly one of {free, cached, live}; table references match
    refcounts exactly; reservations are conserved; the prefix maps
    are mutual inverses."""
    pool = eng._pool
    refs = np.zeros(pool.num_pages, np.int64)
    refs[0] = 1                                  # scratch pin
    for row in eng._ptab:
        for pid in row[row != 0]:
            refs[pid] += 1
    np.testing.assert_array_equal(refs, pool.ref)
    free, cached = set(pool.free), set(pool.cached)
    live = {i for i in range(1, pool.num_pages) if pool.ref[i] > 0}
    assert not (free & cached) and not (free & live) \
        and not (cached & live)
    assert len(free) + len(cached) + len(live) == pool.num_pages - 1
    assert pool.reserved == int(eng._slot_reserve.sum())
    assert pool.by_key == {v: k for k, v in pool.key_of.items()}
    assert all(pool.ref[p] == 0 for p in cached)


# --------------------------------------------------------------------------
# kernel seam: gather/scatter vs the dense write
# --------------------------------------------------------------------------
class TestPagedKernels:
    def test_scatter_gather_roundtrip_matches_dense(self):
        from paddle_tpu.kernels.decode_attention import (
            gather_pages, write_kv, write_kv_paged)
        rng = np.random.RandomState(0)
        B, S, KV, hd, ps = 2, 32, 2, 4, 8
        mp = S // ps
        # per-row positions mid-stream, one-token write (decode shape)
        pos = jnp.asarray([5, 17], jnp.int32)
        k = jnp.asarray(rng.randn(B, 1, KV, hd), jnp.float32)
        dense0 = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
        dense = write_kv(dense0, k, pos)
        # paged mirror: each row owns mp consecutive pages holding the
        # same initial contents
        pages = jnp.concatenate(
            [jnp.zeros((1, ps, KV, hd), jnp.float32),       # scratch
             dense0.reshape(B * mp, ps, KV, hd)], 0)
        table = jnp.arange(1, B * mp + 1, dtype=jnp.int32).reshape(B, mp)
        pages = write_kv_paged(pages, table, k, pos)
        np.testing.assert_array_equal(
            np.asarray(gather_pages(pages, table)), np.asarray(dense))

    def test_out_of_table_positions_hit_scratch(self):
        from paddle_tpu.kernels.decode_attention import write_kv_paged
        B, KV, hd, ps, mp = 1, 1, 2, 4, 2
        pages = jnp.zeros((3, ps, KV, hd), jnp.float32)
        table = jnp.asarray([[1, 2]], jnp.int32)
        k = jnp.ones((B, 1, KV, hd), jnp.float32)
        # position past the table: must land in scratch page 0, not
        # clamp onto the real tail page
        out = write_kv_paged(pages, table, k, jnp.asarray([ps * mp + 1],
                                                          jnp.int32))
        assert np.asarray(out[1:]).sum() == 0.0
        assert np.asarray(out[0]).sum() != 0.0


# --------------------------------------------------------------------------
# bit-parity vs the dense pool
# --------------------------------------------------------------------------
class TestPagedParity:
    def test_gpt_parity_mixed_lengths(self, gpt_setup):
        cfg, params = gpt_setup
        prompts = _prompts([3, 11, 25, 40, 7, 18], seed=1)
        want = _dense(params, cfg).generate(prompts, 8)
        got = _paged(params, cfg).generate(prompts, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_llama_gqa_parity(self, llama_setup):
        cfg, params = llama_setup
        prompts = _prompts([3, 11, 25, 40], seed=2)
        want = _dense(params, cfg, family="llama").generate(prompts, 8)
        got = _paged(params, cfg, family="llama",
                     prefill_chunk=PS).generate(prompts, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_sampled_stream_parity(self, gpt_setup):
        """Sampled streams key on (request id, token index) — layout
        must not perturb them."""
        cfg, params = gpt_setup
        prompts = _prompts([5, 9, 14], seed=3)
        a = _dense(params, cfg, max_top_k=8).generate(
            prompts, 6, temperature=0.8, top_k=5)
        b = _paged(params, cfg, max_top_k=8).generate(
            prompts, 6, temperature=0.8, top_k=5)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# --------------------------------------------------------------------------
# prefix sharing + copy-on-write
# --------------------------------------------------------------------------
class TestPrefixSharing:
    def test_shared_prefix_pages_reused(self, gpt_setup):
        cfg, params = gpt_setup
        rng = np.random.RandomState(7)
        system = rng.randint(0, 64, 3 * PS).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.randint(0, 64, k).astype(np.int32)])
            for k in (2, 3, 4)]
        eng = _paged(params, cfg)
        want = _dense(params, cfg).generate(prompts, 6)
        reqs = [eng.submit(p, 6) for p in prompts]
        eng.step()                       # all three admit
        # sharer requests found the first request's registered pages
        assert reqs[1].shared_tokens == 3 * PS
        assert reqs[2].shared_tokens == 3 * PS
        assert eng.pool_stats()["pages_shared"] >= 3
        _check_pool(eng)
        eng.drain()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), w)
        _check_pool(eng)

    def test_cached_pages_survive_request_death(self, gpt_setup):
        """RadixAttention-style cross-request reuse: the donor
        finishes, its registered pages park in the LRU cache, and a
        later identical prefix maps them without recompute."""
        cfg, params = gpt_setup
        prompt = _prompts([2 * PS + 3], seed=8)[0]
        eng = _paged(params, cfg)
        first = eng.generate([prompt], 6)[0]
        assert eng.pool_stats()["pages_cached"] >= 2
        r2 = eng.submit(prompt, 6)
        eng.drain()
        assert r2.shared_tokens == 2 * PS
        np.testing.assert_array_equal(np.asarray(r2.tokens, np.int32),
                                      first)
        _check_pool(eng)

    def test_cow_isolation_writer_vs_sharer(self, gpt_setup):
        """Two identical page-aligned prompts: the second COWs the
        last shared page and writes into its private copy; BOTH
        streams must equal the dense stream (the sharer is never
        perturbed by the writer)."""
        cfg, params = gpt_setup
        prompt = _prompts([2 * PS], seed=9)[0]       # page-aligned
        want = _dense(params, cfg).generate([prompt], 8)[0]
        eng = _paged(params, cfg)
        ra = eng.submit(prompt, 8)
        rb = eng.submit(prompt, 8)
        cow0 = eng.pool_stats()["cow_copies"]
        eng.drain()
        assert eng.pool_stats()["cow_copies"] > cow0
        np.testing.assert_array_equal(np.asarray(ra.tokens, np.int32),
                                      want)
        np.testing.assert_array_equal(np.asarray(rb.tokens, np.int32),
                                      want)
        _check_pool(eng)

    def test_midprefill_slot_never_writes_shared_pages(self, gpt_setup):
        """The decode tick computes ALL rows (fixed shape) — a slot
        mid-chunked-prefill is inactive but its table already maps
        REAL (possibly shared) pages, so its discarded row's K/V
        write must route to the scratch page, never through the
        table: the pool is shared across rows, and a stray scatter
        into a shared prefix page corrupts every co-batched sharer
        bit-stream (the dense layout is immune — each row owns its
        cache row outright)."""
        cfg, params = gpt_setup
        rng = np.random.RandomState(19)
        system = rng.randint(0, 64, 2 * PS).astype(np.int32)
        pa = np.concatenate([system,
                             rng.randint(0, 64, 3).astype(np.int32)])
        pb = np.concatenate([system,
                             rng.randint(0, 64, 3 * PS)
                             .astype(np.int32)])
        want_a = _dense(params, cfg).generate([pa], 12)[0]
        want_b = _dense(params, cfg).generate([pb], 4)[0]
        eng = _paged(params, cfg, prefill_chunk=PS)
        ra = eng.submit(pa, 12)
        while not ra.tokens:                 # chunked prefill of A
            eng.step()
        pids = [int(p) for p in eng._ptab[ra.slot, :2]]
        assert 0 not in pids                 # A's registered prefix
        snap = np.asarray(eng._cache["k"])[:, pids].copy()
        rb = eng.submit(pb, 4)               # maps A's shared pages,
        #                                      long suffix -> chunks
        ticks_mid_prefill = 0
        while not rb.tokens and not rb.done:
            eng.step()                       # A decodes; B inactive
            np.testing.assert_array_equal(
                np.asarray(eng._cache["k"])[:, pids], snap,
                err_msg="mid-prefill slot scattered into shared pages")
            ticks_mid_prefill += 1
        assert ticks_mid_prefill >= 2        # B really interleaved
        eng.drain()
        np.testing.assert_array_equal(
            np.asarray(ra.tokens, np.int32), want_a)
        np.testing.assert_array_equal(
            np.asarray(rb.tokens, np.int32), want_b)
        _check_pool(eng)

    def test_prefix_hashes_memoized_per_request(self, gpt_setup,
                                                monkeypatch):
        """The head-of-line admission plan runs EVERY tick while a
        request waits for pages — the per-page prefix digests must be
        hashed once per request, not once per tick."""
        import paddle_tpu.inference.serving as srv
        calls = {"n": 0}
        real = srv._prefix_key

        def counting(prompt, n):
            calls["n"] += 1
            return real(prompt, n)

        monkeypatch.setattr(srv, "_prefix_key", counting)
        cfg, params = gpt_setup
        eng = _paged(params, cfg, num_slots=2, num_pages=6)
        occupant = eng.submit(_prompts([4], seed=21)[0], 20)
        eng.step()                      # occupant reserves 3 pages
        waiter = eng.submit(_prompts([4 * PS], seed=22)[0], 4)
        calls["n"] = 0
        for _ in range(10):             # waiter replans head-of-line
            eng.step()
        assert not waiter.tokens        # still waiting for pages
        assert calls["n"] <= len(waiter.prompt) // PS
        eng.drain()
        assert occupant.done and waiter.done
        _check_pool(eng)

    def test_sharing_kill_switch(self, gpt_setup):
        cfg, params = gpt_setup
        prompt = _prompts([2 * PS], seed=10)[0]
        eng = _paged(params, cfg, prefix_sharing=False)
        eng.generate([prompt], 4)
        r2 = eng.submit(prompt, 4)
        eng.drain()
        assert r2.shared_tokens == 0
        assert eng.pool_stats()["pages_cached"] == 0
        _check_pool(eng)


# --------------------------------------------------------------------------
# refcount / free correctness across churn
# --------------------------------------------------------------------------
class TestPoolAccounting:
    def test_join_evict_cancel_churn(self, gpt_setup):
        cfg, params = gpt_setup
        rng = np.random.RandomState(11)
        system = rng.randint(0, 64, 2 * PS).astype(np.int32)
        eng = _paged(params, cfg, num_slots=3)
        live = []
        for wave in range(6):
            # mix of shared-prefix and unique prompts joining mid-decode
            if wave % 2 == 0:
                p = np.concatenate(
                    [system, rng.randint(0, 64, wave + 2)
                     .astype(np.int32)])
            else:
                p = rng.randint(0, 64, 5 + wave).astype(np.int32)
            live.append(eng.submit(p, 10))
            eng.step()
            _check_pool(eng)
            if wave == 2:
                assert live[0].cancel()            # mid-decode cancel
                _check_pool(eng)
            if wave == 4:
                eng.abort_pending("evicted")       # mass eviction
                _check_pool(eng)
        eng.drain()
        _check_pool(eng)
        assert all(r.done for r in live)
        assert eng.pool_stats()["pages_in_use"] == 0
        assert eng._pool.reserved == 0

    def test_hard_reset_rebuilds_pool(self, gpt_setup):
        cfg, params = gpt_setup
        eng = _paged(params, cfg)
        r = eng.submit(_prompts([12])[0], 20)
        eng.step()
        eng._hard_reset("test")
        assert r.done and r.finish_reason == "evicted"
        _check_pool(eng)
        st = eng.pool_stats()
        assert st["pages_in_use"] == 0 and st["pages_cached"] == 0
        # the rebuilt pool serves cleanly
        out = eng.generate(_prompts([9], seed=12), 4)
        assert len(out[0]) == 4
        _check_pool(eng)


# --------------------------------------------------------------------------
# pool exhaustion
# --------------------------------------------------------------------------
class TestPoolExhaustion:
    def test_never_fits_raises_typed(self, gpt_setup):
        cfg, params = gpt_setup
        eng = _paged(params, cfg, num_pages=4)    # 3 allocatable pages
        with pytest.raises(PoolExhaustedError) as ei:
            eng.submit(_prompts([30])[0], 20)     # needs 7 pages
        assert ei.value.pages_needed > ei.value.pages_total

    def test_exhausted_admission_queues_never_wedges(self, gpt_setup):
        """More concurrent demand than pages: later requests WAIT
        (stay queued) and admit as earlier ones free their pages —
        every request completes with the full dense-equal stream."""
        cfg, params = gpt_setup
        prompts = _prompts([12, 14, 10, 9, 13, 11], seed=13)
        want = _dense(params, cfg, num_slots=6).generate(prompts, 10)
        # pages for ~2 requests in flight (each needs ceil(21/8)=3..4)
        eng = _paged(params, cfg, num_slots=6, num_pages=9)
        reqs = [eng.submit(p, 10) for p in prompts]
        eng.step()
        assert sum(1 for r in eng._slot_req if r is not None) < 6
        _check_pool(eng)
        eng.drain()
        _check_pool(eng)
        for r, w in zip(reqs, want):
            assert r.done and r.finish_reason in ("length", "eos")
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), w)

    def test_aligned_full_rejoin_exact_pool_never_livelocks(
            self, gpt_setup):
        """A pool sized EXACTLY to the request envelope: re-submitting
        an identical page-aligned prompt finds an aligned-full cached
        match, whose COW page costs envelope + 1 — impossible here
        forever. The planner must fall back to unshared admission
        (the envelope fits by the submit() guard) instead of queueing
        the request into a livelock."""
        cfg, params = gpt_setup
        prompt = _prompts([PS], seed=20)[0]          # page-aligned
        envelope = -(-(PS + 9 - 1) // PS)            # 2 pages
        eng = _paged(params, cfg, num_slots=1,
                     num_pages=envelope + 1)         # exactly envelope
        first = eng.generate([prompt], 9)[0]
        assert eng.pool_stats()["pages_cached"] == 1  # prefix parked
        r2 = eng.submit(prompt, 9)
        eng.drain(max_ticks=100)
        assert r2.done and r2.finish_reason in ("length", "eos"), \
            "aligned-full match wedged an exactly-sized pool"
        np.testing.assert_array_equal(
            np.asarray(r2.tokens, np.int32), first)
        _check_pool(eng)


# --------------------------------------------------------------------------
# chunked prefill
# --------------------------------------------------------------------------
class TestChunkedPrefill:
    def test_chunked_parity_and_trace_ceiling(self, gpt_setup):
        import math
        cfg, params = gpt_setup
        prompts = _prompts([40, 3, 33, 17], seed=14)
        want = _dense(params, cfg).generate(prompts, 8)
        eng = _paged(params, cfg, prefill_chunk=PS)
        got = eng.generate(prompts, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        from paddle_tpu.profiler import monitor
        assert monitor.counter("serving.prefill_chunks").value > 0
        dec, pre = eng.trace_counts()
        assert dec <= 2
        assert pre <= 2 * int(math.log2(MAXLEN))

    def test_decode_interleaves_with_long_prefill(self, gpt_setup):
        """The SLO story: while a long prompt prefills chunk-by-chunk,
        co-batched decode streams keep emitting EVERY tick — the
        inter-token gap is bounded by one chunk, not the whole
        prompt."""
        cfg, params = gpt_setup
        eng = _paged(params, cfg, prefill_chunk=PS)
        short = eng.submit(_prompts([4], seed=15)[0], 30)
        eng.step()                                 # short active
        long_req = eng.submit(_prompts([40], seed=16)[0], 4)
        eng.step()                                 # long admits, chunking
        assert long_req._pf_next is not None       # mid-prefill
        ticks_while_prefilling = 0
        while long_req._pf_next is not None and not long_req.done:
            n0 = len(short.tokens)
            eng.step()
            if not short.done:
                assert len(short.tokens) == n0 + 1, \
                    "co-batched stream stalled during chunked prefill"
                ticks_while_prefilling += 1
        assert ticks_while_prefilling >= 2        # 40-4=36 tokens / 8
        eng.drain()
        # and the long stream still matches dense
        want = _dense(params, cfg).generate(
            [_prompts([40], seed=16)[0]], 4)[0]
        np.testing.assert_array_equal(
            np.asarray(long_req.tokens, np.int32), want)

    def test_cancel_mid_chunked_prefill_frees_pages(self, gpt_setup):
        cfg, params = gpt_setup
        eng = _paged(params, cfg, prefill_chunk=PS)
        r = eng.submit(_prompts([40], seed=17)[0], 4)
        eng.step()
        assert r._pf_next is not None              # mid-prefill
        assert r.cancel()
        assert r.finish_reason == "cancelled"
        _check_pool(eng)
        assert eng.pool_stats()["pages_in_use"] == 0
        eng.drain()
        _check_pool(eng)


# --------------------------------------------------------------------------
# kv-pool telemetry surface
# --------------------------------------------------------------------------
class TestPoolTelemetry:
    def test_gauges_and_report_block(self, gpt_setup, tmp_path):
        cfg, params = gpt_setup
        from paddle_tpu.profiler import monitor
        eng = _paged(params, cfg, prefill_chunk=PS)
        prompt = _prompts([2 * PS], seed=18)[0]
        cow0 = monitor.counter("serving.cow_copies").value
        path = str(tmp_path / "tele.jsonl")
        monitor.registry().export_jsonl(path)      # report baseline
        eng.generate([prompt], 6)                  # donor registers
        eng.submit(prompt, 6)                      # shares + COWs
        eng.step()
        snap = monitor.snapshot()
        assert snap["serving.pages_in_use"] > 0
        assert snap["serving.cow_copies"] >= cow0 + 1
        eng.drain()
        monitor.registry().export_jsonl(path)
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from telemetry_report import summarize
        srv = summarize(path).get("serving", {})
        assert "kv_pool" in srv
        assert srv["kv_pool"]["cow_copies"] >= 1
        assert srv["kv_pool"]["prefill_chunks"] >= 1


# --------------------------------------------------------------------------
# speculative decode: gamma-token writes + rejected-page rollback
# --------------------------------------------------------------------------
class TestSpecMultiTokenWrites:
    def test_gamma_token_paged_write_matches_sequential(self):
        """A gamma+1-token write_kv_paged (the speculative verify
        pass's shape) must land byte-identical to gamma+1 sequential
        single-token writes — including the rows that cross a page
        boundary mid-block."""
        from paddle_tpu.kernels.decode_attention import write_kv_paged
        rng = np.random.RandomState(3)
        B, KV, hd, ps, mp, T = 2, 2, 4, 8, 4, 5
        pages0 = jnp.asarray(rng.randn(1 + B * mp, ps, KV, hd),
                             jnp.float32)
        table = jnp.arange(1, B * mp + 1, dtype=jnp.int32).reshape(B, mp)
        pos = jnp.asarray([6, 13], jnp.int32)      # both cross a page
        k = jnp.asarray(rng.randn(B, T, KV, hd), jnp.float32)
        got = write_kv_paged(pages0, table, k, pos)
        seq = pages0
        for t in range(T):
            seq = write_kv_paged(seq, table, k[:, t:t + 1], pos + t)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))

    def test_gamma_token_dense_write_drops_past_cache_end(self):
        """Per-row multi-token dense writes (write_kv, T > 1) must DROP
        positions past the cache end — dynamic_update_slice's clamping
        would shift the whole block down and corrupt the row's tail."""
        from paddle_tpu.kernels.decode_attention import write_kv
        rng = np.random.RandomState(4)
        B, S, KV, hd, T = 2, 16, 1, 2, 4
        kc0 = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, KV, hd), jnp.float32)
        pos = jnp.asarray([S - 2, 3], jnp.int32)   # row 0: 2 of 4 OOB
        out = np.asarray(write_kv(kc0, k, pos))
        want = np.asarray(kc0).copy()
        want[0, S - 2:] = np.asarray(k)[0, :2]     # in-range only
        want[1, 3:3 + T] = np.asarray(k)[1]
        np.testing.assert_array_equal(out, want)

    def test_spec_rollback_keeps_shared_pages_and_accounting(
            self, gpt_setup):
        """The satellite guarantee: gamma-token verify writes +
        rejected-token page rollback leave (a) shared/COW prefix pages
        byte-identical to the single-token path and (b) the pool
        accounting identical between ticks — speculation never inflates
        a slot's page footprint or starves other admissions."""
        cfg, params = gpt_setup
        rng = np.random.RandomState(23)
        system = rng.randint(0, 64, 2 * PS).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.randint(0, 64, k).astype(np.int32)])
            for k in (2, 3)]
        want = _dense(params, cfg).generate(prompts, 8)

        # the single-token paged reference: pool + shared-page bytes
        ref = _paged(params, cfg)
        ref_reqs = [ref.submit(p, 8) for p in prompts]
        ref.drain()
        ref_pids = sorted(ref._pool.by_key.values())
        ref_pages = np.asarray(ref._cache["k"])[:, ref_pids].copy()
        ref_stats = ref.pool_stats()

        eng = _paged(params, cfg, spec_decode="spec", gamma=3,
                     draft_layers=cfg.num_layers)
        reqs = [eng.submit(p, 8) for p in prompts]
        while eng.has_work():
            eng.step()
            _check_pool(eng)
            # between ticks no slot may hold a page past its live
            # position (the rollback invariant)
            for i in np.nonzero(eng._active)[0]:
                row = eng._ptab[i]
                first = -(-int(eng._positions[i]) // PS)
                assert not row[first:].any(), (
                    "speculative pages survived the rollback: "
                    f"slot {i} row {row.tolist()} pos "
                    f"{eng._positions[i]}")
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), w)
        pids = sorted(eng._pool.by_key.values())
        np.testing.assert_array_equal(
            np.asarray(eng._cache["k"])[:, pids], ref_pages,
            err_msg="spec decode perturbed shared prefix pages")
        got_stats = eng.pool_stats()
        for key in ("pages_in_use", "pages_cached", "pages_shared",
                    "pages_reserved"):
            assert got_stats[key] == ref_stats[key], (key, got_stats,
                                                      ref_stats)

    def test_spec_cow_sharer_isolated_from_speculating_writer(
            self, gpt_setup):
        """A speculating writer COWs into a shared page exactly like
        the single-token path: the sharer's stream and the registered
        page bytes stay untouched while the writer's verify scatters
        gamma+1 tokens per tick."""
        cfg, params = gpt_setup
        prompt = _prompts([2 * PS], seed=24)[0]        # page-aligned
        want = _dense(params, cfg).generate([prompt], 8)[0]
        eng = _paged(params, cfg, spec_decode="spec", gamma=4,
                     draft_layers=cfg.num_layers)
        ra = eng.submit(prompt, 8)
        rb = eng.submit(prompt, 8)                     # aligned-full COW
        cow0 = eng.pool_stats()["cow_copies"]
        eng.drain()
        assert eng.pool_stats()["cow_copies"] > cow0
        for r in (ra, rb):
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), want)
        _check_pool(eng)
