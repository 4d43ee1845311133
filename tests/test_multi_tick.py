"""Disaggregated-decode tests: fused multi-tick decode
(inference/multi_tick.py), the host-tier KV offload
(inference/host_kv.py) and the prefill/decode role split
(inference/router.py roles=).

Reference analog: the inference decoder loops of
incubate/nn/layer/fused_transformer.py:1022 (one token per full
forward) — here K decode ticks fuse into ONE jitted lax.scan so the
host pays one dispatch + one pull per K tokens.

The load-bearing guarantees:
- multi-tick streams (greedy AND sampled) are BIT-IDENTICAL to the
  single-tick engine at every K, across dense / paged / speculative /
  tensor-parallel layouts — the scan step IS `_decode_tick`'s math;
- one dispatch (== one host pull) per K tokens: serving.decode_ticks
  counts dispatches, so a gen-G stream costs ceil(G/K) of them;
- the trace ceilings survive: one decode trace for a greedy-only
  workload, zero recompiles after warmup;
- K joins the facade engine cache key (switching K rebuilds, same K
  reuses);
- selection: the multi_tick= argument alone (0 / "auto" is K=1);
- host tier: prefix hits BEYOND the device pool's capacity come back
  from host RAM (swap-in, zero re-prefill of those pages) with
  bit-identical streams, and the memory ledger prices the tier as
  kv_pool_host (host RAM) outside the device total;
- role split: every stream hands off prefill -> decode exactly once
  with zero re-prefilled tokens; losing the prefill replica degrades
  to shared duty, never to stuck requests.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference import multi_tick as mt
from paddle_tpu.inference.host_kv import HostKVTier
from paddle_tpu.models.gpt import GPTConfig, init_gpt_params
from paddle_tpu.profiler import monitor

MAXLEN = 64


def _gpt_cfg():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, ffn_hidden=64, max_seq_len=128,
                     sequence_parallel=False, remat=False,
                     dtype=jnp.float32)


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = _gpt_cfg()
    return cfg, init_gpt_params(cfg, jax.random.PRNGKey(0))


def _prompts(lens, seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab - 1, L).astype(np.int32) for L in lens]


def _eng(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    return ServingEngine(params, cfg, family="gpt", max_len=MAXLEN, **kw)


def _ticks():
    return monitor.counter("serving.decode_ticks").value


# ------------------------------------------------------------ selection
@pytest.mark.smoke
class TestResolve:
    def test_default_off(self):
        assert mt.resolve_multi_tick(0) == 1
        assert mt.resolve_multi_tick("auto") == 1

    def test_explicit_knob(self):
        assert mt.resolve_multi_tick(4) == 4
        assert mt.resolve_multi_tick(1) == 1

    def test_negative_raises(self):
        with pytest.raises(ValueError):
            mt.resolve_multi_tick(-2)

    def test_host_kv_resolve(self, gpt_setup):
        cfg, params = gpt_setup
        assert _eng(params, cfg, host_kv_bytes=1 << 20).host_kv_bytes \
            == 1 << 20
        assert _eng(params, cfg).host_kv_bytes == 0
        with pytest.raises(ValueError):
            _eng(params, cfg, host_kv_bytes=-1)


# ------------------------------------------------------ stream parity
@pytest.mark.smoke
class TestParity:
    LENS = (5, 7, 6)

    @pytest.mark.parametrize("k", [1, 4, 8])
    def test_dense_greedy(self, gpt_setup, k):
        cfg, params = gpt_setup
        prompts = _prompts(self.LENS)
        want = _eng(params, cfg).generate(prompts, 12)
        got = _eng(params, cfg, multi_tick=k).generate(prompts, 12)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("k", [4, 8])
    def test_dense_sampled(self, gpt_setup, k):
        cfg, params = gpt_setup
        prompts = _prompts(self.LENS, seed=3)
        kw = dict(max_top_k=8)
        want = _eng(params, cfg, **kw).generate(
            prompts, 10, temperature=0.8, top_k=8)
        got = _eng(params, cfg, multi_tick=k, **kw).generate(
            prompts, 10, temperature=0.8, top_k=8)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("k", [4])
    def test_paged(self, gpt_setup, k):
        cfg, params = gpt_setup
        prompts = _prompts(self.LENS, seed=1)
        kw = dict(kv_layout="paged", page_size=8)
        want = _eng(params, cfg, **kw).generate(prompts, 12)
        got = _eng(params, cfg, multi_tick=k, **kw).generate(prompts, 12)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("k", [2, 4])
    def test_spec(self, gpt_setup, k):
        cfg, params = gpt_setup
        prompts = _prompts(self.LENS, seed=2)
        kw = dict(kv_layout="paged", page_size=8, spec_decode="spec",
                  gamma=2, draft_layers=cfg.num_layers)
        want = _eng(params, cfg).generate(prompts, 12)
        got = _eng(params, cfg, multi_tick=k, **kw).generate(prompts, 12)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)

    def test_tp(self, gpt_setup):
        from paddle_tpu.parallel.mesh import build_mesh
        cfg, params = gpt_setup
        prompts = _prompts(self.LENS, seed=4)
        want = _eng(params, cfg).generate(prompts, 10)
        got = _eng(params, cfg, multi_tick=4,
                   mesh=build_mesh({"tp": 2})).generate(prompts, 10)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)

    def test_eos_early_exit(self, gpt_setup):
        """EOS landing mid-scan must truncate exactly where the
        single-tick engine stops — the device-side finish mask mirrors
        the host rules."""
        cfg, params = gpt_setup
        prompts = _prompts((5, 6), seed=5)
        base = _eng(params, cfg)
        ref = base.generate(prompts, 20)
        eos = int(ref[0][2])                  # 3rd token becomes EOS
        want = _eng(params, cfg).generate(prompts, 20, eos_id=eos)
        got = _eng(params, cfg, multi_tick=4).generate(
            prompts, 20, eos_id=eos)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)
        assert len(got[0]) < 20               # EOS actually fired


# --------------------------------------------- dispatch & trace economy
@pytest.mark.smoke
class TestDispatchEconomy:
    def test_one_dispatch_per_k_tokens(self, gpt_setup):
        cfg, params = gpt_setup
        gen, k = 12, 4
        prompts = _prompts((5,), seed=6)
        eng = _eng(params, cfg, num_slots=1, multi_tick=k)
        eng.generate(prompts, gen)            # warm
        t0 = _ticks()
        out = eng.generate(prompts, gen)
        assert len(out[0]) == gen
        assert _ticks() - t0 == -(-gen // k)  # ceil(gen/K) dispatches

    def test_trace_ceiling_and_zero_recompiles(self, gpt_setup):
        cfg, params = gpt_setup
        prompts = _prompts((5, 7), seed=7)
        eng = _eng(params, cfg, multi_tick=4)
        eng.generate(prompts, 10)
        dec, pre = eng.trace_counts()
        assert dec == 1                       # greedy-only: ONE trace
        eng.generate(prompts, 10)
        dec2, pre2 = eng.trace_counts()
        assert (dec2, pre2) == (dec, pre)     # zero recompiles

    def test_facade_cache_key_on_k(self, gpt_setup):
        from paddle_tpu.models.gpt import GPTModel
        cfg, _ = gpt_setup
        model = GPTModel(cfg)
        prompts = _prompts((5,), seed=8)
        want = model.generate(prompts, 4, num_slots=2, max_len=MAXLEN)
        outs = model.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                              multi_tick=2)
        e2 = model._serving_engine
        assert e2.mt_k == 2
        for a, b in zip(want, outs):
            np.testing.assert_array_equal(a, b)
        model.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                       multi_tick=4)
        e4 = model._serving_engine
        assert e4 is not e2 and e4.mt_k == 4  # K rebuilds...
        model.generate(prompts, 4, num_slots=2, max_len=MAXLEN,
                       multi_tick=4)
        assert model._serving_engine is e4    # ...same K reuses


# ------------------------------------------------------------ host tier
@pytest.mark.smoke
class TestHostTier:
    def test_lru_unit(self):
        tier = HostKVTier(max_bytes=4096)
        k = np.zeros((2, 8, 2, 4), np.float32)     # 512 B each
        assert tier.put("a", k, k) and tier.put("b", k, k)
        assert "a" in tier and tier.get("a") is not None
        assert tier.put("a", k, k) is False         # dup refreshes only
        for i in range(6):
            tier.put(f"x{i}", k, k)
        assert tier.bytes <= 4096 and tier.drops > 0
        st = tier.stats()
        assert st["entries"] == len(tier) and st["spills"] == 8

    def _families(self, n_fam=3, share=16, tail=4):
        rng = np.random.RandomState(9)
        prompts = []
        for f in range(n_fam):
            head = rng.randint(1, 63, share).astype(np.int32)
            for _ in range(2):
                prompts.append(np.concatenate(
                    [head, rng.randint(1, 63, tail).astype(np.int32)]))
        return prompts

    def test_capacity_beyond_device_pool(self, gpt_setup):
        """Prefix reuse must survive device-pool eviction: a pool too
        small to cache every family's prefix still serves host-tier
        hits (swap-ins > 0) with streams bit-identical to a
        tier-less engine."""
        cfg, params = gpt_setup
        prompts = self._families()
        kw = dict(num_slots=1, kv_layout="paged", page_size=8,
                  num_pages=6, prefix_sharing=True)
        plain = _eng(params, cfg, **kw)
        tiered = _eng(params, cfg, host_kv_bytes=1 << 20, **kw)
        for _ in range(2):                    # second round re-hits
            want = plain.generate(prompts, 4)
            got = tiered.generate(prompts, 4)
            for a, b in zip(want, got):
                assert np.array_equal(a, b)
        st = tiered.pool_stats()["host_tier"]
        assert st["spills"] > 0 and st["swapins"] > 0
        assert st["bytes"] > 0

    def test_ledger_prices_host_tier(self, gpt_setup):
        cfg, params = gpt_setup
        prompts = self._families()
        eng = _eng(params, cfg, num_slots=1, kv_layout="paged",
                   page_size=8, num_pages=6, prefix_sharing=True,
                   host_kv_bytes=1 << 20)
        eng.generate(prompts, 4)
        led = eng.memory_ledger()
        comps = led["components"]
        tier_bytes = eng.pool_stats()["host_tier"]["bytes"]
        assert comps["kv_pool_host"] == tier_bytes > 0
        assert led["host_total"] == tier_bytes
        # host rows stay OUT of the device total
        assert led["total"] == pytest.approx(
            sum(v for n, v in comps.items() if n != "kv_pool_host"))

    def test_gauges_ride_flush(self, gpt_setup):
        cfg, params = gpt_setup
        prompts = self._families()
        eng = _eng(params, cfg, num_slots=1, kv_layout="paged",
                   page_size=8, num_pages=6, prefix_sharing=True,
                   host_kv_bytes=1 << 20)
        eng.generate(prompts, 4)
        snap = monitor.snapshot()
        st = eng.pool_stats()["host_tier"]
        assert snap["serving.kv_host_bytes"] == st["bytes"]
        assert snap["serving.ticks_per_pull"] == eng.mt_k
        assert snap["serving.host_spills"] >= st["spills"]
        assert snap["serving.host_swapins"] >= st["swapins"]


# ------------------------------------------------------------ role split
@pytest.mark.smoke
class TestRoleSplit:
    def _prompts(self):
        return _prompts((5, 7, 6, 5), seed=10)

    def test_handoff_parity_zero_reprefill(self, gpt_setup):
        from paddle_tpu.inference.router import create_router
        cfg, params = gpt_setup
        prompts = self._prompts()
        want = _eng(params, cfg, num_slots=4).generate(prompts, 8)
        pre = monitor.counter("serving.prefills").value
        hand = monitor.counter("serving.router.handoffs").value
        router = create_router(params, cfg, replicas=2, family="gpt",
                               num_slots=4, max_len=MAXLEN,
                               concurrent=False,
                               roles=["prefill", "decode"])
        got = router.generate(prompts, 8)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)
        n = len(prompts)
        assert monitor.counter("serving.prefills").value - pre == n
        assert monitor.counter(
            "serving.router.handoffs").value - hand == n
        st = router.stats()
        assert [r["role"] for r in st["per_replica"]] \
            == ["prefill", "decode"]
        assert st["handoffs"] >= n

    def test_prefill_death_degrades_not_stalls(self, gpt_setup):
        from paddle_tpu.inference.router import create_router
        cfg, params = gpt_setup
        prompts = self._prompts()
        router = create_router(params, cfg, replicas=2, family="gpt",
                               num_slots=2, max_len=MAXLEN,
                               concurrent=False,
                               roles=["prefill", "decode"])
        reqs = [router.submit(p, 6) for p in prompts[:2]]
        router.step()
        router.kill_replica(0, reason="drill")    # the prefill replica
        reqs += [router.submit(p, 6) for p in prompts[2:]]
        router.drain(max_ticks=200)
        assert all(r.done for r in reqs)
        assert all(r.finish_reason in ("length", "eos") for r in reqs)

    def test_roles_validation(self, gpt_setup):
        from paddle_tpu.inference.router import EngineRouter
        cfg, params = gpt_setup
        engines = [_eng(params, cfg), _eng(params, cfg)]
        with pytest.raises(ValueError):
            EngineRouter(engines, roles=["prefill", "prefill"])
        with pytest.raises(ValueError):
            EngineRouter(engines, roles=["decode", "decode"])
        with pytest.raises(ValueError):
            EngineRouter(engines, roles=["prefill"])
        with pytest.raises(ValueError):
            EngineRouter(engines, roles=["prefill", "turbo"])


# --------------------------------------------------- telemetry report
@pytest.mark.smoke
class TestTelemetryReport:
    def test_disagg_block_round_trips(self, gpt_setup, tmp_path):
        """monitor JSONL -> telemetry_report.summarize surfaces the
        disaggregation surface: serving.disagg groups ticks_per_pull /
        kv_host_bytes / host_spills / host_swapins (+ the derived
        tokens_per_dispatch), the memory block mirrors the host-tier
        occupancy, and router handoffs stay in the router block."""
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from telemetry_report import summarize
        cfg, params = gpt_setup
        path = str(tmp_path / "disagg.jsonl")
        monitor.registry().export_jsonl(path)
        eng = _eng(params, cfg, num_slots=1, multi_tick=4)
        eng.generate(_prompts([5, 7]), 8)
        monitor.registry().export_jsonl(path)
        doc = summarize(path)
        disagg = doc["serving"]["disagg"]
        assert disagg["ticks_per_pull"] == 4
        # 2 streams x 8 tokens over ceil(8/4)=2 dispatches each
        assert disagg["tokens_per_dispatch"] == pytest.approx(4.0)
        assert "ticks_per_pull" not in doc["serving"]

    def test_host_tier_gauges_round_trip(self, gpt_setup, tmp_path):
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from telemetry_report import summarize
        cfg, params = gpt_setup
        fams = []
        rng = np.random.RandomState(7)
        for _ in range(3):
            head = rng.randint(1, 63, 16).astype(np.int32)
            for _ in range(2):
                fams.append(np.concatenate(
                    [head, rng.randint(1, 63, 4).astype(np.int32)]))
        path = str(tmp_path / "tier.jsonl")
        monitor.registry().export_jsonl(path)
        eng = _eng(params, cfg, num_slots=1, kv_layout="paged",
                   page_size=8, num_pages=6, prefix_sharing=True,
                   host_kv_bytes=1 << 20)
        for _ in range(2):
            eng.generate(fams, 4)
        monitor.registry().export_jsonl(path)
        st = eng.pool_stats()["host_tier"]
        assert st["spills"] > 0 and st["swapins"] > 0
        doc = summarize(path)
        disagg = doc["serving"]["disagg"]
        assert disagg["host_spills"] == st["spills"]
        assert disagg["host_swapins"] == st["swapins"]
        assert disagg["kv_host_bytes"] == st["bytes"]
        assert doc["memory"]["kv_host_bytes"] == st["bytes"]

    def test_router_handoffs_in_router_block(self, gpt_setup, tmp_path):
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from telemetry_report import summarize
        from paddle_tpu.inference.router import create_router
        cfg, params = gpt_setup
        path = str(tmp_path / "roles.jsonl")
        monitor.registry().export_jsonl(path)
        router = create_router(params, cfg, replicas=2, family="gpt",
                               num_slots=3, max_len=MAXLEN,
                               concurrent=False,
                               roles=["prefill", "decode"])
        router.generate(_prompts([5, 7, 6]), 6)
        monitor.registry().export_jsonl(path)
        doc = summarize(path)
        assert doc["serving"]["router"]["handoffs"] >= 3
        assert "router.handoffs" not in doc["serving"]
