"""Auto-parallel planner: cost-model search over (dp, mp, pp, fsdp)
(reference python/paddle/distributed/auto_parallel/static/tuner/
parallel_tuner.py:40 + cost/base_cost.py). The contract pinned here:
legality pruning, memory pruning, the qualitative orderings the cost
model exists to encode, and — the ranking gate — that the predicted
ranking matches the MEASURED step-time ranking of hand-built configs on
the 8-device CPU mesh."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.planner import (
    ChipSpec, ModelSpec, Plan, best_mesh_axes, enumerate_plans,
    plan_parallel, spec_from_gpt_config)


def _spec(**kw):
    base = dict(num_layers=8, hidden_size=512, num_heads=8,
                ffn_hidden=2048, vocab_size=32000, seq_len=1024)
    base.update(kw)
    return ModelSpec(**base)


class TestEnumeration:
    def test_covers_all_legal_factorizations(self):
        plans = enumerate_plans(_spec(), 8, global_batch=32)
        keys = {(p.dp, p.mp, p.pp, p.fsdp) for p in plans}
        # every (dp, mp, pp, fsdp) with product 8, heads/layers/batch legal
        assert (8, 1, 1, 1) in keys and (1, 8, 1, 1) in keys
        assert (2, 2, 2, 1) in keys and (1, 1, 1, 8) in keys
        for p in plans:
            assert p.n_devices == 8

    def test_illegal_degrees_pruned(self):
        # 6 heads: mp=4 cannot divide
        plans = enumerate_plans(_spec(num_heads=6, ffn_hidden=1536),
                                8, 32)
        assert all(p.mp in (1, 2, 6) or 6 % p.mp == 0 for p in plans)
        assert not any(p.mp == 4 for p in plans)
        # 8 layers: pp=3 impossible at n=6... use layers=6, n=8: pp in
        # {1,2} only (4 does not divide 6)
        plans = enumerate_plans(_spec(num_layers=6), 8, 32)
        assert not any(p.pp == 4 for p in plans)

    def test_batch_divisibility(self):
        plans = enumerate_plans(_spec(), 8, global_batch=4)
        assert all(4 % (p.dp * p.fsdp) == 0 for p in plans)


class TestFactorizationEdgeCases:
    """_factorizations / plan_parallel edge cases that previously relied
    on the caller: prime device counts, a global batch no dp×fsdp split
    divides, single-device — each either plans cleanly or fails with an
    error NAMING the violated constraint (ISSUE 10 satellite)."""

    def test_prime_device_count(self):
        from paddle_tpu.parallel.planner import _factorizations
        facts = _factorizations(7)
        assert all(dp * mp * pp * fsdp == 7
                   for dp, mp, pp, fsdp in facts)
        # a prime n admits exactly the 4 one-hot assignments
        assert len(facts) == 4 and (7, 1, 1, 1) in facts \
            and (1, 1, 1, 7) in facts
        # 8 heads / 8 layers: mp=7 and pp=7 are pruned, dp/fsdp legal
        plans = enumerate_plans(_spec(), 7, global_batch=7)
        keys = {(p.dp, p.mp, p.pp, p.fsdp) for p in plans}
        assert keys == {(7, 1, 1, 1), (1, 1, 1, 7)}

    def test_single_device(self):
        from paddle_tpu.parallel.planner import _factorizations
        assert _factorizations(1) == [(1, 1, 1, 1)]
        best = plan_parallel(_spec(), 1, 3)   # odd batch fine at n=1
        assert (best.dp, best.mp, best.pp, best.fsdp) == (1, 1, 1, 1)

    def test_batch_indivisible_names_the_constraint(self):
        # heads=7 forces mp=1 on 16 devices; layers=7 forces pp=1; so
        # every surviving split needs dp*fsdp=16 to divide batch=13
        with pytest.raises(ValueError, match=r"global_batch=13"):
            plan_parallel(_spec(num_heads=7, ffn_hidden=7 * 256,
                                num_layers=7), 16, 13)

    def test_candidates_reflect_heads_and_layers_pruning(self):
        # heads=3 forces mp=1 and layers=8 caps pp at 8, so dp*fsdp=1
        # (which WOULD divide batch=13) is impossible on 16 devices —
        # the error's candidate list shows exactly the surviving splits
        with pytest.raises(ValueError,
                           match=r"candidates: \[2, 4, 8, 16\]"):
            plan_parallel(_spec(num_heads=3, ffn_hidden=3 * 256,
                                num_layers=8), 16, 13)

    def test_max_mp_named_when_it_prunes_everything(self):
        # heads=16 on 16 devices, but batch 13 kills every dp*fsdp>1
        # split and max_mp=1 kills the mp escape: both named
        with pytest.raises(ValueError, match="global_batch=13"):
            plan_parallel(_spec(num_heads=16, num_layers=7), 16, 13,
                          max_mp=1)

    def test_plan_train_search_names_the_empty_space(self):
        from paddle_tpu.parallel.planner import plan_train
        with pytest.raises(ValueError, match="no legal"):
            # heads=7/layers=7 on 16 devices with batch 13: nothing
            # legal at pp=1, and layers=7 divides no pp>1 degree of 16
            # either — the HBM-gate fallback (ISSUE 15) finds nothing
            plan_train(_spec(num_heads=7, ffn_hidden=7 * 256,
                             num_layers=7), 16, 13)

    def test_plan_train_diagnosis_names_batch_constraint(self):
        from paddle_tpu.parallel.planner import plan_train
        # layers=8 admits pp∈{2,4,8} escapes, but every surviving
        # dp*fsdp split (16/pp) still fails 13's divisibility — the
        # diagnosis must name the batch constraint instead of 'every
        # assignment was pruned'
        with pytest.raises(ValueError, match=r"global_batch=13"):
            plan_train(_spec(num_heads=7, ffn_hidden=7 * 256,
                             num_layers=8), 16, 13)


class TestCostModelOrderings:
    """The qualitative orders the model must encode (each mirrors a cost
    the reference tuner prices)."""

    def _by_key(self, plans):
        return {(p.dp, p.mp, p.pp, p.fsdp): p for p in plans}

    def test_dp_beats_tp_when_everything_fits(self):
        # small model, big chip: TP pays per-layer activation
        # all-reduces, DP only the (overlapped) grad reduction
        plans = self._by_key(enumerate_plans(_spec(), 8, 32))
        assert plans[(8, 1, 1, 1)].step_s < plans[(1, 8, 1, 1)].step_s
        assert plans[(8, 1, 1, 1)].step_s < plans[(2, 4, 1, 1)].step_s

    def test_pure_dp_ooms_on_big_model(self):
        # 6.7B-class on a 16 GB chip: 100+ GB of optimizer state per
        # replica cannot fit; sharded plans must rank above it
        big = _spec(num_layers=32, hidden_size=4096, num_heads=32,
                    ffn_hidden=16384, vocab_size=50304, seq_len=2048)
        plans = enumerate_plans(big, 16, 16)
        by = self._by_key(plans)
        assert not by[(16, 1, 1, 1)].fits
        best = plans[0]
        assert best.fits and (best.mp * best.pp * best.fsdp) > 1

    def test_bubble_penalizes_pp_at_small_microbatch(self):
        spec = _spec()
        few = enumerate_plans(spec, 8, 32, microbatches=2)
        many = enumerate_plans(spec, 8, 32, microbatches=16)
        pp_few = self._by_key(few)[(2, 1, 4, 1)]
        pp_many = self._by_key(many)[(2, 1, 4, 1)]
        assert pp_few.step_s > pp_many.step_s

    def test_fsdp_cheaper_than_mp_for_memory_relief(self):
        # when the constraint is optimizer state, fsdp (3 param moves)
        # should beat tp (4L activation moves) for long sequences
        big = _spec(num_layers=24, hidden_size=2048, num_heads=16,
                    ffn_hidden=8192, seq_len=2048)
        by = self._by_key(enumerate_plans(big, 8, 16))
        assert by[(1, 1, 1, 8)].step_s < by[(1, 8, 1, 1)].step_s

    def test_plan_parallel_returns_best_and_raises_when_impossible(self):
        best = plan_parallel(_spec(), 8, 32)
        assert isinstance(best, Plan) and best.fits
        with pytest.raises(ValueError, match="no legal"):
            plan_parallel(_spec(num_heads=7, ffn_hidden=7 * 64 * 4,
                                num_layers=7), 16, 13)

    def test_gpt_config_adapter(self):
        from paddle_tpu.models.gpt import GPTConfig
        cfg = GPTConfig(hidden_size=256, num_layers=4, num_heads=4,
                        vocab_size=1024, max_seq_len=128)
        spec = spec_from_gpt_config(cfg)
        assert spec.ffn_hidden == 1024 and spec.remat_policy == "full"
        best = plan_parallel(cfg, 8, 16)
        assert best.fits


class TestBestMeshAxes:
    def test_small_model_pure_dp(self):
        axes = best_mesh_axes(10_000_000, 8)
        assert axes == {"dp": 8}

    def test_huge_model_brings_in_fsdp(self):
        axes = best_mesh_axes(7_000_000_000, 8)
        assert axes.get("fsdp", 1) > 1
        assert np.prod(list(axes.values())) == 8

    def test_fsdp_degree_divides_device_count(self):
        # 6 devices: doubling 2->4 would strand 2 devices; only
        # divisors of 6 are legal
        for n in (6, 12):
            axes = best_mesh_axes(1_000_000_000, n)
            assert np.prod(list(axes.values())) == n, axes

    def test_engine_auto_mode_picks_and_surfaces_axes(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.parallel.auto_parallel import Engine, Strategy
        model = nn.Linear(16, 16)
        eng = Engine(model=model, strategy=Strategy(mesh_axes="auto"))
        eng.prepare()
        assert eng.strategy.mesh_axes == {"dp": len(jax.devices())}
        assert eng._mesh is not None


class TestPredictedVsMeasured:
    """The ranking gate: predicted ranking == measured step-time ranking
    for hand-built configs on the virtual 8-device mesh. Configs are
    chosen so the ordering is driven by structure (pipeline bubble, TP
    collective volume vs pure DP), not measurement noise."""

    def test_ranking_matches_measured(self):
        from paddle_tpu.models.gpt import (GPTConfig, PARAM_SPECS,
                                           init_gpt_params,
                                           init_opt_state, train_step)
        from paddle_tpu.parallel.mesh import (P, build_mesh,
                                              sharding_for, use_mesh)
        import functools

        B, S = 16, 128
        base = dict(vocab_size=2048, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=S, dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    remat_policy="none", sequence_parallel=False)
        # four hand-built configs: a TP-monotone triple whose measured
        # gaps are large (mp degree 1 -> 4 -> 8 roughly doubles the
        # per-layer collective volume each step, so ranking is driven by
        # structure, not noise) plus a pipeline config whose bubble must
        # price it behind pure DP both ways
        configs = {
            "dp8": (GPTConfig(**base), {"dp": 8}),
            "dp2mp4": (GPTConfig(**base), {"dp": 2, "mp": 4}),
            "mp8": (GPTConfig(**base), {"mp": 8}),
            "pp2mb2": (GPTConfig(**base, pipeline_microbatches=2),
                       {"dp": 4, "pp": 2}),
        }

        def measure(cfg, axes):
            mesh = build_mesh(axes)
            with use_mesh(mesh):
                params = init_gpt_params(cfg, jax.random.PRNGKey(0))
                params = {k: jax.device_put(
                    v, sharding_for(PARAM_SPECS[k], mesh))
                    for k, v in params.items()}
                opt = init_opt_state(params)
                tokens = jax.device_put(
                    np.random.randint(0, 2048, (B, S + 1),
                                      dtype=np.int32),
                    sharding_for(P("dp", None), mesh))
                step = jax.jit(functools.partial(
                    train_step, cfg=cfg, lr=1e-4))
                out = step(params, opt, tokens)
                jax.block_until_ready(out)          # compile + warm
                # min-of-k: robust to load spikes on the shared 1-core
                # host (an average would let one slow iteration invert
                # the measured ranking)
                best = float("inf")
                for _ in range(4):
                    t0 = time.perf_counter()
                    out = step(params, opt, tokens)
                    jax.block_until_ready(out)
                    best = min(best, time.perf_counter() - t0)
                return best

        def measure_all():
            return {name: measure(cfg, axes)
                    for name, (cfg, axes) in configs.items()}

        measured = measure_all()

        # predicted, from the SAME structures through the cost model
        spec = spec_from_gpt_config(configs["dp8"][0])
        plans = {
            "dp8": Plan(dp=8),
            "dp2mp4": Plan(dp=2, mp=4),
            "mp8": Plan(mp=8),
            "pp2mb2": Plan(dp=4, pp=2, microbatches=2),
        }
        from paddle_tpu.parallel.planner import _estimate
        predicted = {name: _estimate(p, spec, B, ChipSpec()).step_s
                     for name, p in plans.items()}

        # (1) the TP-monotone triple ranks identically
        triple = ["dp8", "dp2mp4", "mp8"]
        p_order = sorted(triple, key=predicted.get)

        def ok(m):
            return (sorted(triple, key=m.get) == p_order == triple
                    and m["pp2mb2"] > m["dp8"])

        # shared 1-core host: a load spike spanning one config's timed
        # window can invert an adjacent pair — re-measure once before
        # declaring the ranking broken
        if not ok(measured):
            measured = measure_all()
        assert ok(measured), (measured, predicted)
        # (2) the bubble config prices behind pure DP
        assert predicted["pp2mb2"] > predicted["dp8"]


class TestAllModelFamilyConfigs:
    """spec_from_config duck-types the single-tower family configs
    (GPT/BERT/ViT); the ERNIE-ViL composite is rejected with per-tower
    guidance."""

    def test_bert_config(self):
        from paddle_tpu.models.bert import BertConfig
        from paddle_tpu.parallel.planner import spec_from_config
        spec = spec_from_config(BertConfig())
        assert spec.seq_len == 512 and spec.vocab_size == 30522
        best = plan_parallel(BertConfig(), 8, 32)
        assert best.fits

    def test_vit_config_derives_seq_from_patches(self):
        from paddle_tpu.models.vit import ViTConfig
        from paddle_tpu.parallel.planner import spec_from_config
        spec = spec_from_config(ViTConfig())
        assert spec.seq_len == (224 // 16) ** 2 + 1
        best = plan_parallel(ViTConfig(), 8, 64)
        assert best.fits

    def test_unplannable_config_rejected(self):
        from paddle_tpu.parallel.planner import spec_from_config

        class Odd:
            num_layers, hidden_size, num_heads, ffn_hidden = 2, 8, 2, 32
        with pytest.raises(ValueError, match="sequence length"):
            spec_from_config(Odd())

    def test_llama_config_plans(self):
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.parallel.planner import spec_from_config
        cfg = LlamaConfig(hidden_size=256, num_layers=4, num_heads=8,
                          num_kv_heads=4, vocab_size=1024,
                          max_seq_len=128)
        spec = spec_from_config(cfg)
        assert spec.ffn_hidden == cfg.ffn_hidden
        assert plan_parallel(cfg, 8, 16).fits

    def test_ernie_vil_composite_plans_per_tower(self):
        from paddle_tpu.models.ernie_vil import ErnieViLConfig
        from paddle_tpu.parallel.planner import spec_from_config
        cfg = ErnieViLConfig()
        with pytest.raises(ValueError, match="tower"):
            spec_from_config(cfg)
        # each tower plans fine
        assert plan_parallel(cfg.text, 8, 32).fits
        assert plan_parallel(cfg.vision, 8, 64).fits
