"""Compile, for a DESCRIBED TPU v5e (2x2) that is not attached, the jitted
bodies chip_smoke.py runs, at the widths it runs them: every Pallas
kernel entry point, the GPT-350M train step on one chip and under both
four-chip plans, and the GPT-1.3B decode tick. Nothing executes and no
array is made — shapes only (`jax.eval_shape`) — so these guard every
later PR against what interpret mode cannot see (a block that does not
fit VMEM, a slice off the tiling, a kernel GSPMD cannot partition, a step
that does not fit 16 GB) at no chip time. A compile that passes is not a
chip run.

The topology is described inside the module-scoped `topo` fixture, never
at import: only one process may hold the TPU library, and every xdist
worker imports every test file. Code that asks `jax.default_backend()`
would take its CPU branch here, so each test steers it to "tpu" with
monkeypatch — in the test, not through an option of the program.
"""
import functools
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

HBM_BYTES = 16e9
SIZES = chip_smoke.REAL
# static names (a parametrize argument must not need the topology):
# chip_smoke.kernel_cases(SIZES) is checked against this list in the test
KERNELS = ["flash_fwd_hd64", "flash_bwd_hd64", "flash_fwd_hd128",
           "flash_bwd_hd128", "flash_tiled_hd64", "flash_tiled_hd128",
           "jax_flash", "splash", "ce", "ce_fused",
           "fused_adamw", "quant_matmul_k2048", "quant_matmul_k8192",
           "decode_live_blocks", "mla_live_blocks", "prompt_flash",
           "gated_delta_tick"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, no_compile_cache):
    """The program as it runs on the chip: the gates take their TPU
    branch, and matmuls keep jax's default precision (tests/conftest.py
    pins "highest" for the CPU parity tests, which Mosaic refuses for the
    kernels' bf16 operands: "Bad lhs type")."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):
        yield


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _collectives(compiled) -> dict:
    text = compiled.as_text()
    return {k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, topo, as_tpu):
    cases = {c[0]: c for c in chip_smoke.kernel_cases(SIZES)}
    assert sorted(cases) == sorted(KERNELS)
    _, fn, _oracle, shapes, _tol = cases[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def _lower_train(plan, devices):
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import (init_gpt_params, init_opt_state,
                                       train_step)
    cfg = chip_smoke._gpt_cfg(SIZES.train_model)
    params = jax.eval_shape(
        lambda: init_gpt_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(init_opt_state, params)
    toks = jax.ShapeDtypeStruct((SIZES.train_batch, cfg.max_seq_len + 1),
                                jnp.int32)
    step = make_train_step(train_step, cfg=cfg, lr=1e-3,
                           mesh=plan.build_mesh(devices=devices), plan=plan)
    step._build((params, opt, toks))
    return step._jit.lower(params, opt, toks).compile()


def test_gpt_350m_train_step_compiles_on_one_chip(topo, as_tpu):
    from paddle_tpu.parallel.planner import plan_train
    cfg = chip_smoke._gpt_cfg(SIZES.train_model)
    plan = plan_train(cfg, 1, SIZES.train_batch)
    compiled = _lower_train(plan, list(topo.devices[:1]))
    # the loss head is the Pallas CE pair (forward and backward), and the
    # attention the tiled pair: no score block of the blockwise scan
    # ([batch, heads, queries, a block of 512 keys]) is left in HBM
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 4
    for kernel in ("flash_tiled_fwd", "flash_tiled_bwd"):
        assert kernel in text, kernel
    assert "f32[8,16,1024,512]" not in text
    assert _device_bytes(compiled) < HBM_BYTES
    assert not any(_collectives(compiled).values())


@pytest.mark.parametrize("degrees", [
    {}, dict(dp=2, fsdp=1, tp=2), dict(dp=1, fsdp=1, tp=2, pp=2)],
    ids=["planned", "dp2_tp2", "tp2_pp2"])
def test_gpt_350m_train_step_compiles_on_four_chips(degrees, topo, as_tpu):
    """The GSPMD step holds a Mosaic kernel (the CE), which the
    partitioner refuses unless the call sits in a full-manual shard_map
    (models/losses._ce_rows_over_mesh); the pipelined step is one typed
    full-manual region (parallel/pipeline_train.py)."""
    from paddle_tpu.parallel.planner import plan_train
    cfg = chip_smoke._gpt_cfg(SIZES.train_model)
    plan = plan_train(cfg, 4, SIZES.train_batch, **degrees)
    compiled = _lower_train(plan, list(topo.devices))
    assert _device_bytes(compiled) < HBM_BYTES
    colls = _collectives(compiled)
    assert colls["all-reduce"] > 0               # the gradient reduction
    if plan.pp > 1:
        assert colls["collective-permute"] > 0   # the stage ring
    else:
        assert "tpu_custom_call" in compiled.as_text()
    if degrees.get("tp", 1) > 1 and plan.pp == 1:
        # vocab-parallel logits reach the row-split CE by all-to-all
        assert colls["all-to-all"] > 0


def _lower_decode_tick(topo, tp, slots, max_len, page_size=0):
    """The serving engine's greedy decode tick for GPT-1.3B (`tele` and
    `guard` on, as ServingEngine builds it), compiled for `tp` described
    chips the way ServingEngine(mesh=) places it, over the dense pool or
    — with `page_size` — the paged one. -> (compiled, pool shape, the
    parameter tree's shapes)."""
    from paddle_tpu.inference.serving import (_decode_tick, _traced_on,
                                              family_for)
    from paddle_tpu.kernels.decode_attention import cache_pspecs
    from paddle_tpu.models.gpt import init_gpt_params
    from paddle_tpu.parallel.mesh import sharding_for
    from paddle_tpu.quantization.serving import round_serving_params
    cfg = chip_smoke._gpt_cfg(SIZES.serve_model)
    fam = family_for("gpt")
    mesh = Mesh(np.array(topo.devices[:tp]), ("tp",))
    rep = NamedSharding(mesh, P())
    S = jax.ShapeDtypeStruct

    # float32 as a checkpoint hands them, then the engine's rounding
    # to the compute dtype at build
    shapes = jax.eval_shape(
        lambda: round_serving_params(
            init_gpt_params(cfg, jax.random.PRNGKey(0)), "gpt", cfg))
    params = {n: S(v.shape, v.dtype, sharding=sharding_for(
        fam.serving_specs.get(n, P()), mesh, shape=v.shape))
        for n, v in shapes.items()}
    n, oor_pos = slots, None
    if page_size:
        max_pages = -(-max_len // page_size)
        pool = (cfg.num_layers, n * max_pages + 1, page_size,
                cfg.num_heads, cfg.head_dim)
        oor_pos = max_pages * page_size
    else:
        pool = (cfg.num_layers, n, max_len, cfg.num_heads, cfg.head_dim)
    cache = {"k": S(pool, cfg.dtype), "v": S(pool, cfg.dtype)}
    if page_size:
        cache["pt"] = S((n, max_pages), jnp.int32)
    specs = cache_pspecs(bool(page_size), "tp")
    pin = {k: sharding_for(specs.get(k, P()), mesh, shape=v.shape)
           for k, v in cache.items()}
    cache = {k: S(v.shape, v.dtype, sharding=pin[k])
             for k, v in cache.items()}

    def rep_of(shape, dtype):
        return S(shape, dtype, sharding=rep)
    state = tuple(rep_of((n,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.bool_, jnp.float32, jnp.int32,
        jnp.int32, jnp.int32))
    # a tensor-parallel engine traces its forwards with its mesh ambient
    fwd = _traced_on(fam.forward_cached, mesh) if tp > 1 \
        else fam.forward_cached
    tick = jax.jit(
        functools.partial(_decode_tick, fwd=fwd, cfg=cfg,
                          max_top_k=0, guard=True, oor_pos=oor_pos,
                          cache_pin=pin, tele=True),
        donate_argnums=(1, 2), static_argnames=("sampling",))
    compiled = tick.lower(params, cache, state, rep_of((2,), jnp.uint32),
                          rep_of((n,), jnp.float32),
                          sampling=False).compile()
    return compiled, pool, shapes


def _pool_movers(compiled, pool, tp=1) -> list:
    """Instructions of the compiled tick that produce a WHOLE new KV
    pool (per-device shape): a `copy` or a fresh `AllocateBuffer`. The
    in-place row write (a scatter / dynamic-update-slice fusion on the
    donated, aliased buffer) also has the pool's shape and is what the
    tick should be left with."""
    dims = list(pool)
    dims[3] //= tp                       # head-sharded (cache_pspecs)
    shape = "bf16[" + ",".join(map(str, dims)) + "]"
    return [ln.strip()[:160] for ln in compiled.as_text().splitlines()
            if f"= {shape}" in ln
            and (" copy(" in ln or "AllocateBuffer" in ln)]


def test_gpt_1p3b_dense_decode_tick_moves_no_pool(topo, as_tpu):
    """The benchmark's serving cell (16 slots x 1024, dense pool): the
    layer scan carries the two 1.6 GB pools and writes the tick's 16 new
    rows in place. Riding the scan as xs/ys they cost two pool-sized
    `copy`s, two `AllocateBuffer`s and 5.77 GB of temporaries."""
    compiled, pool, shapes = _lower_decode_tick(topo, 1, slots=16,
                                                max_len=1024)
    assert _pool_movers(compiled, pool) == []
    ma = compiled.memory_analysis()
    pools = 2 * int(np.prod(pool)) * 2
    assert ma.alias_size_in_bytes >= pools
    # the weights reach the tick at the compute dtype: no f32->bf16
    # `convert` of a whole stack or of the embedding (2.417e9 of
    # temporaries and 11 of the tick's 20 ms when they were float32)
    weights = {"bf16[" + ",".join(map(str, shapes[n].shape)) + "]"
               for n in ("mlp_up_w", "mlp_down_w", "qkv_w", "attn_out_w",
                         "wte")}
    assert weights == {"bf16[24,2048,8192]", "bf16[24,8192,2048]",
                       "bf16[24,2048,6144]", "bf16[24,2048,2048]",
                       "bf16[50304,2048]"}
    assert [ln.strip()[:160] for ln in compiled.as_text().splitlines()
            if " convert(" in ln
            and any(f"= {w}" in ln for w in weights)] == []
    assert ma.temp_size_in_bytes < 2e6
    assert _device_bytes(compiled) < HBM_BYTES
    # the attention is the length-aware kernel on the pool as carried:
    # nothing slices or copies a layer (134 MB) out of it first, and the
    # masked score fusion over all 1024 positions is gone
    text = compiled.as_text()
    assert "decode_attention_live_blocks" in text
    layer = "bf16[" + ",".join(map(str, (1,) + pool[1:])) + "]"
    assert [ln.strip()[:160] for ln in text.splitlines()
            if f"= {layer}" in ln or f"= {layer.replace('[1,', '[')}" in ln
            ] == []
    assert "f32[16,1024,16]" not in text


def test_gpt_1p3b_dense_decode_tick_keeps_the_einsum_under_tp(topo, as_tpu):
    """Sharded four ways the way ServingEngine(mesh=) places it, the same
    tick holds no Mosaic kernel (GSPMD cannot partition one) and still
    moves no pool."""
    compiled, pool, _ = _lower_decode_tick(topo, 4, slots=16, max_len=1024)
    assert "tpu_custom_call" not in compiled.as_text()
    assert _pool_movers(compiled, pool, 4) == []
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("tp", [1, 4])
def test_gpt_1p3b_paged_decode_tick_compiles(tp, topo, as_tpu):
    """The serving engine's decode tick over the paged pool at the
    smoke's shape (8 slots, 1024 positions, pages of 16), on one chip
    and sharded four ways the way ServingEngine(mesh=) places it."""
    compiled, pool, shapes = _lower_decode_tick(
        topo, tp, slots=SIZES.slots, max_len=SIZES.max_len, page_size=16)
    assert _device_bytes(compiled) < HBM_BYTES
    assert _pool_movers(compiled, pool, tp) == []
    if tp > 1:
        # row-parallel matmuls reduce over tp, and no device holds the
        # whole parameter tree
        assert _collectives(compiled)["all-reduce"] > 0
        whole = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in shapes.values())
        assert compiled.memory_analysis().argument_size_in_bytes < whole


def test_jamba2_3b_decode_tick_moves_no_pool_and_no_weight_stack(topo,
                                                                 as_tpu):
    """The jamba2 cell's decode tick (128 slots x 8192, AI21-Jamba2-3B at
    its published widths): the scan over periods carries four pools — K
    and V by position, the recurrent state and the convolution rows — and
    writes each layer's rows in place, and a layer's weights are read
    straight out of the whole stacks. As the scan's xs each period's
    2.9 GB of weights were copied out before its body ran (2.97 GB of
    temporaries)."""
    from paddle_tpu.inference.serving import _decode_tick, family_for
    from paddle_tpu.models import jamba
    cfg = jamba.JambaConfig()
    assert cfg.layers_of(jamba.MAMBA) == 26 and cfg.period[7] == "attention"
    one = SingleDeviceSharding(topo.devices[0])
    slots, max_len = 128, 8192
    params = _on(one, jax.eval_shape(
        lambda: jamba.init_jamba_params(cfg, jax.random.PRNGKey(0))))
    cache = _on(one, jax.eval_shape(
        lambda: jamba.init_cache(cfg, slots, max_len)))
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    state = tuple(S((slots,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.bool_, jnp.float32, jnp.int32,
        jnp.int32, jnp.int32))
    tick = jax.jit(
        functools.partial(_decode_tick, fwd=family_for("jamba").forward_cached,
                          cfg=cfg, max_top_k=0, guard=True, oor_pos=None,
                          cache_pin=None, tele=True),
        donate_argnums=(1, 2), static_argnames=("sampling",))
    compiled = tick.lower(params, cache, state, S((2,), jnp.uint32),
                          S((slots,), jnp.float32), sampling=False).compile()
    ma = compiled.memory_analysis()
    pools = {k: v for k, v in cache.items() if k != "stats"}
    assert pools["ssm"].shape == (26, 128, 16, 5120) \
        and pools["ssm"].dtype == jnp.float32
    assert pools["k"].shape == (2, 128, 8192, 1, 128)
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pools.values())
    assert ma.alias_size_in_bytes >= held           # updated in place
    text = compiled.as_text()
    for v in pools.values():
        shape = f"{'f32' if v.dtype == jnp.float32 else 'bf16'}[" \
            + ",".join(map(str, v.shape)) + "]"
        assert [ln.strip()[:160] for ln in text.splitlines()
                if f"= {shape}" in ln
                and (" copy(" in ln or "AllocateBuffer" in ln)] == []
    assert ma.temp_size_in_bytes < 100e6
    assert 8.2e9 < _device_bytes(compiled) < 8.6e9 < HBM_BYTES


def test_joyai_llm_flash_tick_and_longest_prompt_fit_and_move_no_pool(
        topo, as_tpu):
    """The joyai-llm-flash cell's two extreme bodies (32 slots x 16,384,
    13 of 40 layers, 16 of 256 experts held, every width as published):
    the decode tick — absorbed attention as the Pallas kernel
    `mla_absorbed_live_blocks` over the two latent pools, which ride both
    layer scans' carry, are written in place (reading an idle row back to
    keep it made the compiler re-lay the whole `kpe` pool out, 1.7 GB of
    temporaries) and are read by the kernel where they lie: `kpe` through
    its [L, B, 64, S] view, which on the chip is a bitcast of the buffer
    (the position axis is minor there) — and the 16,384 prompt bucket,
    whose decompressed attention runs in blocks. What
    `sizing.compile_bytes` of the configuration file states."""
    from paddle_tpu.inference.serving import (_decode_tick, _prefill_slot,
                                              family_for)
    from paddle_tpu.models import joyai_llm_flash as m
    cfg = m.JoyaiLlmFlashConfig(num_layers=13, experts_held=16)
    fam = family_for("joyai_llm_flash")
    one = SingleDeviceSharding(topo.devices[0])
    slots, max_len = 32, 16384
    params = _on(one, jax.eval_shape(lambda: m.init_joyai_llm_flash_params(
        cfg, jax.random.PRNGKey(0), mtp=False)))
    assert sum(int(np.prod(v.shape)) for v in params.values()) \
        == 1_885_031_424
    cache = _on(one, jax.eval_shape(
        lambda: m.init_cache(cfg, slots, max_len)))
    pools = {k: v for k, v in cache.items() if k != "stats"}
    assert pools["ckv"].shape == (13, 32, 16384, 512)
    assert pools["kpe"].shape == (13, 32, 16384, 64)
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pools.values())
    assert held == 32 * 16384 * 14_976
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    state = tuple(S((slots,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.bool_, jnp.float32, jnp.int32,
        jnp.int32, jnp.int32))
    tick = jax.jit(
        functools.partial(_decode_tick, fwd=fam.forward_cached, cfg=cfg,
                          max_top_k=0, guard=True, oor_pos=None,
                          cache_pin=None, tele=True),
        donate_argnums=(1, 2), static_argnames=("sampling",))
    compiled = tick.lower(params, cache, state, S((2,), jnp.uint32),
                          S((slots,), jnp.float32), sampling=False).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= held           # donated through
    text = compiled.as_text()
    # the kernel, once a kind of layer, and no masked score fusion over
    # every position of every slot beside it
    assert text.count("tpu_custom_call") == 2
    assert "mla_absorbed_live_blocks" in text
    assert "f32[32,32,16384]" not in text
    # nothing makes a new pool or a layer of one: what has a pool's or a
    # layer's shape (in either order of `kpe`'s two minor axes) is a
    # parameter, the carry, the in-place row write or the free view
    moved = ("copy", "dynamic-slice", "pad", "transpose", "AllocateBuffer")
    shapes = [f"bf16[{lead}{a},{b}]"
              for lead in ("13,32,", "1,32,", "32,")
              for a, b in ((16384, 512), (16384, 64), (64, 16384))]
    assert [ln.strip()[:160] for ln in text.splitlines()
            if any(f"= {shape}" in ln for shape in shapes)
            and any(f" {op}(" in ln for op in moved)] == []
    assert "= bf16[13,32,64,16384]{3,2,1,0:T(8,128)(2,1)} bitcast(" in text
    assert ma.temp_size_in_bytes < 100e6
    assert 11.5e9 < _device_bytes(compiled) < 11.8e9 < HBM_BYTES
    prefill = jax.jit(
        functools.partial(_prefill_slot, fwd=fam.forward_cached,
                          init_cache=fam.init_cache, cfg=cfg, max_top_k=0,
                          guard=True, cache_pin=None,
                          family_prefill=fam.prefill),
        donate_argnums=(1,), static_argnames=("sampling",))
    compiled = prefill.lower(
        params, cache, S((1, max_len), jnp.int32), S((), jnp.int32),
        S((), jnp.int32), S((1,), jnp.float32), S((1,), jnp.int32),
        S((1,), jnp.int32), S((2,), jnp.uint32), sampling=False).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    assert "mla_absorbed_live_blocks" not in compiled.as_text()
    # the longest prompt program did not grow (13.13 GB before the kernel)
    assert 12.8e9 < _device_bytes(compiled) < 13.2e9 < 15.75e9


_GIGACHAT_TICK = {}


def _gigachat3_5_tick(topo, fam, cfg, params, cache, slots):
    """The gigachat3.5 cell's decode tick compiled once for the file (two
    tests read it)."""
    if "tick" not in _GIGACHAT_TICK:
        from paddle_tpu.inference.serving import _decode_tick
        one = SingleDeviceSharding(topo.devices[0])
        S = functools.partial(jax.ShapeDtypeStruct, sharding=one)
        state = tuple(S((slots,), dt) for dt in (
            jnp.int32, jnp.int32, jnp.bool_, jnp.float32, jnp.int32,
            jnp.int32, jnp.int32))
        tick = jax.jit(
            functools.partial(_decode_tick, fwd=fam.forward_cached, cfg=cfg,
                              max_top_k=0, guard=True, oor_pos=None,
                              cache_pin=None, tele=True),
            donate_argnums=(1, 2), static_argnames=("sampling",))
        _GIGACHAT_TICK["tick"] = tick.lower(
            params, cache, state, S((2,), jnp.uint32),
            S((slots,), jnp.float32), sampling=False).compile()
    return _GIGACHAT_TICK["tick"]


def _gigachat3_5_cell(topo):
    """The gigachat3.5 cell's cut (layers 0 and 4-7 of 40, 16 of 256
    experts, 16,032 rows, every width as published) as shapes on one
    described chip -> (family, cfg, params, cache)."""
    from paddle_tpu.inference.serving import family_for
    from paddle_tpu.models import gigachat3_5 as m
    cfg = m.GigaChat35Config(num_layers=5, full_attention_layers=(4,),
                             first_k_dense_replace=1, experts_held=16,
                             vocab_size=16032, max_seq_len=8192)
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(lambda: m.init_gigachat3_5_params(
        cfg, jax.random.PRNGKey(0))))
    cache = _on(one, jax.eval_shape(lambda: m.init_cache(cfg, 128, 8192)))
    return family_for("gigachat3_5"), cfg, params, cache


def test_gigachat3_5_tick_steps_the_state_in_place_with_the_kernel(topo,
                                                                   as_tpu):
    """The delta-rule step of each of the cell's four linear layers is the
    Pallas kernel `gated_delta_tick` on the carried pool itself: what has
    the pool's shape is the parameter and the kernel's aliased outputs —
    no layer's select + dynamic-update-slice write (which read the state
    a second time), no S^T k / S^T q reduction fusion over [128, 64, 128]
    in the step's scope, no pool-sized temporary — and the tick is no
    larger than the `jnp` step's 13,262,326,272 B (0.39 GB of
    temporaries)."""
    fam, cfg, params, cache = _gigachat3_5_cell(topo)
    compiled = _gigachat3_5_tick(topo, fam, cfg, params, cache, 128)
    text = compiled.as_text()
    lines = text.splitlines()
    assert sum(1 for ln in lines if " custom-call(" in ln
               and "gated_delta_tick" in ln) == 4
    pool = "= f32[4,128,64,128,128]"
    assert {re.search(r"\} ([\w-]+)\(", ln).group(1)
            for ln in lines if pool in ln} <= {"parameter",
                                               "get-tuple-element"}
    assert "select_dynamic-update-slice" not in text
    assert [ln.strip()[:160] for ln in lines
            if "= f32[128,64,128]" in ln and " reduce(" in ln
            and "gated_delta/step" in ln] == []
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.39e9
    assert _device_bytes(compiled) <= 13.26e9


def test_gigachat3_5_tick_and_prompt_bucket_fit_and_move_no_pool(topo,
                                                                 as_tpu):
    """The gigachat3.5 cell's two largest bodies (128 slots x 8,192; layers
    0 and 4-7 of 40, 16 of 256 experts, 16,032 rows, every width as
    published): the decode tick — four linear layers' delta-rule state
    (2.15 GB float32) and convolution rows and one latent layer's pools in
    both layer passes' carry, every row written in place, the latent
    attention the Pallas kernel at 64 heads x 128 rows (which asks for the
    VMEM its whole queries and output need) — and the 4,096 prompt
    bucket, whose chunked delta rule makes each chunk's arrays inside its
    step (made for every chunk ahead of the walk they held 0.78 GB more).
    What `sizing.compile_bytes` of the configuration file states."""
    from paddle_tpu.inference.serving import _prefill_slot
    fam, cfg, params, cache = _gigachat3_5_cell(topo)
    one = SingleDeviceSharding(topo.devices[0])
    slots = 128
    assert sum(int(np.prod(v.shape)) for v in params.values()) \
        == 4_731_722_752
    pools = {k: v for k, v in cache.items() if k != "stats"}
    assert pools["dn_state"].shape == (4, 128, 64, 128, 128)
    assert pools["dn_state"].dtype == jnp.float32
    assert pools["dn_conv"].shape == (4, 128, 3, 16384)
    assert pools["ckv"].shape == (1, 128, 8192, 512)
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pools.values())
    assert held == 3_405_774_848
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    compiled = _gigachat3_5_tick(topo, fam, cfg, params, cache, slots)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= held           # donated through
    text = compiled.as_text()
    # the latent kernel, once: one latent layer
    assert sum(1 for ln in text.splitlines() if " custom-call(" in ln
               and "mla_absorbed_live_blocks" in ln) == 1
    moved = ("copy", "dynamic-slice", "pad", "transpose", "AllocateBuffer")
    shapes = ["f32[4,128,64,128,128]", "f32[128,64,128,128]",
              "bf16[4,128,3,16384]", "bf16[1,128,8192,512]",
              "bf16[128,8192,512]", "bf16[1,128,64,8192]"]
    assert [ln.strip()[:160] for ln in text.splitlines()
            if any(f"= {shape}" in ln for shape in shapes)
            and any(f" {op}(" in ln for op in moved)] == []
    assert ma.temp_size_in_bytes < 0.5e9
    assert 13.1e9 < _device_bytes(compiled) < 13.4e9 < HBM_BYTES
    prefill = jax.jit(
        functools.partial(_prefill_slot, fwd=fam.forward_cached,
                          init_cache=fam.init_cache, cfg=cfg, max_top_k=0,
                          guard=True, cache_pin=None,
                          family_prefill=fam.prefill),
        donate_argnums=(1,), static_argnames=("sampling",))
    compiled = prefill.lower(
        params, cache, S((1, 4096), jnp.int32), S((), jnp.int32),
        S((), jnp.int32), S((1,), jnp.float32), S((1,), jnp.int32),
        S((1,), jnp.int32), S((2,), jnp.uint32), sampling=False).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    assert 14.2e9 < _device_bytes(compiled) < 14.5e9 < 15.75e9
