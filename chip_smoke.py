#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls, at the published widths of the models it supports (weights
random, from --seed):

  1. surface  `import paddle_tpu as paddle`: a small nn net eager, backward,
              one AdamW step, jit.to_static agreeing with eager, a
              num_workers=2 DataLoader feeding hapi.Model.fit
  2. kernels  every Pallas kernel at the widths of phases 3-4, compiled by
              Mosaic and compared with its jax-level oracle
  3. train    GPT-350M through plan_train(cfg, 1, B) + make_train_step
  4. serve    GPT-1.3B ServingEngine behind create_router(replicas=1):
              dense vs paged vs speculative streams, a reference decode,
              trace ceilings, a weight-only int8 engine
  5. serve_recurrent  a tiny model of the family that keeps a recurrent
              state beside its K/V (jamba): one slot admitted, decoded
              and admitted again reads nothing of its last occupant

`--chips 4` runs the four-chip phase instead (and nothing else): the
sharded train plans against the one-chip step, a tp=4 engine against the
one-chip engine, and a four-replica router with a replica on each chip.

It needs a TPU: on any other platform it exits non-zero before a phase
runs and prints no result. Each phase prints one JSON object; the LAST
line of a run that passed is
`{"ok": true, "device": {"platform", "kind", "count"}}`, and nothing that
failed ever reaches it. Off the chip the same phase functions are driven
at tiny widths by tests/test_chip_smoke.py (the rehearsal), which never
prints that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from typing import Optional, Tuple


# --------------------------------------------------------------------- sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a run is sized by. A model is a GPT3_CONFIGS name or
    GPTConfig kwargs; kernel shapes are the ones those models produce."""
    train_model: object
    train_batch: int
    train_steps: int
    serve_model: object
    slots: int
    max_len: int
    prompt_lens: Tuple[int, ...]
    new_tokens: int
    prefill_chunk: int
    flash_shapes: Tuple[Tuple[int, int, int, int], ...]   # [B, S, H, hd]
    ce_shape: Tuple[int, int]                             # [T, V]
    adamw_leaf: Tuple[int, ...]
    qmm_shapes: Tuple[Tuple[int, int, int], ...]          # [M, K, N]
    decode_pool: Tuple[int, int, int, int, int]           # [L, B, S, KV, hd]
    latent_pool: Tuple[int, int, int, int, int, int]      # [L, B, S, C, R, H]


# the published widths: GPT-350M (24L x 1024d x 16 heads, hd 64) trains at
# B=8, S=1024; GPT-1.3B (24L x 2048d x 16 heads, hd 128, ffn 8192) serves
REAL = Sizes(
    train_model="350m", train_batch=8, train_steps=5,
    serve_model="1.3b", slots=8, max_len=1024,
    prompt_lens=(17, 120, 300, 29, 100, 280), new_tokens=32,
    prefill_chunk=128,
    flash_shapes=((8, 1024, 16, 64), (2, 1024, 16, 128)),
    ce_shape=(8 * 1024, 50304),
    adamw_leaf=(24, 1024, 4096),            # mlp_up_w, the largest leaf
    qmm_shapes=((256, 2048, 8192), (256, 8192, 2048)),
    decode_pool=(4, 8, 1024, 16, 128),      # four layers of the serve pool
    # two layers of JoyAI-LLM-Flash's latent pools at a quarter the length
    latent_pool=(2, 8, 4096, 512, 64, 32),
)

_TINY_GPT = dict(vocab_size=640, hidden_size=128, num_layers=2, num_heads=2,
                 max_seq_len=128, dtype="float32")
# the rehearsal's widths (tests/test_chip_smoke.py): same code, toy sizes,
# float32 so that two layouts of one computation agree to the last token
TINY = Sizes(
    train_model=_TINY_GPT, train_batch=4, train_steps=3,
    serve_model=_TINY_GPT, slots=4, max_len=128,
    prompt_lens=(5, 30, 70, 9, 25, 66), new_tokens=6,
    prefill_chunk=32,
    flash_shapes=((1, 256, 2, 64), (1, 128, 2, 128)),
    ce_shape=(256, 640),
    adamw_leaf=(2, 128, 512),
    qmm_shapes=((16, 256, 512), (16, 512, 256)),
    decode_pool=(2, 4, 256, 8, 128),
    latent_pool=(2, 4, 2048, 128, 64, 8),
)

# bf16 compute rounds to 8 bits of mantissa; losses of two layouts of one
# step differ by reduction order at that precision (f32 compute: the
# tolerance tests/test_plan4d.py uses)
LOSS_RTOL = {"bfloat16": 2e-3, "float32": 2e-4}


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------- harness
class Run:
    """What the phases share: the seed, the platform found, and the one
    place a phase's record (seconds, compile seconds, peak bytes) is
    assembled and printed."""

    def __init__(self, seed: int, emit=print):
        import jax
        self.seed = int(seed)
        self.emit = emit
        self.devices = jax.devices()
        self.on_chip = self.devices[0].platform == "tpu"
        self._compile_s = 0.0
        self.compiles = 0           # executables XLA built so far
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)
        self.records = []

    def _on_event(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += seconds
            self.compiles += 1

    def key(self, n: int):
        import jax
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), n)

    def peak_bytes(self) -> Optional[int]:
        stats = self.devices[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def phase(self, name: str, fn, *args) -> dict:
        t0, c0 = time.perf_counter(), self._compile_s
        details = fn(self, *args) or {}
        rec = {"phase": name, "ok": True,
               "seconds": round(time.perf_counter() - t0, 2),
               "compile_seconds": round(self._compile_s - c0, 2),
               "peak_bytes_in_use": self.peak_bytes(), **details}
        self.records.append(rec)
        self.emit(json.dumps(rec))
        gc.collect()
        return rec


def _gpt_cfg(model):
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPT3_CONFIGS, GPTConfig
    if isinstance(model, str):
        return GPT3_CONFIGS[model]
    return GPTConfig(**dict(model, dtype=jnp.dtype(model["dtype"]).type))


def _rel_err(got, want) -> float:
    """Largest difference of two arrays over the size of the larger entry
    of `want` (at least 1)."""
    import jax.numpy as jnp
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(g - w)) / jnp.maximum(
        jnp.max(jnp.abs(w)), 1.0))


# ------------------------------------------------------------ phase: surface
def phase_surface(run: Run) -> dict:
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.io import DataLoader, Dataset

    paddle.seed(run.seed)
    rng = np.random.RandomState(run.seed)
    net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 10))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    loss_fn = nn.CrossEntropyLoss()
    x = paddle.to_tensor(rng.randn(32, 64).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, 32).astype(np.int64))

    out = net(x)
    loss = loss_fn(out, y)
    loss.backward()
    w = net.parameters()[0]
    check(w.grad is not None and np.isfinite(w.grad.numpy()).all(),
          "backward() left no finite gradient on the first weight")
    before = w.numpy().copy()
    opt.step()
    opt.clear_grad()
    check(np.abs(w.numpy() - before).max() > 0,
          "the AdamW step did not move the first weight")
    eager = net(x).numpy()
    static = paddle.jit.to_static(net)(x).numpy()
    check(np.allclose(static, eager, rtol=1e-5, atol=1e-5),
          f"to_static disagrees with eager by "
          f"{np.abs(static - eager).max():.3e}")
    platforms = {d.platform for d in out._value.devices()}
    check(platforms == {run.devices[0].platform},
          f"result arrays live on {platforms}, not on "
          f"{run.devices[0].platform}")

    # DataLoader workers are forked AFTER jax is up: they must stay off
    # the device and still feed fit(). A timeout turns a hang into an error
    class Pairs(Dataset):
        def __init__(self):
            self.x = rng.randn(96, 64).astype(np.float32)
            self.y = rng.randint(0, 10, (96, 1)).astype(np.int64)

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    loader = DataLoader(Pairs(), batch_size=16, num_workers=2, timeout=120,
                        drop_last=True)
    multiprocess = loader._multiprocess_ok()
    check(multiprocess or not run.on_chip,
          "the native shm ring is unavailable: num_workers=2 would run "
          "on threads, which proves nothing about forked workers")
    model = paddle.Model(net)
    model.prepare(opt, loss_fn)
    hist = model.fit(loader, epochs=1, verbose=0)
    check(hist["loss"] and np.isfinite(hist["loss"]).all(),
          f"hapi.Model.fit over the worker loader gave {hist['loss']}")
    return {"loader_workers": "processes" if multiprocess else "threads",
            "fit_loss": float(hist["loss"][-1])}


# ------------------------------------------------------------ phase: kernels
def kernel_cases(sizes: Sizes):
    """The Pallas kernels of the main path as (name, fn, oracle, arg
    shapes, tolerance) at `sizes`' widths — shapes only, so that
    tests/test_chip_compile.py can compile the same list for a described
    chip. `fn` and `oracle` take the arrays `_kernel_args` makes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import (pallas_attention, pallas_ce,
                                    pallas_update, quant_matmul)
    from paddle_tpu.models.gpt import apply_adamw

    bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
    S = jax.ShapeDtypeStruct
    cases = []

    def flash_fwd(q, k, v):
        return pallas_attention.mha_fwd(q, k, v, causal=True)

    def flash_fwd_oracle(q, k, v):
        return fa._dense_attention_lse(q, k, v, True)

    def flash_bwd(q, k, v, do):
        out, lse = fa._blockwise_attention_lse(q, k, v, True)
        return pallas_attention.mha_bwd(q, k, v, out, lse, do, causal=True)

    def flash_bwd_oracle(q, k, v, do):
        out, lse = fa._blockwise_attention_lse(q, k, v, True)
        return fa._flash_bwd(q, k, v, out, lse, do, True)

    for shp in sizes.flash_shapes:
        qkv = (S(shp, bf16),) * 3
        cases.append((f"flash_fwd_hd{shp[-1]}", flash_fwd, flash_fwd_oracle,
                      qkv, 3e-2))
        cases.append((f"flash_bwd_hd{shp[-1]}", flash_bwd, flash_bwd_oracle,
                      qkv + (S(shp, bf16),), 6e-2))

    # the training attention's tiled pair, forward and backward, against
    # the autodiff of the dense softmax
    def dense_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa._dense_reference(
                q, k, v, True).astype(f32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    def flash_tiled(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: pallas_attention.tiled_mha(
                q, k, v, True).astype(f32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for shp in sizes.flash_shapes:
        cases.append((f"flash_tiled_hd{shp[-1]}", flash_tiled, dense_grads,
                      (S(shp, bf16),) * 3, 6e-2))

    # the upstream wrappers, at the training attention shape
    shp = sizes.flash_shapes[0]
    qkv = (S(shp, bf16),) * 3
    for name, impl in (("jax_flash", fa._jax_flash_mha),
                       ("splash", fa._splash_mha)):
        def upstream(q, k, v, impl=impl):
            return jax.value_and_grad(
                lambda q, k, v: impl(q, k, v, True).astype(f32).sum(),
                argnums=(0, 1, 2))(q, k, v)

        # the summed output is a large number: compare the gradients and
        # the sum relative to its size
        cases.append((name, upstream, dense_grads, qkv, 6e-2))

    T, V = sizes.ce_shape
    ce_args = (S((T, V), bf16), S((T,), i32), S((T,), f32))

    def ce_oracle(logits, tgt, w):
        def loss(x):
            xf = x.astype(f32)
            per = jax.nn.logsumexp(xf, -1) - jnp.take_along_axis(
                xf, tgt[:, None], -1)[:, 0]
            return (per * w).sum()
        return jax.value_and_grad(loss)(logits)

    for name, ce_fn in (("ce", pallas_ce.ce_with_logits),
                        ("ce_fused", pallas_ce.ce_fused_train)):
        def ce(logits, tgt, w, ce_fn=ce_fn):
            return jax.value_and_grad(
                lambda x: (ce_fn(x, tgt) * w).sum())(logits)
        cases.append((name, ce, ce_oracle, ce_args, 2e-2))

    # one leaf through the kernel and through models.gpt.apply_adamw, the
    # jax form the kernel names as its oracle (it is that form while
    # pallas_update.FUSED_UPDATE is off, and it is);
    # `v` arrives as a normal draw and a second moment is a square
    def adamw_with(apply):
        def run(p, g, m, v):
            new_p, new_opt = apply(
                {"w": g}, {"w": p},
                {"m": {"w": m}, "v": {"w": jnp.square(v)},
                 "step": jnp.float32(3.0)}, 3e-4)
            return new_p["w"], new_opt["m"]["w"], new_opt["v"]["w"]
        return run

    cases.append(("fused_adamw", adamw_with(pallas_update.fused_apply_adamw),
                  adamw_with(apply_adamw),
                  (S(sizes.adamw_leaf, f32),) * 4, 1e-5))

    for M, K, N in sizes.qmm_shapes:
        cases.append((f"quant_matmul_k{K}",
                      quant_matmul._pallas_quant_matmul,
                      quant_matmul._xla_quant_matmul,
                      (S((M, K), bf16), S((K, N), i8), S((N,), f32)),
                      2e-2))

    # the decode attention's live blocks against the masked einsum over
    # the whole layer; the int32 draw (0 .. hd - 1) spread over the pool
    from paddle_tpu.kernels import decode_attention as da
    L, B, P, KV, hd = sizes.decode_pool

    def spread(draw):
        return draw * 37 % P

    def decode(kc, vc, q, draw):
        return da.length_aware_attention(
            q, kc, vc, jnp.int32(L - 1),
            da.work_list(spread(draw), None, B, P))

    def decode_oracle(kc, vc, q, draw):
        return da.cached_attention(q, kc[L - 1], vc[L - 1], spread(draw),
                                   impl="dense")

    cases.append(("decode_live_blocks", decode, decode_oracle,
                  (S(sizes.decode_pool, bf16),) * 2
                  + (S((B, 1, KV, hd), bf16), S((B,), i32)), 1e-5))

    # the absorbed latent attention's live blocks against the family's
    # two masked einsums over the whole layer (the probabilities are
    # rounded to bf16 after they are normalised there, before it here:
    # 2^-9 of an output); own names: the closures above bind theirs late
    from paddle_tpu.kernels import latent_attention as la
    from paddle_tpu.models import joyai_llm_flash as joyai
    LL, LB, LP, C, R, H = sizes.latent_pool
    latent_cfg = joyai.JoyaiLlmFlashConfig(kv_lora_rank=C,
                                           qk_rope_head_dim=R)

    def absorbed(ckv, kpe, q_lat, q_pe, draw):
        return la.absorbed_attention_live_blocks(
            q_lat, q_pe, ckv, kpe, jnp.int32(LL - 1),
            da.work_list(draw * 37 % LP, None, LB, LP, la.LATENT_BLOCK),
            latent_cfg.qk_head_dim)

    def absorbed_oracle(ckv, kpe, q_lat, q_pe, draw):
        return joyai._masked_einsums(q_lat, q_pe, ckv[LL - 1], kpe[LL - 1],
                                     draw * 37 % LP, latent_cfg)

    cases.append(("mla_live_blocks", absorbed, absorbed_oracle,
                  (S((LL, LB, LP, C), bf16), S((LL, LB, LP, R), bf16),
                   S((LB, H, C), bf16), S((LB, H, R), bf16),
                   S((LB,), i32)), 1e-2))
    return cases


def _kernel_args(key, shapes):
    """Random arrays for a case's shapes: normal draws, int8 weights over
    their range, int32 targets below the first argument's last (vocabulary)
    dimension."""
    import jax
    import jax.numpy as jnp
    args = []
    for i, s in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if s.dtype == jnp.int32:
            args.append(jax.random.randint(k, s.shape, 0, shapes[0].shape[-1],
                                           jnp.int32))
        elif s.dtype == jnp.int8:
            args.append(jax.random.randint(k, s.shape, -127, 128,
                                           jnp.int32).astype(jnp.int8))
        else:
            args.append(jax.random.normal(k, s.shape, jnp.float32)
                        .astype(s.dtype))
    return args


def phase_kernels(run: Run, sizes: Sizes) -> dict:
    import contextlib
    import jax
    from jax.experimental.pallas import tpu as pltpu

    # off the chip the same kernels run in Pallas' TPU interpreter; on
    # the chip they are compiled by Mosaic, and the lowered text says so
    mode = contextlib.nullcontext() if run.on_chip \
        else pltpu.force_tpu_interpret_mode()
    report = {}
    with mode:
        for i, (name, fn, oracle, shapes, tol) in enumerate(
                kernel_cases(sizes)):
            args = _kernel_args(run.key(100 + i), shapes)
            lowered = jax.jit(fn).lower(*args)
            check(not run.on_chip or "tpu_custom_call" in lowered.as_text(),
                  f"{name}: the lowered program holds no tpu_custom_call "
                  "— Mosaic did not compile it")
            got = jax.block_until_ready(lowered.compile()(*args))
            want = jax.block_until_ready(jax.jit(oracle)(*args))
            # each leaf against its own magnitude
            err = max(_rel_err(g, w)
                      for g, w in zip(jax.tree_util.tree_leaves(got),
                                      jax.tree_util.tree_leaves(want)))
            check(math.isfinite(err) and err <= tol,
                  f"{name}: differs from its jax-level oracle by {err:.3e} "
                  f"of the leaf's magnitude (tolerance {tol:.0e})")
            report[name] = float(f"{err:.3e}")
            del got, want, args
    return {"kernels": report}


# -------------------------------------------------------------- phase: train
def _train_losses(cfg, plan, mesh, batch, steps, seed, lr=3e-4):
    """`steps` steps of the planned train step from the seeded init on one
    repeated batch -> (losses, step times, the step, final params)."""
    import jax
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import (init_gpt_params, init_opt_state,
                                       train_step)
    params = init_gpt_params(cfg, jax.random.PRNGKey(seed))
    opt = init_opt_state(params)
    step = make_train_step(train_step, cfg=cfg, lr=lr, mesh=mesh, plan=plan)
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, params, opt = step(params, opt, batch)
        jax.block_until_ready((loss, params, opt))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, times, step, params


def _seeded_batch(run: Run, cfg, batch: int):
    import numpy as np
    rng = np.random.RandomState(run.seed + 1)
    return rng.randint(0, cfg.vocab_size,
                       (batch, cfg.max_seq_len + 1)).astype(np.int32)


def phase_train(run: Run, sizes: Sizes) -> dict:
    from paddle_tpu.parallel.planner import plan_train
    cfg = _gpt_cfg(sizes.train_model)
    B = sizes.train_batch
    plan = plan_train(cfg, 1, B)
    mesh = plan.build_mesh(devices=run.devices[:1])
    toks = _seeded_batch(run, cfg, B)
    losses, times, step, params = _train_losses(
        cfg, plan, mesh, toks, sizes.train_steps, run.seed)
    check(all(math.isfinite(l) for l in losses),
          f"non-finite training loss: {losses}")
    # the first update must lower the loss on the batch it was computed
    # from. Later steps are reported, not judged: AdamW without warm-up
    # moves every weight by about lr a step whatever the gradient's size,
    # and from a random init the loss of step 3-5 spikes at any useful
    # rate (seen on the chip at 1e-3 and at 3e-4) — that is the optimizer
    # doing what it was told, not a fault of the path
    check(losses[1] < losses[0],
          f"the first update did not lower the loss on its own batch: "
          f"{losses}")
    check(step.trace_count == 1,
          f"the train step traced {step.trace_count} times; one compile "
          "must serve every step")
    del params
    return {"model": f"{cfg.num_layers}Lx{cfg.hidden_size}d",
            "plan": plan.name, "batch": B, "seq": cfg.max_seq_len,
            "losses": [round(l, 4) for l in losses],
            "first_step_seconds": round(times[0], 2),
            "step_ms": [round(t * 1e3, 1) for t in times[1:]]}


# -------------------------------------------------------------- phase: serve
def _prompts(run: Run, cfg, lens):
    import numpy as np
    rng = np.random.RandomState(run.seed + 2)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def _serve(router, prompts, new_tokens: int, first: int = 3):
    """Submit `first` requests, then one more every third tick, so that
    each later prompt prefills while earlier requests decode; drain.
    Returns the finished requests in submission order."""
    waiting = list(prompts)
    reqs = [router.submit(waiting.pop(0), new_tokens)
            for _ in range(min(first, len(waiting)))]
    tick = 0
    while router.has_work() or waiting:
        if waiting and tick % 3 == 2:
            reqs.append(router.submit(waiting.pop(0), new_tokens))
        router.step()
        tick += 1
        check(tick < 50 * (new_tokens + len(prompts)),
              f"the router did not drain in {tick} ticks: {router.stats()}")
    for r in reqs:
        check(r.done and r.finish_reason == "length"
              and len(r.tokens) == new_tokens,
              f"request {r.id} ended {r.finish_reason!r} with "
              f"{len(r.tokens)}/{new_tokens} tokens: {router.stats()}")
    return reqs


class _Judge:
    """Greedy streams of two engine variants must be identical. The model
    computes its logits in `cfg.dtype`; in bf16 two candidates are often
    the SAME number or one step apart, and which of them an argmax takes
    then turns on reduction order (a different cache layout, a verify pass
    over several positions, a tensor-parallel all-reduce). Such a
    divergence is accepted only if the model's own logits at that
    position, recomputed by the plain forward, put the two tokens within
    `TIE_ULPS` steps of that dtype of each other (in float32 that is
    exact agreement to seven digits). Everything after the first
    divergence of a stream has another context and is not compared."""

    TIE_ULPS = 2

    def __init__(self, params, cfg):
        import jax
        from paddle_tpu.models.gpt import gpt_forward
        self.params, self.cfg = params, cfg
        self._fwd = jax.jit(lambda p, t: gpt_forward(p, t, cfg))

    def gap_ulps(self, context, tok_a: int, tok_b: int) -> float:
        """How far apart the reference forward puts two candidate tokens
        after `context`, in steps of the logits' dtype at their size."""
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.models.decode import next_pow2
        n = len(context)
        padded = np.zeros((1, min(next_pow2(n, 8), self.cfg.max_seq_len)),
                          np.int32)
        padded[0, :n] = context
        logits = self._fwd(self.params, padded)[0, n - 1]
        a, b = (float(logits[t]) for t in (tok_a, tok_b))
        size = max(abs(a), abs(b), float(jnp.finfo(logits.dtype).tiny))
        ulp = float(jnp.finfo(logits.dtype).eps) * 2.0 ** math.floor(
            math.log2(size))
        return abs(a - b) / ulp

    def compare(self, what: str, prompts, got, want) -> dict:
        import numpy as np
        exact, ties = 0, []
        for i, (p, g, w) in enumerate(zip(prompts, got, want)):
            g, w = np.asarray(g), np.asarray(w)
            diff = np.nonzero(g != w)[0]
            if not len(diff):
                exact += 1
                continue
            j = int(diff[0])
            gap = self.gap_ulps(np.concatenate([p, w[:j]]), int(g[j]),
                                int(w[j]))
            check(gap <= self.TIE_ULPS,
                  f"{what}: request {i} diverges at token {j} "
                  f"({int(g[j])} vs {int(w[j])}) where the model's logits "
                  f"are {gap:.1f} ulps apart — not a tie")
            ties.append({"request": i, "token": j, "gap_ulps": round(gap, 2)})
        return {"exact": exact, "of": len(prompts), "ties": ties}


def _router(params, cfg, sizes: Sizes, **engine_kw):
    from paddle_tpu.inference.router import create_router
    return create_router(params, cfg, replicas=1, num_slots=sizes.slots,
                         max_len=sizes.max_len, **engine_kw)


def phase_serve(run: Run, sizes: Sizes) -> dict:
    import jax
    import numpy as np
    from paddle_tpu.models.gpt import greedy_generate, init_gpt_params
    cfg = _gpt_cfg(sizes.serve_model)
    params = init_gpt_params(cfg, run.key(3))
    prompts = _prompts(run, cfg, sizes.prompt_lens)
    judge = _Judge(params, cfg)
    off = dict(spec_decode="off", quant="off")
    variants = [
        ("dense", dict(kv_layout="dense", **off)),
        ("paged", dict(kv_layout="paged", **off,
                       prefill_chunk=sizes.prefill_chunk)),
        ("paged_spec", dict(kv_layout="paged", spec_decode="spec",
                            quant="off",
                            prefill_chunk=sizes.prefill_chunk)),
        ("paged_int8", dict(kv_layout="paged", spec_decode="off",
                            quant="int8",
                            prefill_chunk=sizes.prefill_chunk)),
    ]
    streams, report = {}, {}
    ceiling = 2 * int(math.log2(sizes.max_len))
    for name, kw in variants:
        # one variant's KV pool on the chip at a time
        router = _router(params, cfg, sizes, **kw)
        reqs = _serve(router, prompts, sizes.new_tokens)
        streams[name] = [np.asarray(r.tokens, np.int32) for r in reqs]
        eng = router.replicas[0].eng
        decode_traces, prefill_traces = eng.trace_counts()
        check(decode_traces <= 2 and prefill_traces <= ceiling,
              f"{name}: {decode_traces} decode / {prefill_traces} prefill "
              f"traces exceed the engine's ceilings (2 / {ceiling})")
        report[name] = {"traces": [decode_traces, prefill_traces]}
        if name == "dense":
            # zero recompiles after warm-up (what tools/bench_serving.py
            # asserts): the same workload again builds no executable. Its
            # wall time over its ticks (prefills included) is the one
            # warm serving time this script sees: an order of magnitude
            built, ticks = run.compiles, router._ticks
            t0 = time.perf_counter()
            _serve(router, prompts, sizes.new_tokens)
            report[name]["warm_pass"] = {
                "seconds": round(time.perf_counter() - t0, 3),
                "ticks": router._ticks - ticks}
            check(run.compiles == built,
                  f"dense: a second pass of the same workload compiled "
                  f"{run.compiles - built} more executables")
        check(all(0 <= int(t) < cfg.vocab_size
                  for s in streams[name] for t in s),
              f"{name}: a token outside the vocabulary")
        router.close()
        del router, eng, reqs
        gc.collect()

    report["paged"]["vs_dense"] = judge.compare(
        "paged vs dense", prompts, streams["paged"], streams["dense"])
    report["paged_spec"]["vs_paged"] = judge.compare(
        "spec vs non-spec", prompts, streams["paged_spec"],
        streams["paged"])
    # int8 is a different model: it has to answer, not to agree
    report["paged_int8"]["agrees_with_paged"] = round(float(np.mean(
        [np.mean(a == b) for a, b in zip(streams["paged_int8"],
                                         streams["paged"])])), 3)

    # the plain scan-fused decode, independent of the engine
    head = 8 if sizes.new_tokens >= 8 else sizes.new_tokens
    p0 = prompts[0]
    ref = np.asarray(jax.block_until_ready(greedy_generate(
        params, p0[None, :], cfg, head, sizes.max_len)))[0, len(p0):]
    report["reference_decode"] = judge.compare(
        "engine vs greedy_generate", [p0], [streams["paged"][0][:head]],
        [ref])
    cfg_name = f"{cfg.num_layers}Lx{cfg.hidden_size}d"
    return {"model": cfg_name, "slots": sizes.slots,
            "max_len": sizes.max_len, "requests": len(prompts),
            "new_tokens": sizes.new_tokens, "variants": report}


def phase_serve_recurrent(run: Run, sizes: Sizes) -> dict:
    """The family that keeps a recurrent state beside its keys and values
    (models/jamba.py), tiny: ONE slot admits a request, decodes it, and is
    admitted again. The second occupant has to decode exactly what it
    decodes in an engine whose slot nobody used — the same program on the
    same inputs but for the rows the first occupant left, which admission
    must have replaced whole."""
    import jax.numpy as jnp
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.models.jamba import JambaConfig, init_jamba_params
    cfg = JambaConfig(vocab_size=640, hidden_size=128, num_layers=4,
                      num_heads=2, num_kv_heads=1, head_dim=64,
                      ffn_hidden=256, max_seq_len=128, attn_layer_period=2,
                      attn_layer_offset=1, mamba_dt_rank=8,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_jamba_params(cfg, run.key(5))
    long_one, short_one = _prompts(run, cfg, (70, 9))

    def serve(prompts):
        router = create_router(params, cfg, replicas=1, family="jamba",
                               num_slots=1, max_len=128)
        reqs = _serve(router, prompts, sizes.new_tokens,
                      first=len(prompts))
        eng = router.replicas[0].eng
        pools = {k: int(v.nbytes) for k, v in eng._cache.items()
                 if k != "stats"}
        router.close()
        return [list(r.tokens) for r in reqs], pools

    (_, reused), pools = serve([long_one, short_one])
    (fresh,), _ = serve([short_one])
    check(reused == fresh,
          f"a re-admitted slot decoded {reused}, a fresh one {fresh}: "
          "something of the last occupant was read")
    check(all(0 <= t < cfg.vocab_size for t in reused),
          "a token outside the vocabulary")
    return {"model": f"{cfg.num_layers}Lx{cfg.hidden_size}d "
                     f"{'/'.join(t[0] for t in cfg.layer_types)}",
            "new_tokens": sizes.new_tokens, "pool_bytes": pools,
            "readmitted_equals_fresh": True}


# --------------------------------------------------------- phase: four chips
def _device_bytes(devices):
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]


def _check_sharded(name: str, tree, devices) -> list:
    """Every device holds part of `tree`, and no leaf whose sharding
    splits it sits whole on one device. -> bytes of the tree per device."""
    import jax
    per_device = {d: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(tree):
        split = not leaf.sharding.is_fully_replicated
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
            check(not split or shard.data.size < leaf.size,
                  f"{name}: a leaf of shape {leaf.shape} with sharding "
                  f"{leaf.sharding.spec} sits whole on {shard.device}")
    out = [per_device[d] for d in devices]
    check(all(b > 0 for b in out),
          f"{name}: parameter bytes per device {out} — a device holds "
          "nothing")
    return out


def four_train(run: Run, sizes: Sizes) -> dict:
    import numpy as np
    from paddle_tpu.parallel.planner import plan_train
    cfg = _gpt_cfg(sizes.train_model)
    B, steps = sizes.train_batch, 3
    toks = _seeded_batch(run, cfg, B)
    devs = run.devices[:4]
    one = plan_train(cfg, 1, B)
    ref, _, _, p = _train_losses(cfg, one, one.build_mesh(devices=devs[:1]),
                                 toks, steps, run.seed)
    del p
    gc.collect()
    rtol = LOSS_RTOL[np.dtype(cfg.dtype).name]
    report = {"one_chip_losses": [round(l, 5) for l in ref], "rtol": rtol}
    for label, degrees in (("planned", {}),
                           ("tp2_pp2", dict(dp=1, fsdp=1, tp=2, pp=2))):
        plan = plan_train(cfg, 4, B, **degrees)
        mesh = plan.build_mesh(devices=devs)
        got, _, step, params = _train_losses(cfg, plan, mesh, toks, steps,
                                             run.seed)
        dev = max(abs(g - r) / abs(r) for g, r in zip(got, ref))
        param_bytes = _check_sharded(f"{label} ({plan.name})", params, devs)
        report[label] = {"plan": plan.name,
                         "losses": [round(l, 5) for l in got],
                         "max_rel_dev": float(f"{dev:.3e}"),
                         "param_bytes_per_device": param_bytes,
                         "bytes_in_use_per_device": _device_bytes(devs)}
        check(dev <= rtol,
              f"{label} ({plan.name}): losses {got} leave the one-chip "
              f"step's {ref} by {dev:.3e} (tolerance {rtol:.0e})")
        check(step.trace_count == 1,
              f"{label}: the step traced {step.trace_count} times")
        del params, step
        gc.collect()
    return report


def four_serve(run: Run, sizes: Sizes) -> dict:
    import jax
    import numpy as np
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.models.gpt import init_gpt_params
    from paddle_tpu.parallel.mesh import build_mesh
    cfg = _gpt_cfg(sizes.serve_model)
    devs = run.devices[:4]
    params = init_gpt_params(cfg, run.key(3))
    prompts = _prompts(run, cfg, sizes.prompt_lens)
    judge = _Judge(params, cfg)
    kw = dict(kv_layout="paged", spec_decode="off", quant="off",
              num_slots=sizes.slots, max_len=sizes.max_len)

    # the one-chip engine the sharded one is compared with, on device 0
    one = create_router(params, cfg, replicas=1, **kw)
    want = [np.asarray(r.tokens, np.int32)
            for r in _serve(one, prompts, sizes.new_tokens)]
    one.close()
    del one
    gc.collect()

    tp = create_router(params, cfg, replicas=1,
                       meshes=[build_mesh({"tp": 4}, devices=devs)], **kw)
    got = [np.asarray(r.tokens, np.int32)
           for r in _serve(tp, prompts, sizes.new_tokens)]
    eng = tp.replicas[0].eng
    report = {"tp4": {
        "vs_one_chip": judge.compare("tp=4 vs one chip", prompts, got, want),
        "param_bytes_per_device": _check_sharded("tp=4 engine", eng._params,
                                                 devs),
        "bytes_in_use_per_device": _device_bytes(devs)}}
    tp.close()
    del tp, eng
    gc.collect()

    # four replicas, a chip each, placed by create_router itself
    fleet = create_router(params, cfg, replicas=4, **kw)
    eight = (prompts + _prompts(run, cfg, sizes.prompt_lens[::-1]))[:8]
    reqs = _serve(fleet, eight, sizes.new_tokens, first=4)
    homes = []
    for i, rep in enumerate(fleet.replicas):
        where = {d for leaf in jax.tree_util.tree_leaves(
            (rep.eng._params, rep.eng._cache)) for d in leaf.devices()}
        check(where == {devs[i]},
              f"replica {i}: parameters and KV pool live on {where}, "
              f"not on {devs[i]} alone")
        homes.append(str(devs[i]))
    used = sorted({r.replica for r in reqs})
    check(len(used) > 1,
          f"eight requests all went to replica {used}: "
          f"{fleet.stats()['per_replica']}")
    report["router4"] = {"replica_devices": homes,
                         "replicas_used": used,
                         "requests": len(reqs),
                         "bytes_in_use_per_device": _device_bytes(devs)}
    check(all(b > 0 for b in report["router4"]["bytes_in_use_per_device"])
          or not run.on_chip, "a chip of the four-replica router is empty")
    fleet.close()
    return report


# ----------------------------------------------------------------------- run
def run_one_chip(run: Run, sizes: Sizes) -> None:
    """Phases 1-5. A phase that fails raises, and nothing runs after it."""
    run.phase("surface", phase_surface)
    run.phase("kernels", phase_kernels, sizes)
    run.phase("train", phase_train, sizes)
    run.phase("serve", phase_serve, sizes)
    run.phase("serve_recurrent", phase_serve_recurrent, sizes)


def run_four_chips(run: Run, sizes: Sizes) -> None:
    """The four-chip phase and what it is compared with, and no other. A
    four-chip call is dear, so both halves run before a failure of either
    is raised."""
    check(len(run.devices) >= 4,
          f"the four-chip phase needs four devices, jax found "
          f"{len(run.devices)}")
    failures = []
    for name, fn in (("four_chips.train", four_train),
                     ("four_chips.serve", four_serve)):
        try:
            run.phase(name, fn, sizes)
        except Exception as e:                   # noqa: BLE001 — reported
            import traceback
            traceback.print_exc()
            run.emit(json.dumps({"phase": name, "ok": False,
                                 "error": f"{type(e).__name__}: {e}"}))
            failures.append(name)
        gc.collect()
    check(not failures, f"failed: {failures}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds every weight and every input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the four-chip phase and nothing else")
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu.utils.compile_cache import (seed_cache_env,
                                                sync_compile_cache_for)
    seed_cache_env()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    sync_compile_cache_for("tpu")
    run = Run(args.seed)
    if args.chips == 4:
        run_four_chips(run, REAL)
    else:
        run_one_chip(run, REAL)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
