"""Ablation bench: where do the GPT train-step milliseconds go?

Runs on the TPU. Each variant rebuilds + jits the step and measures
steady-state ms/step; differences between variants attribute time to the
ablated component. Also calibrates the achievable matmul rate (bf16 and
fp32) so MFU targets are grounded in what the chip actually delivers,
not the datasheet.

Usage: python tools/ablate_step.py [variant ...]   (default: all)
Output: one JSON line per variant on stdout; diagnostics on stderr.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def log(m):
    print(f"[ablate] {m}", file=sys.stderr, flush=True)


def emit(name, ms, extra=None):
    rec = {"variant": name, "ms": round(ms, 2)}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)


from bench_util import (chained_ms, force as _force,  # noqa: E402
                        mix_grads, timeit)


# ------------------------------------------------------------ calibration
def calib_matmul():
    """Achievable dense matmul rate, bf16 and f32 — the real peak.

    The scan carries a square activation through back-to-back matmuls
    with NO reshaping/slicing between them (an earlier version sliced the
    product back to [M,K] each iteration, which inserted a 64MB copy per
    matmul and understated the peak by ~2x). Weights are 1/D-filled so
    each hop is a row-mean: magnitudes are hop-count-invariant and the
    long chains below can't overflow."""
    # inner chain length keeps ONE dispatch's device time well above the
    # host's per-dispatch cost
    for n, dt in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
        D = 4096
        x = jnp.full((D, D), 0.5, dt)
        w = jnp.full((D, D), 1.0 / D, dt)
        fl = 2.0 * D * D * D
        length = 128 if dt == jnp.bfloat16 else 32

        @jax.jit
        def mm(x, w):
            def body(h, _):
                return (h @ w).astype(dt), None
            h, _ = jax.lax.scan(body, x, None, length=length)
            return h

        ms = timeit(mm, x, w, iters=3)
        tf = length * fl / (ms * 1e-3) / 1e12
        emit(f"calib_matmul_{n}", ms, {"tflops": round(tf, 1)})

    # the model's actual hot shape: [B*S, D] @ [D, 4D] (MLP up-proj)
    M, K, N = 8192, 1024, 4096
    # 1/K and 1/N fills make each (h@b)@c round trip a pure mean:
    # magnitudes stay at 0.5 across the whole chain
    a = jnp.full((M, K), 0.5, jnp.bfloat16)
    b = jnp.full((K, N), 1.0 / K, jnp.bfloat16)
    c = jnp.full((N, K), 1.0 / N, jnp.bfloat16)

    @jax.jit
    def mlp(a, b, c):
        def body(h, _):
            return ((h @ b) @ c).astype(jnp.bfloat16), None
        h, _ = jax.lax.scan(body, a, None, length=128)
        return h

    ms = timeit(mlp, a, b, c, iters=3)
    tf = 128 * 2 * (2.0 * M * K * N) / (ms * 1e-3) / 1e12
    emit("calib_matmul_mlp_shape", ms, {"tflops": round(tf, 1)})


def calib_attention():
    """Flash fwd kernel alone vs the XLA blockwise path, fwd and fwd+bwd."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels.pallas_attention import mha_fwd
    B, S, H, D = 8, 1024, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    # chained (see bench_util.chained_ms): a single-kernel dispatch is
    # short next to the host's per-dispatch cost
    emit("attn_pallas_fwd", chained_ms(
        lambda qc: mha_fwd(qc, k, v, causal=True)[0].astype(q.dtype),
        q, length=32, iters=3))

    emit("attn_xla_fwd", chained_ms(
        lambda qc: fa._blockwise_attention_lse(
            qc, k, v, True)[0].astype(q.dtype),
        q, length=32, iters=3))

    def grad_q(loss):
        gfn = jax.grad(loss, argnums=(0, 1, 2))
        return lambda qc: mix_grads(gfn(qc, k, v), q.dtype)

    def loss_pallas(q, k, v):
        return jnp.sum(fa._flash_mha(q, k, v, True).astype(jnp.float32))

    # main() snapshots/restores the whole env around each variant, so
    # plain sets are safe here
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    emit("attn_fwd_jaxbwd",
         chained_ms(grad_q(loss_pallas), q, length=16, iters=3))
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "0"
    emit("attn_fwd_pallasbwd",
         chained_ms(grad_q(lambda q, k, v: loss_pallas(q, k, v) * 1.0),
                    q, length=16, iters=3))


# ------------------------------------------------------------ step variants
def build(cfg_kw, batch=8, seq=1024):
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       init_opt_state)
    kw = dict(vocab_size=32768, hidden_size=1024, num_layers=24,
              num_heads=16, max_seq_len=1024, dtype=jnp.bfloat16,
              sequence_parallel=False)
    kw.update(cfg_kw)
    cfg = GPTConfig(**kw)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                              cfg.vocab_size)
    return cfg, params, opt, toks


def step_ms(cfg, params, opt, toks, iters=10):
    from paddle_tpu.models.gpt import train_step
    from paddle_tpu.models.facade import make_train_step
    step = make_train_step(train_step, cfg=cfg, lr=1e-4)
    t0 = time.perf_counter()
    loss, params, opt = step(params, opt, toks)
    float(loss)
    log(f"  compile+first {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt = step(params, opt, toks)
    float(loss)
    return (time.perf_counter() - t0) / iters * 1e3


def v_baseline():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    cfg, p, o, t = build(dict(remat=True, remat_policy="full"))
    emit("full_remat_pallasfwd_jaxbwd_b8", step_ms(cfg, p, o, t))


def v_dots():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    cfg, p, o, t = build(dict(remat=True, remat_policy="dots"))
    emit("dots_remat_b8", step_ms(cfg, p, o, t))


def v_dots_flash():
    """dots + saved flash outputs: no attention recompute in backward."""
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    cfg, p, o, t = build(dict(remat=True, remat_policy="dots_flash"))
    emit("dots_flash_remat_b8", step_ms(cfg, p, o, t))


def v_noremat_b4():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    cfg, p, o, t = build(dict(remat=False), batch=4)
    emit("noremat_b4", step_ms(cfg, p, o, t))


def v_xla_attn():
    os.environ["PADDLE_TPU_DISABLE_PALLAS"] = "1"
    cfg, p, o, t = build(dict(remat=True, remat_policy="full"))
    emit("xla_attn_b8", step_ms(cfg, p, o, t))


def v_no_attn():
    """Attention replaced by identity: isolates the whole attention cost."""
    from paddle_tpu.kernels import flash_attention as fa
    orig = fa._flash_mha
    fa._flash_mha = lambda q, k, v, causal, kv_len=None: v
    try:
        cfg, p, o, t = build(dict(remat=True, remat_policy="full"))
        emit("no_attn_b8", step_ms(cfg, p, o, t))
    finally:
        fa._flash_mha = orig


def v_fwd_only():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    from paddle_tpu.models.gpt import gpt_loss
    cfg, p, o, t = build(dict(remat=False))
    f = jax.jit(functools.partial(gpt_loss, cfg=cfg))
    t0 = time.perf_counter()
    float(f(p, t))
    log(f"  compile+first {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(10):
        out = f(p, t)
    float(out)
    emit("fwd_only_noremat_b8", (time.perf_counter() - t0) / 10 * 1e3)


def v_no_head():
    """Loss = mean of final hidden state: isolates LM head + softmax cost."""
    from paddle_tpu.models import gpt as G
    cfg, p, o, t = build(dict(remat=True, remat_policy="full"))

    def loss_nohead(params, batch, cfg):
        inp = batch[:, :-1]
        B, S = inp.shape
        x = jnp.take(params["wte"], inp, axis=0).astype(cfg.dtype)
        x = x + params["wpe"][:S][None].astype(cfg.dtype)
        stacked = {k: params[k] for k in G._BLOCK_KEYS_DENSE if k in params}
        x, _aux = G._apply_stack(stacked, x, cfg)
        x = G._ln(x, params["ln_f_scale"], params["ln_f_bias"],
                  cfg.layer_norm_eps)
        return jnp.mean(x.astype(jnp.float32))

    orig = G.gpt_loss
    G.gpt_loss = loss_nohead
    try:
        emit("no_head_b8", step_ms(cfg, p, o, t))
    finally:
        G.gpt_loss = orig


def v_no_ln():
    """LayerNorm replaced by identity: isolates LN (f32 stats) cost.
    Same backward impl as v_baseline, so the delta is pure LN."""
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    from paddle_tpu.models import gpt as G
    orig = G._ln
    G._ln = lambda x, scale, bias, eps: x
    try:
        cfg, p, o, t = build(dict(remat=True, remat_policy="full"))
        emit("no_ln_b8", step_ms(cfg, p, o, t))
    finally:
        G._ln = orig


def v_no_mlp():
    """Dense FFN replaced by identity: isolates the MLP cost.
    Same backward impl as v_baseline, so the delta is pure MLP."""
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    from paddle_tpu.models import gpt as G
    orig = G._dense_ffn
    G._dense_ffn = lambda x, *a: x
    try:
        cfg, p, o, t = build(dict(remat=True, remat_policy="full"))
        emit("no_mlp_b8", step_ms(cfg, p, o, t))
    finally:
        G._dense_ffn = orig


def v_jaxflash():
    """Upstream jax.experimental TPU flash kernel as the attention impl.
    Numerics first: the step timing means nothing if the upstream kernel
    disagrees with the dense oracle on this backend."""
    _impl_variant("jax_flash", "jaxflash_dotsflash_b8")


def _impl_variant(impl, row_name):
    """Parity-check `impl` against the dense oracle on-device, then time
    the full step with it (dots_flash remat so the kernel's forward is
    saved, not recomputed)."""
    from paddle_tpu.kernels import flash_attention as fa
    fn = {"jax_flash": fa._jax_flash_mha, "splash": fa._splash_mha}[impl]
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 512, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 512, 4, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 512, 4, 64), jnp.bfloat16)
    got = np.asarray(jax.jit(fn, static_argnums=3)(q, k, v, True),
                     np.float32)
    want = np.asarray(fa._dense_reference(q, k, v, True), np.float32)
    err = float(np.max(np.abs(got - want)))
    if err > 0.05:
        emit(f"{row_name}_parity", -1.0, {"max_abs_err": err})
        return
    os.environ["PADDLE_TPU_ATTN_IMPL"] = impl
    cfg, p, o, t = build(dict(remat=True, remat_policy="dots_flash"))
    emit(row_name, step_ms(cfg, p, o, t),
         {"parity_max_abs_err": round(err, 5)})


def v_splash():
    """Upstream splash-attention kernel as the attention impl."""
    _impl_variant("splash", "splash_dotsflash_b8")


# ------------------------------------------------- 3D sharded-step rows
def _step_flops(cfg, params, batch, seq):
    """Step arithmetic volume (cost_model.train_flops_per_token — ONE
    home for the MFU accounting, real param count) — the evidence field
    the kernel-registry plausibility gate (registry.gate_ms) needs, so
    a host-bound plan3d timing can be rejected like any other row."""
    from paddle_tpu.cost_model import train_flops_per_token
    n_params = sum(int(v.size) for v in params.values())
    return train_flops_per_token(n_params, cfg.num_layers,
                                 cfg.hidden_size, seq) * batch * seq


def _plan3d_variant(row_name, cfg_kw, donate=True, batch=8, seq=1024,
                    overlap=False):
    """One sharded-step ablation row: plan the 3D dp×fsdp×tp assignment
    for THIS backend's device count (on one TPU chip the plan degrades
    to dp1 — the row then isolates the pin/donate overhead itself),
    build the planner-driven GSPMD step with the given remat policy and
    donation setting, and emit steady-state ms/step in the
    kernel-registry evidence format (ms + flops + the knobs), so the
    TPU-window gap hunt — attention impl x remat x donation — is one
    `tools/ablate_step.py plan3d...` command."""
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import train_step
    from paddle_tpu.parallel.planner import plan_train
    n = len(jax.devices())
    cfg, params, opt, toks = build(cfg_kw, batch=batch, seq=seq)
    plan = plan_train(cfg, n, batch, overlap=overlap)
    mesh = plan.build_mesh()
    step = make_train_step(train_step, cfg=cfg, lr=1e-4, donate=donate,
                           mesh=mesh, plan=plan)
    t0 = time.perf_counter()
    loss, params, opt = step(params, opt, toks)
    float(loss)
    log(f"  compile+first {time.perf_counter() - t0:.1f}s "
        f"(plan {plan.name})")
    t0 = time.perf_counter()
    for _ in range(10):
        loss, params, opt = step(params, opt, toks)
    float(loss)
    ms = (time.perf_counter() - t0) / 10 * 1e3
    emit(row_name, ms, {
        "flops": _step_flops(cfg, params, batch, seq),
        "knobs": {"plan": plan.name, "donate": donate,
                  "remat": cfg.remat,
                  "remat_policy": cfg.remat_policy if cfg.remat
                  else "none", "n_devices": n,
                  "overlap": bool(getattr(plan, "overlap", False))},
        "traces": step.trace_count,
    })


def v_plan3d():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    _plan3d_variant("plan3d_dots_b8", dict(remat=True,
                                           remat_policy="dots"))


def v_plan3d_full():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    _plan3d_variant("plan3d_full_b8", dict(remat=True,
                                           remat_policy="full"))


def v_plan3d_noremat():
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    _plan3d_variant("plan3d_noremat_b4", dict(remat=False), batch=4)


def v_plan3d_nodonate():
    """Donation OFF over the same plan as plan3d_dots: the delta prices
    what the pinned donation aliasing buys (two live copies of params +
    Adam moments, extra HBM traffic)."""
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    _plan3d_variant("plan3d_dots_nodonate_b8",
                    dict(remat=True, remat_policy="dots"), donate=False)


def v_plan3d_overlap():
    """Overlap A/B (ISSUE 16): the plan3d_dots grid with the latency-
    hiding collective schedule on (plan.overlap -> the XLA async-
    collective/collective-matmul compiler options on TPU meshes; a
    no-op attachment on CPU, where the row pins parity + trace count).
    Run `plan3d plan3d_overlap` together — the delta IS the hidden
    coll_fsdp time."""
    os.environ["PADDLE_TPU_DISABLE_PALLAS_BWD"] = "1"
    _plan3d_variant("plan3d_overlap_b8",
                    dict(remat=True, remat_policy="dots"), overlap=True)


def v_fused_step():
    """Fused-kernel A/B (ISSUE 16): the plan3d_dots grid with BOTH
    fused Pallas step kernels forced on — one-pass CE+grad
    (kernels/pallas_ce.ce_fused_train) and the fused AdamW master
    update (kernels/pallas_update.fused_apply_adamw) — by pointing the
    registry resolution at them in-process (the shipped default stays
    off; tools/bench_fused_step.py --adopt is the only writer). On a
    non-TPU backend the kill-switch gates keep the oracles, so the row
    is only meaningful on the chip."""
    from paddle_tpu.kernels import registry as reg
    forced = {"ce": "pallas_fused", "fused_update": "pallas"}
    orig = reg.winner
    reg.winner = (lambda kernel, backend=None, bucket="*", path=None:
                  forced.get(kernel) or orig(kernel, backend=backend,
                                             bucket=bucket, path=path))
    try:
        _plan3d_variant("plan3d_fusedkernels_b8",
                        dict(remat=True, remat_policy="dots"))
    finally:
        reg.winner = orig


def v_train_attrib():
    """Achieved-vs-roofline evidence rows for the planned train step
    (ISSUE 12): run tools/train_attrib.py's measurement in-process for
    the plan this backend's device count admits and emit one
    kernel-registry-format row per plan — ms + step FLOPs + the ledger
    phase attribution + the HLO audit finding count — so the MFU gap
    hunt has per-phase attribution next to the plan3d timings."""
    import train_attrib as ta
    n = len(jax.devices())
    plans = "dp2_fsdp2_tp2,dp1_fsdp8_tp1" if n >= 8 else "dp1_fsdp1_tp1"
    args = type("A", (), {})()
    args.batch, args.seq, args.steps, args.every = 8, 1024, 10, 3
    args.hidden, args.layers, args.vocab = 1024, 24, 32768
    if jax.devices()[0].platform == "cpu":
        # ANY CPU run gets the test shape, not the flagship (a 24L
        # flagship step on a host core measures swap — at any device
        # count)
        args.hidden, args.layers, args.vocab = 128, 2, 512
        args.seq, args.steps = 32, 12
    args.jsonl_prefix = "/tmp/ablate_train_attrib"
    cfg = ta.build_cfg(args)
    for name in plans.split(","):
        row = ta.measure_plan(name, cfg, args, None, None, None)
        top = max(row["phases"].items(), key=lambda kv: kv[1]["share"])
        emit(f"train_attrib_{row['plan']}",
             row["measured_ms_per_step_p50"] or -1.0, {
                 "flops": row["model_flops_per_step"],
                 "roofline_ms": row["roofline_ms_per_step"],
                 "achieved_vs_roofline": row["achieved_vs_roofline"],
                 "peak_mfu": row["peak_mfu"],
                 "achieved_mfu": row["achieved_mfu"],
                 "bound_phase": f"{top[0]}({top[1]['bound']})",
                 "audit_findings": len(row["audit"]["findings"]),
                 "knobs": {"plan": row["plan"], "batch": args.batch,
                           "seq": args.seq,
                           "n_devices": len(jax.devices())},
             })


def v_sgd():
    """AdamW swapped for plain SGD: isolates optimizer-update cost."""
    from paddle_tpu.models import gpt as G
    cfg, p, o, t = build(dict(remat=True, remat_policy="full"))

    def sgd_step(params, opt_state, batch, cfg, lr=1e-4, **_kw):
        loss, grads = jax.value_and_grad(
            lambda pp: G.gpt_loss(pp, batch, cfg))(params)
        new_params = jax.tree_util.tree_map(
            lambda pp, g: (pp.astype(jnp.float32)
                           - lr * g.astype(jnp.float32)).astype(pp.dtype),
            params, grads)
        return loss, new_params, opt_state

    orig = G.train_step
    G.train_step = sgd_step
    try:
        emit("sgd_b8", step_ms(cfg, p, o, t))
    finally:
        G.train_step = orig


VARIANTS = {
    "calib": calib_matmul,
    "calib_attn": calib_attention,
    "baseline": v_baseline,
    "dots": v_dots,
    "dots_flash": v_dots_flash,
    "noremat_b4": v_noremat_b4,
    "xla_attn": v_xla_attn,
    "no_attn": v_no_attn,
    "fwd_only": v_fwd_only,
    "no_head": v_no_head,
    "sgd": v_sgd,
    "no_ln": v_no_ln,
    "no_mlp": v_no_mlp,
    "jaxflash": v_jaxflash,
    "splash": v_splash,
    # 3D sharded-step rows (ISSUE 10): remat x donation over the
    # planner-driven GSPMD step — run all four for the gap hunt
    "plan3d": v_plan3d,
    "plan3d_full": v_plan3d_full,
    "plan3d_noremat": v_plan3d_noremat,
    "plan3d_nodonate": v_plan3d_nodonate,
    # ISSUE 16 A/B rows: latency-hiding collectives and the fused step
    # kernels over the same plan3d_dots grid
    "plan3d_overlap": v_plan3d_overlap,
    "fused_step": v_fused_step,
    # per-phase roofline attribution + collective audit over the
    # planned step (ISSUE 12) — the evidence row every future MFU
    # optimization PR ships with
    "train_attrib": v_train_attrib,
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    devs = jax.devices()
    log(f"backend {devs[0].platform} ({devs[0].device_kind})")
    for n in names:
        log(f"=== {n} ===")
        # whole-environment snapshot: variants may set any kill-switch /
        # impl env freely and never leak it into the next variant, even
        # when they raise mid-flight
        snapshot = dict(os.environ)
        try:
            VARIANTS[n]()
        except Exception as e:
            emit(n, -1.0, {"error": repr(e)[:200]})
            log(f"variant {n} failed: {e!r}")
        finally:
            os.environ.clear()
            os.environ.update(snapshot)


if __name__ == "__main__":
    main()
