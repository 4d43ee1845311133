"""Executable 4D pipeline-parallel training: dp×fsdp×tp×pp in ONE
full-manual shard_map.

Reference analog: the 1F1B pipeline schedule + hybrid-parallel engine
(fleet/meta_parallel/pipeline_parallel.py:188 — the 1F1B loop,
pp_layers.py:887 stage segmentation, and mp_layers.py:35,173's
ColumnParallel/RowParallel split), which runs per-rank processes
exchanging NCCL P2P tensors under a host-driven schedule. TPU-native
collapse: the whole dp×fsdp×tp×pp step is one SPMD program — stage
parameters are the stacked layer axis sharded over the 'pp' mesh axis
(planner.TrainPlan keeps 'pp' in the remapped specs), microbatches
circulate between neighbouring stages on parallel.pipeline's
scan-of-ppermute schedule, and the backward is jax autodiff replaying
that schedule in reverse (the 1F1B-shaped cooldown/warmup swap), so
the steady-state bubble is (pp-1)/(m+pp-1) per phase — the planner's
(pp-1)/m model, not the (pp-1)× serial fill of layer-sharded
execution.

Why FULL-manual: the GSPMD formulation (pp manual, dp/fsdp/tp left to
the partitioner — parallel/pipeline.pipeline_forward) leaves the tp and
ZeRO-3 collectives where the partitioner puts them; here every axis is
hand-partitioned inside one shard_map over the WHOLE mesh, so each
collective below is one the code names:

- tp: Megatron column/row-parallel — qkv/up matmuls consume this
  rank's column shard (heads/ffn columns), row-parallel outputs
  partial-sum then psum over 'tp'; the embedding and the tied LM head
  are vocab-parallel with a psum'd fused-CE (the lse and target-gather
  reductions cross the vocab shards);
- fsdp: ZeRO-3 — each weight's fsdp-sharded dim is all-gathered just
  in time inside the per-layer scan body (re-gathered in the backward
  under remat); the all_gather transpose IS the gradient
  reduce-scatter, so ZeRO-3's schedule falls out of autodiff;
- dp: pure batch replication — gradient psum after the backward;
- pp: the stage-chunk axis — each rank holds layers
  [s·L/pp, (s+1)·L/pp) of every stacked leaf and runs
  parallel.pipeline.spmd_pipeline's circulate schedule over the
  microbatched activations.

Gradient correctness: the region is typed (`check_vma=True`), so every
value is either varying over a mesh axis (a per-rank value) or
invariant over it (one value all ranks hold), a psum takes varying to
invariant, and autodiff transposes each retyping to its exact adjoint
(psum <-> pcast-to-varying). Two rules follow, one per kind of axis:

- dp, fsdp, pp carry DIFFERENT data per rank (batch shards, stages).
  Every parameter leaf is pcast to varying over those of the three its
  PartitionSpec does not name BEFORE the loss is traced, so its
  cotangent accumulates per rank through both scans with no collective
  inside them, and is psum'd once after the backward over exactly the
  axes it was cast over. The differentiated scalar is the per-rank
  PARTIAL loss — CE masked to the LAST pipeline stage and divided by
  dp·fsdp — whose psum over (dp, fsdp, pp) is the global mean.
- tp carries the SAME activations on every rank. Leaves the spec does
  not shard over tp (norm scales, row-parallel biases) stay invariant:
  the residual stream is invariant over tp, it turns varying where it
  meets a column shard, and the transpose of that retyping is the
  Megatron backward all-reduce. Their gradients come out complete on
  every rank, the loss is not replicated over tp, and nothing divides
  by it.

The out_specs check is the guard: a loss or a bubble that is typed
varying over any axis, or a new leaf that is not typed as its spec
says, fails at trace time (validated to ~1e-7 relative against the
unsharded grads).

The step honors the facade contract `(params, opt_state, batch) ->
(loss, new_params, new_opt)` (plus a trailing bubble-fraction scalar
under with_stats=True — models.facade._PipelineTrainStep strips it and
publishes `train.bubble_fraction`), so donation, the resilient guard
and the telemetry accumulator ride it unchanged through
models.facade.make_train_step's pinned-sharding machinery.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import _clean_spec, leaf_path_name as _leaf_name
from .pipeline import spmd_pipeline, vary

__all__ = ["make_pp_step_fn"]


# ---------------------------------------------------------------- helpers
def _spec_axes(spec) -> set:
    axes = set()
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            axes.add(a)
    return axes


def _gather(w, axis_name: str, axis: int):
    """Just-in-time ZeRO-3/tp weight gather (tiled along `axis`); the
    autodiff transpose is the gradient reduce-scatter."""
    return jax.lax.all_gather(w, axis_name, axis=axis, tiled=True)


def _vocab_parallel_embed(wte, tokens, tp_axis: str):
    """Embedding gather over a vocab-sharded [V/tp, D] table: local
    rows masked-gathered, psum over tp rebuilds the full rows (the
    transpose scatters the full cotangent back into each rank's
    shard)."""
    ti = jax.lax.axis_index(tp_axis)
    v_loc = wte.shape[0]
    idx = tokens.astype(jnp.int32) - ti * v_loc
    ok = (idx >= 0) & (idx < v_loc)
    x = jnp.take(wte, jnp.clip(idx, 0, v_loc - 1), axis=0)
    return jax.lax.psum(
        jnp.where(ok[..., None], x, jnp.zeros((), x.dtype)), tp_axis)


def _vocab_parallel_ce(logits, targets, tp_axis: str):
    """models/losses.fused_softmax_ce over vocab-sharded logits
    [.., V/tp]: the logsumexp and the target gather each cross the
    vocab shards with one psum; the global max is a pmax of a
    stop-gradient value (pmax has no differentiation rule, and
    subtracting a constant leaves the math exact either way). Every
    reduction ends invariant over tp, and so does the loss. Returns the
    mean loss over all positions."""
    lf = logits.astype(jnp.float32)
    ti = jax.lax.axis_index(tp_axis)
    v_loc = lf.shape[-1]
    mx = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(lf, -1)), tp_axis)
    se = jax.lax.psum(jnp.sum(jnp.exp(lf - mx[..., None]), -1), tp_axis)
    lse = mx + jnp.log(se)
    tl = targets.astype(jnp.int32) - ti * v_loc
    ok = (tl >= 0) & (tl < v_loc)
    g = jnp.take_along_axis(lf, jnp.clip(tl, 0, v_loc - 1)[..., None],
                            -1)[..., 0]
    tgt = jax.lax.psum(jnp.where(ok, g, jnp.zeros((), g.dtype)), tp_axis)
    return jnp.mean(lse - tgt)


def _run_pipeline(stacked, x, gather_fn, compute_fn, pp: int,
                  microbatches: int, remat: bool, overlap: bool = False):
    """Microbatch the local activations and run the stage-chunk scan
    through spmd_pipeline's circulate schedule. `stacked` leaves carry
    this rank's [L/pp, ...] stage chunk; returns (y, schedule stats).

    The per-layer block is split at the ZeRO-3 seam:
    `gather_fn(lp) -> gw` issues the just-in-time weight all-gathers,
    `compute_fn(gw, h)` is everything else. overlap=False composes the
    two inside the scan body — the historical trace, gather and compute
    strictly serial per layer. overlap=True double-buffers the gather
    through the scan CARRY: layer 0's weights gather before the scan,
    and iteration i issues layer i+1's all-gather BEFORE running layer
    i's compute, so XLA's async scheduler can slide the gather under
    the matmuls (latency-hiding collectives —
    docs/parallel_training.md §Collective overlap). The autodiff
    transpose replays the same offset in reverse: layer i+1's gradient
    reduce-scatter (the gather's transpose) lands in iteration i's
    backward, overlapping layer i's dgrad matmuls.

    Costs, by construction: one extra (discarded) gather per stage scan
    (the xs roll wraps layer 0 back in at the end), and — under
    remat — the gathered weights ride the carry, so they are saved as
    per-iteration residuals instead of re-gathered in the backward:
    overlap trades the ZeRO-3 backward re-gather's memory saving for
    schedule slack. That is why the knob is off by default."""
    if not overlap:
        def block_fn(lp, h):
            return compute_fn(gather_fn(lp), h)
        body = jax.checkpoint(block_fn) if remat else block_fn

        def stage_fn(chunk, h):
            def scan_body(h, lp):
                return body(lp, h), None
            h, _ = jax.lax.scan(scan_body, h, chunk)
            return h
    else:
        comp = jax.checkpoint(compute_fn) if remat else compute_fn

        def stage_fn(chunk, h):
            first = jax.tree_util.tree_map(lambda a: a[0], chunk)
            gw0 = gather_fn(first)
            # xs rolled by -1: iteration i carries layer i's gathered
            # weights in and sees layer i+1's SHARDED leaves as xs
            nxt = jax.tree_util.tree_map(
                lambda a: jnp.roll(a, -1, axis=0), chunk)

            def scan_body(carry, lp_next):
                h, gw = carry
                gw_next = gather_fn(lp_next)   # prefetch: issue first,
                h = comp(gw, h)                # compute hides it
                return (h, gw_next), None
            (h, _), _ = jax.lax.scan(scan_body, (h, gw0), nxt)
            return h

    b_loc = x.shape[0]
    x_mb = x.reshape((microbatches, b_loc // microbatches) + x.shape[1:])
    piped = spmd_pipeline(stage_fn, pp, microbatches,
                          schedule_stats=True)
    # spmd_pipeline expects the per-rank chunk behind a leading dim of 1
    # (pipeline_forward's P('pp') slicing); the raw [L/pp, ...] shard is
    # exactly that chunk
    chunk = jax.tree_util.tree_map(lambda a: a[None], stacked)
    y_mb, stats = piped(chunk, x_mb)
    return y_mb.reshape(x.shape), stats


# ------------------------------------------------------- family: GPT
def _gpt_gather_weights(lp, tp_axis: str):
    """The layer's just-in-time ZeRO-3/tp weight gathers — the overlap
    seam (_run_pipeline): everything here may be issued one layer ahead
    of the compute consuming it. Pass-through leaves (ln scales/biases,
    the tp-partial output biases) copy through unchanged so compute
    reads ONE dict."""
    gw = dict(lp)
    gw["qkv_w"] = _gather(_gather(lp["qkv_w"], "fsdp", 0),
                          tp_axis, 1)                          # [D, 3D]
    if lp.get("qkv_b") is not None:
        gw["qkv_b"] = _gather(lp["qkv_b"], tp_axis, 0)         # [3D]
    gw["attn_out_w"] = _gather(lp["attn_out_w"], "fsdp", 1)    # [D/tp,D]
    gw["mlp_up_w"] = _gather(lp["mlp_up_w"], "fsdp", 0)        # [D,F/tp]
    gw["mlp_down_w"] = _gather(lp["mlp_down_w"], "fsdp", 1)    # [F/tp,D]
    return gw


def _gpt_stage_compute(gw, x, cfg, tp: int, tp_axis: str):
    """One transformer block over this rank's tp shard (models/gpt._block
    semantics, hand-partitioned) given pre-gathered weights `gw`. The
    fused qkv weight's [3·D] column axis concatenates q|k|v, so its tp
    shard is NOT a head block — gather the columns once and slice this
    rank's heads out of each of q/k/v (exact: column selection commutes
    with the matmul)."""
    from ..models.gpt import _ln
    D = cfg.hidden_size
    H, hd = cfg.num_heads, cfg.head_dim
    h_loc, d_loc = H // tp, D // tp
    ti = jax.lax.axis_index(tp_axis)
    B, S, _ = x.shape

    h = x
    a_in = _ln(h, gw["ln1_scale"], gw["ln1_bias"], cfg.layer_norm_eps)
    w_qkv = gw["qkv_w"]                                        # [D, 3D]
    b_qkv = gw.get("qkv_b")                                    # [3D]

    def head_cols(w, j):
        return jax.lax.dynamic_slice_in_dim(w, j * D + ti * d_loc, d_loc,
                                            axis=-1)

    qkv_loc = []
    for j in range(3):
        p_j = jnp.einsum("bsd,df->bsf", a_in,
                         head_cols(w_qkv, j).astype(a_in.dtype))
        if b_qkv is not None:
            p_j = p_j + head_cols(b_qkv, j).astype(p_j.dtype)
        qkv_loc.append(p_j.reshape(B, S, h_loc, hd))
    q, k, v = qkv_loc
    from ..kernels.flash_attention import flash_attention_fn
    ctx = flash_attention_fn(q, k, v, causal=True).reshape(B, S, d_loc)
    w_o = gw["attn_out_w"]                                     # [D/tp, D]
    a = jax.lax.psum(
        jnp.einsum("bsd,df->bsf", ctx, w_o.astype(ctx.dtype)), tp_axis)
    if gw.get("attn_out_b") is not None:
        a = a + gw["attn_out_b"].astype(a.dtype)
    h = h + a

    m_in = _ln(h, gw["ln2_scale"], gw["ln2_bias"], cfg.layer_norm_eps)
    w_up = gw["mlp_up_w"]                                      # [D, F/tp]
    mh = jnp.einsum("bsd,df->bsf", m_in, w_up.astype(m_in.dtype))
    if gw.get("mlp_up_b") is not None:
        mh = mh + gw["mlp_up_b"].astype(mh.dtype)
    mh = jax.nn.gelu(mh)
    w_dn = gw["mlp_down_w"]                                    # [F/tp, D]
    mo = jax.lax.psum(
        jnp.einsum("bsf,fd->bsd", mh, w_dn.astype(mh.dtype)), tp_axis)
    if gw.get("mlp_down_b") is not None:
        mo = mo + gw["mlp_down_b"].astype(mo.dtype)
    return h + mo


def _gpt_pp_ce(params, toks, cfg, tp: int, tp_axis: str, pp: int,
               microbatches: int, overlap: bool = False):
    from ..models import gpt as gpt_mod
    inp, tgt = toks[:, :-1], toks[:, 1:]
    S = inp.shape[1]
    wte = _gather(params["wte"], "fsdp", 1)                   # [V/tp, D]
    wpe = _gather(params["wpe"], "fsdp", 1)                   # [Smax, D]
    x = _vocab_parallel_embed(wte, inp, tp_axis).astype(cfg.dtype)
    x = x + wpe[:S][None].astype(cfg.dtype)
    stacked = {k: params[k] for k in gpt_mod._BLOCK_KEYS_DENSE
               if k in params}
    gather = functools.partial(_gpt_gather_weights, tp_axis=tp_axis)
    compute = functools.partial(_gpt_stage_compute, cfg=cfg, tp=tp,
                                tp_axis=tp_axis)
    y, stats = _run_pipeline(stacked, x, gather, compute, pp,
                             microbatches, remat=cfg.remat,
                             overlap=overlap)
    y = gpt_mod._ln(y, params["ln_f_scale"], params["ln_f_bias"],
                    cfg.layer_norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", y, wte.astype(y.dtype))
    return _vocab_parallel_ce(logits, tgt, tp_axis), stats


# ----------------------------------------------------- family: Llama
def _llama_gather_weights(lp):
    """Llama's per-layer ZeRO-3 gathers — the overlap seam (see
    _gpt_gather_weights). Norm scales copy through."""
    gw = dict(lp)
    for k in ("q_w", "k_w", "v_w", "gate_w", "up_w"):
        gw[k] = _gather(lp[k], "fsdp", 0)
    for k in ("o_w", "down_w"):
        gw[k] = _gather(lp[k], "fsdp", 1)
    return gw


def _llama_stage_compute(gw, x, cfg, tp: int, tp_axis: str, cos, sin):
    """models/llama._block over this rank's tp shard, given pre-gathered
    weights `gw`. The separate q/k/v leaves column-shard straight into
    contiguous head blocks (no fused-qkv reshuffle); GQA holds KV/tp
    kv-heads per rank, and the repeat factor H//KV aligns them with
    this rank's query heads."""
    from ..models.llama import _rmsnorm, _apply_rope
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h_loc, kv_loc = H // tp, KV // tp
    B, S, D = x.shape

    h = _rmsnorm(x, gw["attn_norm"], cfg.rms_eps)
    q = (h @ gw["q_w"].astype(h.dtype)).reshape(B, S, h_loc, hd)
    k = (h @ gw["k_w"].astype(h.dtype)).reshape(B, S, kv_loc, hd)
    v = (h @ gw["v_w"].astype(h.dtype)).reshape(B, S, kv_loc, hd)
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    from ..kernels.flash_attention import flash_attention_fn
    ctx = flash_attention_fn(q, k, v, causal=True)
    w_o = gw["o_w"]                                    # [(H·hd)/tp, D]
    x = x + jax.lax.psum(
        ctx.reshape(B, S, h_loc * hd) @ w_o.astype(x.dtype), tp_axis)

    hh = _rmsnorm(x, gw["ffn_norm"], cfg.rms_eps)
    gated = jax.nn.silu(
        hh @ gw["gate_w"].astype(hh.dtype)) * (
        hh @ gw["up_w"].astype(hh.dtype))
    w_dn = gw["down_w"]                                # [F/tp, D]
    x = x + jax.lax.psum(gated @ w_dn.astype(x.dtype), tp_axis)
    return x


def _llama_pp_ce(params, toks, cfg, tp: int, tp_axis: str, pp: int,
                 microbatches: int, overlap: bool = False):
    from ..models import llama as llama_mod
    inp, tgt = toks[:, :-1], toks[:, 1:]
    S = inp.shape[1]
    wte = _gather(params["wte"], "fsdp", 1)                   # [V/tp, D]
    x = _vocab_parallel_embed(wte, inp, tp_axis).astype(cfg.dtype)
    cos, sin = llama_mod._rope_tables(S, cfg.head_dim, cfg.rope_theta)
    stacked = {k: params[k] for k in llama_mod._BLOCK_KEYS
               if k in params}
    compute = functools.partial(_llama_stage_compute, cfg=cfg, tp=tp,
                                tp_axis=tp_axis, cos=cos, sin=sin)
    y, stats = _run_pipeline(stacked, x, _llama_gather_weights, compute,
                             pp, microbatches, remat=cfg.remat,
                             overlap=overlap)
    y = llama_mod._rmsnorm(y, params["norm_f"], cfg.rms_eps)
    logits = jnp.einsum("bsd,vd->bsv", y, wte.astype(y.dtype))
    return _vocab_parallel_ce(logits, tgt, tp_axis), stats


def _family_of(cfg) -> str:
    name = type(cfg).__name__
    if "Llama" in name or hasattr(cfg, "num_kv_heads"):
        return "llama"
    if "GPT" in name or hasattr(cfg, "pipeline_microbatches"):
        return "gpt"
    raise NotImplementedError(
        f"pipeline-parallel training supports the gpt/llama stacked-"
        f"scan families; got config {name}")


# ------------------------------------------------------- the step builder
def make_pp_step_fn(cfg, plan, mesh, lr: float = 3e-4,
                    with_stats: bool = False, overlap=None, **adamw_kw):
    """Build the facade-contract pp>1 train step fn for (cfg, plan):
    `(params, opt_state, batch) -> (loss, new_params, new_opt)` — plus
    a trailing schedule-measured bubble-fraction scalar under
    `with_stats=True`. The fn traces ONE full-manual shard_map over the
    plan's mesh; models.facade.make_train_step wraps it in the pinned
    _ShardedTrainStep machinery (resolve_plan_step is the seam the
    resilient guard and the telemetry instrumenter route through).

    `overlap` (None = follow `plan.overlap`) selects _run_pipeline's
    double-buffered ZeRO-3 gather prefetch
    (docs/parallel_training.md §Collective overlap)."""
    family = _family_of(cfg)
    if overlap is None:
        overlap = bool(getattr(plan, "overlap", False))
    overlap = bool(overlap)
    pp = int(plan.axes.get("pp", 1))
    if pp <= 1:
        raise ValueError("make_pp_step_fn needs a plan with a pp>1 axis"
                         " — use the GSPMD 3D step otherwise")
    tp_axis = plan.mapping.get("mp", "tp")
    tp = int(plan.axes.get(tp_axis, 1))
    dp = int(plan.axes.get("dp", 1))
    fsdp = int(plan.axes.get("fsdp", 1))
    microbatches = int(getattr(plan.plan, "microbatches", 0) or 0)
    if microbatches < 2:
        raise ValueError(
            f"plan {plan.name} carries microbatches={microbatches}; the "
            "pipelined step needs >=2 (plan_train picks them for pp>1 "
            "plans)")
    missing = [a for a in ("dp", "fsdp", tp_axis, "pp")
               if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"the pp train step needs all of dp/fsdp/{tp_axis}/pp as "
            f"mesh axes (degree 1 included); mesh {dict(mesh.shape)} "
            f"lacks {missing}")
    if getattr(cfg, "num_experts", 0):
        raise NotImplementedError(
            "MoE under pipeline parallelism is not implemented (the "
            "expert dispatch needs its own manual partitioning)")
    if getattr(cfg, "context_parallel", "none") not in ("none",):
        raise NotImplementedError(
            "context parallelism does not compose with the manual pp "
            "step yet")
    if family == "llama" and tp > 1 and cfg.num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide num_kv_heads={cfg.num_kv_heads} "
            "(the manual GQA split holds KV/tp kv-heads per rank)")
    specs: Dict = plan.specs or {}
    ce_fn = {"gpt": _gpt_pp_ce, "llama": _llama_pp_ce}[family]
    axis_names = tuple(str(a) for a in mesh.axis_names)
    # the axes whose ranks see different data (module docstring)
    data_axes = tuple(a for a in axis_names if a != tp_axis)

    import jax.tree_util as jtu

    def _spec_for(path, leaf):
        return _clean_spec(specs.get(_leaf_name(path), P()), mesh,
                           getattr(leaf, "shape", ()))

    def _state_specs(tree):
        return jtu.tree_map_with_path(_spec_for, tree)

    def _batch_specs(tree):
        def pin(leaf):
            nd = len(getattr(leaf, "shape", ()))
            return P(("dp", "fsdp"), *([None] * (nd - 1))) if nd else P()
        return jax.tree_util.tree_map(pin, tree)

    def _reduce_grad(g, p):
        """psum a per-rank gradient over the data axes its parameter is
        invariant over — the axes `vary` cast it over; axes it is
        sharded over already carry complete shard-gradients (the gather
        transposes reduce-scattered them)."""
        over = tuple(a for a in data_axes if a not in jax.typeof(p).vma)
        return jax.lax.psum(g, over) if over else g

    def local_step(params, opt_state, batch):
        toks = batch["tokens"] if isinstance(batch, dict) else batch
        if toks.shape[0] % microbatches:
            raise ValueError(
                f"per-shard batch {toks.shape[0]} is not divisible by "
                f"microbatches={microbatches} (plan {plan.name})")

        def loss_fn(p):
            ce, stats = ce_fn(p, toks, cfg, tp, tp_axis, pp,
                              microbatches, overlap)
            stage = jax.lax.axis_index("pp")
            # per-rank PARTIAL loss: masked to the LAST stage (where
            # the pipeline's outputs are real — the mask also routes
            # the head/final-norm cotangents to exactly one stage) and
            # divided by the dp·fsdp batch shards, so the partials sum
            # to the global mean exactly once
            part = ce * (stage == pp - 1).astype(ce.dtype) / (dp * fsdp)
            return part, stats

        (part, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(
                jax.tree_util.tree_map(lambda a: vary(a, data_axes),
                                       params))
        loss = jax.lax.psum(part, data_axes)
        grads = jax.tree_util.tree_map(_reduce_grad, grads, params)
        from ..models.gpt import apply_adamw
        new_params, new_opt = apply_adamw(grads, params, opt_state, lr,
                                          **adamw_kw)
        out = (loss, new_params, new_opt)
        if with_stats:
            bubble = 1.0 - stats["busy"] / (stats["stages"]
                                            * stats["ticks"])
            out = out + (bubble,)
        return out

    def step(params, opt_state, batch):
        in_specs = (_state_specs(params), _state_specs(opt_state),
                    _batch_specs(batch))
        out_specs = (P(), in_specs[0], in_specs[1])
        if with_stats:
            out_specs = out_specs + (P(),)
        sm = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, axis_names=set(axis_names),
                           check_vma=True)
        return sm(params, opt_state, batch)

    step.plan = plan
    step.microbatches = microbatches
    step.overlap = overlap
    return step
