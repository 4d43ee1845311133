"""GPT — the flagship model family (BASELINE config 3: GPT-3 scale under
TP×PP×DP×SP(×EP) hybrid parallelism).

Reference analog: the fleet GPT workload (SURVEY.md §3.4 north-star stack —
ColumnParallelLinear/RowParallelLinear mp_layers.py:35,173, PipelineLayer
pp_layers.py, fused_attention/fused_feedforward CUDA ops).

TPU-native architecture:
- A *functional core* (init_gpt_params / gpt_forward / train_step): params
  are one pytree with per-block weights STACKED on a leading layer axis and
  the blocks applied with lax.scan — compile time stays O(1) in depth, and
  the stacked axis is what 'pp' shards for SPMD pipelining.
- Sharding is declarative: PARAM_SPECS maps each leaf to a PartitionSpec
  over ('dp','fsdp','pp','mp'); activations get with_sharding_constraint.
  TP = mp sharding of head/ffn dims (the ColumnParallel/RowParallel split),
  ZeRO-3 = 'fsdp' sharding of the remaining weight dim, SP = sequence
  sharding on 'mp' in the norm/residual regions (Megatron-SP), EP = expert
  axis sharding for the MoE variant. XLA GSPMD inserts all collectives.
- Attention runs through the fused flash-attention path
  (paddle_tpu.kernels) in bf16 — MXU-native.
- A thin `GPTModel` nn.Layer facade exposes the paddle-shaped API over the
  same functional core for eager/`to_static` use.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import get_mesh, constraint as mesh_constraint
from .facade import FacadeModel


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: Optional[int] = None          # default 4*hidden
    max_seq_len: int = 1024
    dropout: float = 0.0
    use_bias: bool = True
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16                 # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True                        # jax.checkpoint each block
    # remat selectivity (full-stack remat costs ~1/3 extra FLOPs
    # on models that fit without it): "full" rematerializes everything;
    # "dots" saves matmul/einsum outputs across the backward (XLA then only
    # recomputes cheap elementwise/norm work — the flash-attention kernel
    # keeps its own O(S·D) residuals via custom_vjp either way)
    # "full" | "dots" | "dots_flash" | "offload_dots":
    # - "dots" saves dot_general outputs (XLA recomputes elementwise only,
    #   but the Pallas attention — a pallas_call, not a dot — still reruns
    #   in the backward);
    # - "dots_flash" additionally saves the named flash-attention outputs
    #   (~B*S*D bf16 per layer) so no attention forward is recomputed;
    # - "offload_dots" saves dots to pinned host memory (HBM headroom);
    # - "all_but_mlp" checkpoints ONLY the dense FFN (nested, inside an
    #   otherwise unremat'd block) — near-no-remat speed at batches
    #   where true no-remat OOMs; recompute = the FFN forward per layer.
    # All raced on hardware once (2026-07; BASELINE.md "Earlier chip numbers").
    remat_policy: str = "full"
    # lax.scan unroll factor over the layer axis: >1 lets XLA fuse across
    # adjacent blocks at the cost of compile time; raced on hardware, the
    # default stays 1 (numerics identical either way)
    scan_unroll: int = 1
    # unroll for the CACHED decode path's layer scan (forward_cached):
    # at T=1 the matvecs are tiny and the loop's own per-layer steps
    # weigh in, so the serving engine auto-raises this for shallow
    # models; numerics are bit-identical either way
    decode_scan_unroll: int = 1
    sequence_parallel: bool = True            # SP on the 'mp' axis
    # context parallelism for long sequences: "none" | "ring" | "ulysses";
    # shards the sequence axis over the mesh's 'sp' axis ('mp' if absent)
    context_parallel: str = "none"
    # MoE (expert parallel) — 0 experts = dense FFN
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    moe_gate: str = "switch"          # parallel.moe.GATES: naive|switch|gshard
    moe_aux_weight: float = 0.01      # load-balancing loss coefficient
    # real pipeline parallelism (reference 1F1B/interleaved schedules,
    # fleet/meta_parallel/pipeline_parallel.py:188,565): >1 microbatches +
    # a pp>1 mesh routes the block stack through parallel.pipeline's SPMD
    # ppermute-ring schedule; 0/1 = layer-weight sharding only.
    # pipeline_interleave must stay 1: virtual stages are a measured
    # throughput loss in the scan formulation (perf/pipeline_ab.json);
    # interleaved 1F1B lives in parallel.host_pipeline.HostPipeline.
    pipeline_microbatches: int = 0
    pipeline_interleave: int = 1

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size
        assert self.hidden_size % self.num_heads == 0

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# --------------------------------------------------------------------------
# Sharding rules: leaf name -> PartitionSpec over (dp, fsdp, pp, mp).
# Block weights have a leading stacked layer axis -> 'pp'.
# --------------------------------------------------------------------------
PARAM_SPECS: Dict[str, P] = {
    "wte":        P("mp", "fsdp"),          # vocab-parallel embedding
    "wpe":        P(None, "fsdp"),
    "ln_f_scale": P(None),
    "ln_f_bias":  P(None),
    # stacked block params: leading axis = layer (pp)
    "ln1_scale":  P("pp", None),
    "ln1_bias":   P("pp", None),
    "ln2_scale":  P("pp", None),
    "ln2_bias":   P("pp", None),
    "qkv_w":      P("pp", "fsdp", "mp"),    # column-parallel
    "qkv_b":      P("pp", "mp"),
    "attn_out_w": P("pp", "mp", "fsdp"),    # row-parallel
    "attn_out_b": P("pp", None),
    "mlp_up_w":   P("pp", "fsdp", "mp"),    # column-parallel
    "mlp_up_b":   P("pp", "mp"),
    "mlp_down_w": P("pp", "mp", "fsdp"),    # row-parallel
    "mlp_down_b": P("pp", None),
    # MoE (expert axis 'ep')
    "gate_w":     P("pp", None, None),
    "moe_up_w":   P("pp", "ep", None, "mp"),
    "moe_up_b":   P("pp", "ep", "mp"),
    "moe_down_w": P("pp", "ep", "mp", None),
    "moe_down_b": P("pp", "ep", None),
}


# Serving/decode tensor-parallel specs: the SAME column/row split as
# PARAM_SPECS, remapped onto the serving mesh's single 'tp' axis
# (parallel.mesh.tp_specs — dp/fsdp/pp drop: the slot pool owns the
# batch and the layer stack scans on-chip at decode). Consumed by
# inference/serving.py `mesh=`; the KV cache's head axis shards
# through kernels/decode_attention.cache_pspecs.
from ..parallel.mesh import tp_specs as _tp_specs
SERVING_PARAM_SPECS: Dict[str, P] = _tp_specs(PARAM_SPECS)


def init_gpt_params(cfg: GPTConfig, key) -> Dict[str, jax.Array]:
    """Initialize the parameter pytree (host-side, then shard via
    paddle_tpu.parallel.mesh.shard_value per PARAM_SPECS)."""
    k = jax.random.split(key, 16)
    D, F, L, V = (cfg.hidden_size, cfg.ffn_hidden, cfg.num_layers,
                  cfg.vocab_size)
    std = 0.02
    pd = cfg.param_dtype

    def norm(key, shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    params = {
        "wte": norm(k[0], (V, D)),
        "wpe": norm(k[1], (cfg.max_seq_len, D), 0.01),
        "ln_f_scale": jnp.ones((D,), pd),
        "ln_f_bias": jnp.zeros((D,), pd),
        "ln1_scale": jnp.ones((L, D), pd),
        "ln1_bias": jnp.zeros((L, D), pd),
        "ln2_scale": jnp.ones((L, D), pd),
        "ln2_bias": jnp.zeros((L, D), pd),
        "qkv_w": norm(k[2], (L, D, 3 * D)),
        "qkv_b": jnp.zeros((L, 3 * D), pd),
        "attn_out_w": norm(k[3], (L, D, D), std / math.sqrt(2 * L)),
        "attn_out_b": jnp.zeros((L, D), pd),
    }
    if cfg.num_experts > 0:
        E = cfg.num_experts
        params.update({
            "gate_w": norm(k[4], (L, D, E)),
            "moe_up_w": norm(k[5], (L, E, D, F)),
            "moe_up_b": jnp.zeros((L, E, F), pd),
            "moe_down_w": norm(k[6], (L, E, F, D), std / math.sqrt(2 * L)),
            "moe_down_b": jnp.zeros((L, E, D), pd),
        })
    else:
        params.update({
            "mlp_up_w": norm(k[5], (L, D, F)),
            "mlp_up_b": jnp.zeros((L, F), pd),
            "mlp_down_w": norm(k[6], (L, F, D), std / math.sqrt(2 * L)),
            "mlp_down_b": jnp.zeros((L, D), pd),
        })
    return params


def shard_gpt_params(params, mesh=None):
    from ..parallel.mesh import shard_value, get_mesh as _gm
    mesh = mesh or _gm()
    if mesh is None:
        return params
    return {name: shard_value(v, PARAM_SPECS[name], mesh)
            for name, v in params.items()}


def _ln(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


def _model_axis():
    """The live model-parallel mesh-axis NAME for activation hints:
    'mp' by family convention, but the 3D/4D planner meshes
    (parallel/planner.py plan_train) name the remapped axis 'tp' — an
    'mp' hint there would make mesh_constraint degrade to identity
    (all-or-nothing), leaving GSPMD to guess the activation layouts
    (the audited involuntary reshards around the scan carry). Resolved
    per trace from the ambient mesh; None outside a mesh."""
    mesh = get_mesh()
    if mesh is None:
        return None
    for ax in ("mp", "tp"):
        if ax in mesh.axis_names:
            return ax
    return None


def _sp_constraint(x, cfg):
    """Sequence-parallel: shard (batch, seq) as (dp, mp) in norm regions."""
    if cfg.sequence_parallel:
        return mesh_constraint(x, P(("dp", "fsdp"), _model_axis(), None))
    return mesh_constraint(x, P(("dp", "fsdp"), None, None))


def _tp_constraint(x, cfg):
    """Inside attention/FFN: batch on dp, heads/features on mp."""
    return mesh_constraint(x, P(("dp", "fsdp"), None, _model_axis()))


def _attention(x, w_qkv, b_qkv, w_out, b_out, cfg, mask_causal=True):
    B, S, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    # Reshard hygiene (hlo_audit): the fused [D, q|k|v] weight's tp
    # shard tiles (3D/tp columns) straddle the q/k/v block boundaries
    # at D, so splitting a tp-sharded [B,S,3D] projection makes GSPMD
    # re-tile each block with involuntary collective-permutes inside
    # the layer scan (resharding_permute findings, once per layer per
    # direction). Gather the weight first — an all-gather over fsdp/mp,
    # the PLANNED ZeRO-3/Megatron spelling whose autodiff transpose is
    # the gradient reduce-scatter (the same gather-then-slice schedule
    # the full-manual pp step hand-writes in
    # parallel/pipeline_train._gpt_stage_compute) — project in ONE fused
    # einsum, pin the projection's feature dim replicated so the q/k/v
    # split is shard-local, then re-pin each projection head-parallel
    # (H is a multiple of the tp degree, so that slice is local too).
    # Concretely: reshape the gathered weight to [D, 3, H, hd] (free on
    # a replicated value) and project straight into head-structured
    # form — the projection is then tp-sharded on the HEAD dim with
    # block-aligned boundaries, and the q/k/v selection is an indexed
    # slice of an UNSHARDED dim, shard-local in both directions of
    # autodiff. Splitting a [B,S,3D] projection (or the weight) instead
    # leaves 3D/tp shard tiles straddling the block boundaries, which
    # the scan residual stash re-tiles with misaligned permutes.
    ax = _model_axis()
    w_qkv = mesh_constraint(w_qkv, P(None, None))
    w4 = w_qkv.astype(x.dtype).reshape(D, 3, H, hd)
    p = jnp.einsum("bsd,dkhf->bskhf", x, w4)
    if b_qkv is not None:
        b_qkv = mesh_constraint(b_qkv, P(None))
        p = p + b_qkv.astype(x.dtype).reshape(3, H, hd)
    p = mesh_constraint(p, P(("dp", "fsdp"), None, None, ax, None))
    head_spec = P(("dp", "fsdp"), None, ax, None)
    q, k_, v = (mesh_constraint(p[:, :, i], head_spec) for i in range(3))
    if cfg.context_parallel in ("ring", "ulysses"):
        from ..parallel.mesh import get_mesh
        from ..parallel.context_parallel import (ring_attention,
                                                 ulysses_attention)
        mesh = get_mesh()
        if mesh is None:
            raise ValueError(
                f"context_parallel={cfg.context_parallel!r} needs an active "
                "mesh (use paddle_tpu.parallel.mesh.use_mesh / "
                "set_global_mesh) with an 'sp' (or 'mp') axis")
        if "sp" in mesh.axis_names:
            axis = "sp"
        elif "mp" in mesh.axis_names:
            # Megatron-style reuse of the tensor-parallel axis: heads are
            # then gathered inside the CP shard_map, costing redundant
            # compute when mp>1 is also used for TP — prefer a dedicated
            # 'sp' axis for long-context runs
            axis = "mp"
        else:
            raise ValueError(
                f"context_parallel={cfg.context_parallel!r}: mesh "
                f"{dict(mesh.shape)} has neither an 'sp' nor an 'mp' axis")
        cp_fn = ring_attention if cfg.context_parallel == "ring" else \
            ulysses_attention
        ctx = cp_fn(q, k_, v, mesh, axis=axis, causal=mask_causal)
    else:
        from ..kernels.flash_attention import flash_attention_fn
        ctx = flash_attention_fn(q, k_, v, causal=mask_causal)
    # named so remat_policy="dots_flash" can SAVE the attention output:
    # the flash kernel is a pallas_call, not a dot_general, so the "dots"
    # policy alone recomputes all attention forwards in the backward
    from jax.ad_checkpoint import checkpoint_name
    ctx = checkpoint_name(ctx, "flash_out")
    ctx = mesh_constraint(ctx, head_spec)
    ctx = mesh_constraint(ctx.reshape(B, S, D),
                          P(("dp", "fsdp"), None, ax))
    # row-parallel output projection: the mp-sharded contraction leaves
    # per-rank partial sums — GSPMD's all-reduce here is the planned
    # Megatron activation reduction, and pinning the result replicated
    # on the feature dim stops the scan carry from flipping layouts
    out = jnp.einsum("bsd,df->bsf", ctx, w_out.astype(x.dtype))
    out = mesh_constraint(out, P(("dp", "fsdp"), None, None))
    if b_out is not None:
        out = out + b_out.astype(x.dtype)
    return out


def _dense_ffn(x, up_w, up_b, down_w, down_b):
    # column→row parallel Megatron pair; the explicit pins keep the
    # hidden activation's batch dim on the SAME ("dp","fsdp") merged
    # axis order as every other activation — without them the up
    # projection's autodiff transpose regroups the batch contraction in
    # (fsdp,dp) order and GSPMD bridges the two linearizations with a
    # collective-permute inside the scan (hlo_audit resharding_permute)
    ax = _model_axis()
    x = mesh_constraint(x, P(("dp", "fsdp"), None, None))
    up_w = mesh_constraint(up_w, P(None, None))
    h = jnp.einsum("bsd,df->bsf", x, up_w.astype(x.dtype))
    if up_b is not None:
        h = h + up_b.astype(x.dtype)
    h = mesh_constraint(h, P(("dp", "fsdp"), None, ax))
    h = jax.nn.gelu(h)
    down_w = mesh_constraint(down_w, P(None, None))
    out = jnp.einsum("bsf,fd->bsd", h, down_w.astype(x.dtype))
    out = mesh_constraint(out, P(("dp", "fsdp"), None, None))
    if down_b is not None:
        out = out + down_b.astype(x.dtype)
    return out


def _moe_ffn(x, gate_w, up_w, up_b, down_w, down_b, cfg):
    """Capacity-based expert-parallel MoE (parallel.moe GShard dispatch;
    reference incubate MoELayer moe_layer.py:261 + moe/gate zoo). Returns
    (y, aux load-balancing loss); expert_capacity_factor and moe_gate come
    from the config."""
    from ..parallel.moe import moe_ffn
    return moe_ffn(x, gate_w, up_w, up_b, down_w, down_b,
                   gate=cfg.moe_gate,
                   capacity_factor=cfg.expert_capacity_factor)


def _block(params_l, x, cfg):
    """One transformer block on stacked-layer slice params_l.
    Returns (x, aux) — aux is the MoE load-balancing loss (0 for dense)."""
    h = _sp_constraint(x, cfg)
    # the named scopes are metadata: they put the phase's name on every
    # HLO op (forward, recomputation and transpose) for the device trace
    with jax.named_scope("attention"):
        a_in = _ln(h, params_l["ln1_scale"], params_l["ln1_bias"],
                   cfg.layer_norm_eps)
        a = _attention(a_in, params_l["qkv_w"],
                       params_l.get("qkv_b"), params_l["attn_out_w"],
                       params_l.get("attn_out_b"), cfg)
    h = _sp_constraint(h + a, cfg)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        m_in = _ln(h, params_l["ln2_scale"], params_l["ln2_bias"],
                   cfg.layer_norm_eps)
        if cfg.num_experts > 0:
            m, aux = _moe_ffn(m_in, params_l["gate_w"],
                              params_l["moe_up_w"], params_l["moe_up_b"],
                              params_l["moe_down_w"],
                              params_l["moe_down_b"], cfg)
        else:
            ffn = _dense_ffn
            if cfg.remat and cfg.remat_policy == "all_but_mlp":
                # nested checkpoint JUST around the FFN: everything else
                # in the block is saved (no block-level remat for this
                # policy — see _apply_stack), but none of the 4D-wide
                # FFN internals can be (a names-based policy fails here:
                # gelu decomposes into unnamed elementwise primitives
                # whose outputs remain saveable, so the cut just moves
                # onto them)
                ffn = jax.checkpoint(_dense_ffn)
            m = ffn(m_in, params_l["mlp_up_w"], params_l.get("mlp_up_b"),
                    params_l["mlp_down_w"], params_l.get("mlp_down_b"))
    return _sp_constraint(h + m, cfg), aux


_BLOCK_KEYS_DENSE = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                     "qkv_w", "qkv_b", "attn_out_w", "attn_out_b",
                     "mlp_up_w", "mlp_up_b", "mlp_down_w", "mlp_down_b")
_BLOCK_KEYS_MOE = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                   "qkv_w", "qkv_b", "attn_out_w", "attn_out_b",
                   "gate_w", "moe_up_w", "moe_up_b", "moe_down_w",
                   "moe_down_b")


def _pipeline_active(cfg: GPTConfig) -> int:
    """Return the pp degree when the pipelined path should run, else 0."""
    if cfg.pipeline_microbatches <= 1:
        return 0
    mesh = get_mesh()
    if mesh is None or "pp" not in mesh.axis_names:
        return 0
    pp = mesh.shape["pp"]
    return pp if pp > 1 else 0


def _apply_stack(stacked, x, cfg: GPTConfig):
    """Apply the transformer block stack: pipelined over the 'pp' mesh axis
    when configured, else a layer-axis lax.scan (layer-weight sharding).
    Returns (x, aux) — the MoE load-balancing loss. Under the pipelined
    path the aux rides the ppermute ring with the activations
    (spmd_pipeline with_aux) and comes back as the microbatch mean."""
    pp = _pipeline_active(cfg)
    if pp:
        from ..parallel.pipeline import pipeline_forward
        m, v = cfg.pipeline_microbatches, cfg.pipeline_interleave
        n_chunks = pp * v
        L = cfg.num_layers
        B = x.shape[0]
        if L % n_chunks != 0:
            raise ValueError(
                f"num_layers={L} must be a multiple of "
                f"pp*interleave={n_chunks}")
        if B % m != 0:
            raise ValueError(
                f"batch={B} must be a multiple of "
                f"pipeline_microbatches={m}")
        chunked = {k: val.reshape((n_chunks, L // n_chunks) + val.shape[1:])
                   for k, val in stacked.items()}

        moe = cfg.num_experts > 0 and cfg.moe_aux_weight != 0.0

        if moe:
            # aux rides the ppermute ring with the activations (per-stage
            # accumulation, the reference's 1F1B aux handling)
            def stage_fn(chunk_params, h):
                def body_fn(carry, lp):
                    h, aux = carry
                    h2, aux_l = _block(lp, h, cfg)
                    return (h2, aux + aux_l), None
                # runs inside the pp-manual shard_map: the zero init must be
                # marked device-varying to match the scan's carry vma type
                aux0 = jax.lax.pcast(jnp.zeros((), jnp.float32), "pp",
                                     to="varying")
                (h, aux), _ = jax.lax.scan(body_fn, (h, aux0), chunk_params)
                return h, aux
        else:
            def stage_fn(chunk_params, h):
                def body_fn(h, lp):
                    h2, _aux = _block(lp, h, cfg)
                    return h2, None
                h, _ = jax.lax.scan(body_fn, h, chunk_params)
                return h

        x_mb = x.reshape((m, B // m) + x.shape[1:])
        # "all_but_mlp" already nests its checkpoint around the FFN in
        # _block; stacking the stage-level checkpoint on top would pay
        # full remat PLUS an extra FFN recompute
        stage_remat = cfg.remat and cfg.remat_policy != "all_but_mlp"
        if moe:
            y, aux_mb = pipeline_forward(stage_fn, chunked, x_mb, pp, m,
                                         interleave=v, remat=stage_remat,
                                         with_aux=True)
            return y.reshape(x.shape), jnp.mean(aux_mb)
        y = pipeline_forward(stage_fn, chunked, x_mb, pp, m,
                             interleave=v, remat=stage_remat)
        return y.reshape(x.shape), jnp.zeros((), jnp.float32)

    body = functools.partial(_block, cfg=cfg)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif cfg.remat_policy == "dots_flash":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        "flash_out")))
        elif cfg.remat_policy == "offload_dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                    "device", "pinned_host"))
        elif cfg.remat_policy == "all_but_mlp":
            # near-no-remat: NO block-level checkpoint — _block instead
            # nests jax.checkpoint around just the dense FFN, so the
            # 4D-wide hidden activations (what pushes true no-remat past
            # HBM at the bench batch) are recomputed (~16% of step
            # FLOPs) and everything else is saved
            pass
        else:
            body = jax.checkpoint(body)

    def scan_fn(carry, layer_params):
        h, aux = carry
        h2, aux_l = body(layer_params, h)
        return (h2, aux + aux_l), None

    (x, aux), _ = jax.lax.scan(
        scan_fn, (x, jnp.zeros((), jnp.float32)), stacked,
        unroll=cfg.scan_unroll)
    return x, aux


def _gpt_forward_impl(params, tokens, cfg: GPTConfig):
    """→ (logits [B,S,V], aux MoE loss)."""
    B, S = tokens.shape
    # Reshard hygiene (hlo_audit): a token gather from the
    # vocab-sharded table makes GSPMD reshard the gathered rows between
    # layouts (involuntary full rematerialization at this op). Gather
    # the table first — an all-gather over mp/fsdp, planned ZeRO-3
    # spelling, whose transpose reduce-scatters the embedding cotangent
    # back onto the shards — then the row lookup is rank-local. The
    # tied LM head below keeps consuming the SHARDED table: the
    # vocab-parallel matmul never needs full rows.
    with jax.named_scope("embed"):
        wte = mesh_constraint(params["wte"], P(None, None))
        x = jnp.take(wte, tokens, axis=0).astype(cfg.dtype)
        x = x + params["wpe"][:S][None].astype(cfg.dtype)
    x = _sp_constraint(x, cfg)

    block_keys = _BLOCK_KEYS_MOE if cfg.num_experts > 0 else _BLOCK_KEYS_DENSE
    stacked = {k: params[k] for k in block_keys if k in params}

    x, aux = _apply_stack(stacked, x, cfg)
    # re-pin the scan output: the layer scan's COTANGENT carry seeds
    # from this value's layout, and without the pin the unembed dgrad
    # hands the transpose scan a relinearized (fsdp-major) batch
    # assignment that GSPMD then bridges with a per-iteration
    # collective-permute inside the backward while loop
    x = _sp_constraint(x, cfg)
    with jax.named_scope("ce_head"):
        x = _ln(x, params["ln_f_scale"], params["ln_f_bias"],
                cfg.layer_norm_eps)
        # tied LM head (vocab-parallel matmul — mp shards the vocab dim)
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["wte"].astype(x.dtype))
    logits = mesh_constraint(logits, P(("dp", "fsdp"), None, _model_axis()))
    return logits, aux


def gpt_forward(params, tokens, cfg: GPTConfig):
    """tokens [B, S] int32 → logits [B, S, V] (compute dtype cfg.dtype)."""
    return _gpt_forward_impl(params, tokens, cfg)[0]


def gpt_loss(params, batch, cfg: GPTConfig):
    """Causal LM loss (+ MoE aux loss when experts are active);
    batch = (tokens[B,S+1]) or dict with input/labels.

    Fused cross-entropy: loss = mean(logsumexp(logits) - logit[target]).
    Mathematically identical to -mean(log_softmax[target]) but never
    materializes the [B,S,V] f32 log-prob tensor — the lse reduction and
    the target gather each stream the logits once, an HBM-bandwidth win
    at V=32k+ (the reference's fused softmax_with_cross_entropy kernel,
    phi/kernels/gpu/cross_entropy_kernel.cu, made the same trade)."""
    from .losses import fused_softmax_ce
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, aux = _gpt_forward_impl(params, inp, cfg)
    with jax.named_scope("ce_head"):
        loss = fused_softmax_ce(logits, tgt)
    if cfg.num_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


# --------------------------------------------------------------------------
# Fused train step (fwd + bwd + AdamW) — the unit bench/dryrun compile.
# --------------------------------------------------------------------------
def init_opt_state(params):
    return {
        "m": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "step": jnp.zeros((), jnp.float32),
    }


def apply_adamw(grads, params, opt_state, lr, beta1=0.9, beta2=0.95,
                eps=1e-8, weight_decay=0.1):
    """One fused AdamW update over the param tree (f32 master math,
    params cast back to their storage dtype). Shared by every flagship
    family's train_step (gpt, llama) so the update rule cannot drift.

    With `pallas_update.FUSED_UPDATE` set (it is not) the whole update
    runs on the TPU through the hand-tiled Pallas kernel
    (kernels/pallas_update.py — one launch per leaf, rule-for-rule these
    numerics); this jax form is the default and the parity oracle."""
    from ..kernels.pallas_update import fused_update_enabled
    if fused_update_enabled():
        from ..kernels.pallas_update import fused_apply_adamw
        return fused_apply_adamw(grads, params, opt_state, lr,
                                 beta1=beta1, beta2=beta2, eps=eps,
                                 weight_decay=weight_decay)
    step = opt_state["step"] + 1.0
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m_new = beta1 * m + (1 - beta1) * gf
        v_new = beta2 * v + (1 - beta2) * jnp.square(gf)
        den = jnp.sqrt(v_new / bc2) + eps
        p_new = p.astype(jnp.float32) * (1.0 - lr * weight_decay) - \
            lr * (m_new / bc1) / den
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    new = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(treedef, [n[0] for n in new])
    new_m = jax.tree_util.tree_unflatten(treedef, [n[1] for n in new])
    new_v = jax.tree_util.tree_unflatten(treedef, [n[2] for n in new])
    return new_params, {"m": new_m, "v": new_v, "step": step}


def train_step(params, opt_state, batch, cfg: GPTConfig, lr=3e-4,
               beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1):
    loss, grads = jax.value_and_grad(
        lambda p: gpt_loss(p, batch, cfg))(params)
    with jax.named_scope("optimizer"):
        new_params, new_opt = apply_adamw(
            grads, params, opt_state, lr, beta1=beta1, beta2=beta2,
            eps=eps, weight_decay=weight_decay)
    return loss, new_params, new_opt


# --------------------------------------------------------------------------
# nn.Layer facade (paddle-shaped API over the functional core)
# --------------------------------------------------------------------------
class GPTModel(FacadeModel):
    """Paddle-shaped facade: .parameters(), forward(tokens)->logits, works
    eagerly and under paddle_tpu.jit.to_static (the functional core runs
    as one traced op through the dispatch layer — plumbing shared with
    BertModel/ViTModel via models/facade.py)."""

    _serving_family = "gpt"

    def __init__(self, cfg: GPTConfig, seed: int = 0):
        super().__init__(
            cfg,
            lambda c, key: shard_gpt_params(init_gpt_params(c, key)),
            PARAM_SPECS, seed)

    def forward(self, tokens):
        cfg = self.cfg
        return self._dispatch(
            "gpt_forward",
            lambda params, tok: gpt_forward(params, tok, cfg), tokens)

    __call__ = forward

    def loss(self, tokens):
        cfg = self.cfg
        return self._dispatch(
            "gpt_loss",
            lambda params, tok: gpt_loss(params, tok, cfg), tokens)


# canonical configs (reference: GPT-3 table; 6.7B is BASELINE config 3)
GPT3_CONFIGS = {
    "125m": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "350m": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "1.3b": GPTConfig(hidden_size=2048, num_layers=24, num_heads=16),
    "2.7b": GPTConfig(hidden_size=2560, num_layers=32, num_heads=32),
    "6.7b": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                      max_seq_len=2048),
    "13b": GPTConfig(hidden_size=5120, num_layers=40, num_heads=40,
                     max_seq_len=2048),
}


# --------------------------------------------------------------------------
# KV-cache decode path (reference: FusedMultiTransformer inference decoder,
# incubate/nn/layer/fused_transformer.py:1022, and the inference
# AnalysisPredictor's decoder workloads). TPU-native: the cache is one
# stacked [L, B, max_len, H, hd] buffer per k/v, carried WHOLE through the
# layer scan (the stacked params and the layer index are what scans): each
# layer writes only the step's new rows at [layer, ...] in place and reads
# its own rows back for the attention, so no layer's cache is sliced out or
# restacked. Prefill writes the prompt's k/v while running the causal
# forward, decode steps are single-token attention over the cache (a
# bandwidth-bound matvec — flash tiling buys nothing at T=1; masking, or
# on a TPU the per-row live length, keeps kv_len dynamic under jit).
# --------------------------------------------------------------------------
def init_kv_cache(cfg: GPTConfig, batch: int, max_len: int):
    """→ {"k","v": [L, B, max_len, H, hd]} in the activation dtype."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _cached_attention(x, params_l, layer, kc, vc, pos, cfg, pt=None,
                      plan=None):
    """Block `layer`'s attention with cache update. x [B,T,D]; kc/vc
    the STACKED pools [L,B,max_len,H,hd] (dense) or [L,P,page_size,H,hd]
    pages with the per-slot page table `pt` [B,max_pages] (the serving
    engine's paged pool); pos = number of tokens already in the cache —
    a scalar (whole-batch decode) or a [B] vector of per-row positions
    (the serving engine's slot pool, where every slot advances
    independently). Returns (attn_out, kc, vc) — the same pools with
    the step's rows written at [layer, ...]. The cache write and the
    masked attention go through the decode-attention seam
    (kernels/decode_attention.py);
    the paged path scatters the write through the table and attends a
    gathered per-slot view — bit-identical to the dense layout. The
    dense path hands the seam the pools whole with `layer` and the
    forward's `plan` (`live_block_plan`): where there is one, the
    attention reads only each row's live blocks."""
    B, T, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    from ..kernels.quant_matmul import leaf_matmul
    qkv = leaf_matmul(x, params_l, "qkv_w")
    if params_l.get("qkv_b") is not None:
        qkv = qkv + params_l["qkv_b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, H, hd)
    v = v.reshape(B, T, H, hd)
    from ..kernels.decode_attention import (cached_attention, layer_view,
                                            write_kv, write_kv_paged)
    with jax.named_scope("kv_update"):
        if pt is None:
            kc = write_kv(kc, k, pos, layer)
            vc = write_kv(vc, v, pos, layer)
        else:
            kc = write_kv_paged(kc, pt, k, pos, layer)
            vc = write_kv_paged(vc, pt, v, pos, layer)
    with jax.named_scope("decode_attention"):
        if pt is None:
            ctx = cached_attention(q, kc, vc, pos, layer=layer, plan=plan)
        else:
            ctx = cached_attention(q, layer_view(kc, layer, pt),
                                   layer_view(vc, layer, pt), pos)
    ctx = ctx.reshape(B, T, D).astype(x.dtype)
    out = leaf_matmul(ctx, params_l, "attn_out_w")
    if params_l.get("attn_out_b") is not None:
        out = out + params_l["attn_out_b"].astype(x.dtype)
    return out, kc, vc


def gpt_forward_cached(params, tokens, cache, pos, cfg: GPTConfig,
                       layers: Optional[int] = None, live=None):
    """Forward `tokens` [B,T] against a cache holding `pos` tokens.
    → (logits [B,T,V], updated cache). Works for prefill (pos=0, T=prompt)
    and decode (T=1), for dense and MoE configs (reference: the inference
    decoder's global_scatter path — here the same capacity dispatch runs
    on the decode tokens; the aux load-balancing loss is discarded at
    inference). `pos` may be a traced scalar (whole-batch decode; the
    bucketed models/decode.py driver passes the true prompt length) or a
    [B] vector of per-row slot positions (inference/serving.py: each
    slot holds its own request mid-stream).

    `layers` (static) truncates the layer scan to the FIRST `layers`
    blocks, with the final norm + tied LM head applied to the
    truncated stack's output — the self-draft pass of speculative
    decoding (inference/spec_decode.py). The cache must then be the
    matching first-`layers` view ({"k","v": [layers, ...]}); layer k's
    K/V depends only on layers below it, so the truncated pass's
    writes are bit-identical to the full pass's first `layers` layers.

    Cache layouts: dense {"k","v": [L, B, max_len, H, hd]} or the
    serving engine's paged pool {"k","v": [L, P, page_size, H, hd],
    "pt": [B, max_pages]} — the page table rides the cache dict and is
    returned unchanged; the per-layer write/attend goes through the
    paged seam (kernels/decode_attention.py) and is bit-identical to
    the dense layout.

    `live` [B, T] bool marks the rows that are requests (the serving
    tick's active mask): nothing here computes differently for it — it
    only tells the dense pool's decode attention which rows have
    nothing to read."""
    B, T = tokens.shape
    pt = cache.get("pt")
    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
        if jnp.ndim(pos) == 0:
            wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], pos, T,
                                               axis=0)[None]
        else:
            # mode="clip": the serving decode tick parks inactive rows
            # at an out-of-table sentinel position (their K/V scatters
            # to the scratch page); the default "fill" would embed them
            # as NaN, and NaN written to scratch poisons every later
            # gather of it
            wpe = jnp.take(params["wpe"],
                           pos[:, None] + jnp.arange(T), axis=0,
                           mode="clip")
        x = x + wpe.astype(cfg.dtype)

    block_keys = _BLOCK_KEYS_MOE if cfg.num_experts > 0 else _BLOCK_KEYS_DENSE
    # weight-only int8 serving (quantization/serving.py): quantized
    # trees drop the fp matmul leaves and carry <name>_q/<name>_scale
    # instead — both stacked on the same leading layer axis, so they
    # ride the scan (and the layers= draft slice) like the fp weights
    block_keys = block_keys + tuple(
        k2 for k in block_keys for k2 in (k + "_q", k + "_scale"))
    stacked = {k: params[k] for k in block_keys if k in params}
    n_layers = cfg.num_layers
    if layers is not None:
        stacked = {k: v[:layers] for k, v in stacked.items()}
        n_layers = int(layers)
    from ..kernels.decode_attention import live_block_plan
    from ..kernels.quant_matmul import leaf_matmul, quant_matmul
    plan = None if pt is not None else live_block_plan(
        T, cache["k"], pos, live)

    def scan_fn(carry, layer_in):
        h, kc, vc = carry
        params_l, layer = layer_in
        with jax.named_scope("attention"):
            a_in = _ln(h, params_l["ln1_scale"], params_l["ln1_bias"],
                       cfg.layer_norm_eps)
            a, kc, vc = _cached_attention(a_in, params_l, layer, kc, vc,
                                          pos, cfg, pt=pt, plan=plan)
        h = h + a
        with jax.named_scope("mlp"):
            m_in = _ln(h, params_l["ln2_scale"], params_l["ln2_bias"],
                       cfg.layer_norm_eps)
            if cfg.num_experts > 0:
                m, _aux = _moe_ffn(m_in, params_l["gate_w"],
                                   params_l["moe_up_w"],
                                   params_l["moe_up_b"],
                                   params_l["moe_down_w"],
                                   params_l["moe_down_b"], cfg)
            else:
                # leaf_matmul-routed FFN (same contraction as
                # _dense_ffn; the quantized tree swaps each matmul for
                # the fused dequant-matmul per leaf)
                mh = leaf_matmul(m_in, params_l, "mlp_up_w")
                if params_l.get("mlp_up_b") is not None:
                    mh = mh + params_l["mlp_up_b"].astype(mh.dtype)
                mh = jax.nn.gelu(mh)
                m = leaf_matmul(mh, params_l, "mlp_down_w")
                if params_l.get("mlp_down_b") is not None:
                    m = m + params_l["mlp_down_b"].astype(m.dtype)
        return (h + m, kc, vc), None

    # the pools ride the CARRY, never xs/ys: as xs the scan would slice
    # each layer out into its own buffer, as ys restack every layer's
    # whole slice into a new pool and copy that onto the donated one
    (x, kcs, vcs), _ = jax.lax.scan(
        scan_fn, (x, cache["k"], cache["v"]),
        (stacked, jnp.arange(n_layers, dtype=jnp.int32)),
        unroll=max(1, min(getattr(cfg, "decode_scan_unroll", 1),
                          n_layers)))
    with jax.named_scope("lm_head"):
        x = _ln(x, params["ln_f_scale"], params["ln_f_bias"],
                cfg.layer_norm_eps)
        if "head_q" in params:
            # quantized tied head: a transposed int8 copy ([D, V] +
            # per-vocab scales) so `wte` itself stays fp for the
            # embedding gather (quantization/serving.py)
            logits = quant_matmul(x, params["head_q"],
                                  params["head_scale"])
        else:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["wte"].astype(x.dtype))
    out = {"k": kcs, "v": vcs}
    if pt is not None:
        out["pt"] = pt
    return logits, out


def greedy_generate(params, prompt, cfg: GPTConfig, max_new_tokens: int,
                    max_len: Optional[int] = None):
    """Greedy decode through the KV cache (shared driver:
    models/decode.py). prompt [B, T0] → [B, T0 + max_new_tokens]."""
    from .decode import greedy_generate_with
    return greedy_generate_with(gpt_forward_cached, init_kv_cache,
                                params, prompt, cfg, max_new_tokens,
                                max_len)
