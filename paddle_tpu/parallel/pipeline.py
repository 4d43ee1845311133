"""Pipeline parallelism, SPMD-style.

Reference analog: PipelineLayer (fleet/meta_parallel/parallel_layers/
pp_layers.py), the 1F1B / interleaved schedules
(meta_parallel/pipeline_parallel.py:188,565), and the P2P tensor exchange
(pp_utils/p2p_communication.py:733).

TPU-native redesign: instead of per-rank processes exchanging tensors over
NCCL P2P under a host-driven 1F1B schedule, the WHOLE pipeline is one SPMD
program: stage parameters are stacked on a leading axis sharded over the
'pp' mesh axis, and a lax.scan over (microbatches + stages - 1) ticks moves
activations between neighbouring stages with lax.ppermute over ICI. Every
stage computes on every tick (after warmup), which IS the GPipe/1F1B
steady-state — but scheduled by XLA, overlapping the ppermute transfer with
the next microbatch's compute. Backward is jax autodiff through the scan:
the reverse pass replays the schedule in reverse (cooldown/warmup swap),
with jax.checkpoint on the stage body bounding activation memory like the
reference's recompute-in-1F1B.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from .mesh import get_mesh


def vary(x, axes):
    """Retype `x` as device-varying over every axis of `axes` it does not
    vary over yet (`jax.lax.pcast` refuses an axis that already is)."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def bubble_fraction(n_stages: int, n_microbatches: int,
                    interleave: int = 1) -> float:
    """Idle fraction of the SPMD schedule: warmup+cooldown ticks over total.
    GPipe-circulate (interleave=1): (p-1)/(m+p-1)."""
    dead = interleave * n_stages - 1
    return dead / (n_microbatches + dead)


def naive_bubble_fraction(n_stages: int) -> float:
    """Layer-sharded sequential execution: only 1/p stages busy at a time."""
    return 1.0 - 1.0 / n_stages


def spmd_pipeline(stage_fn: Callable, n_stages: int, n_microbatches: int,
                  axis_name: str = "pp", interleave: int = 1,
                  with_aux: bool = False, schedule_stats: bool = False):
    """Lift `stage_fn(chunk_params, x) -> y` into a pipelined
    `fn(stacked_params, microbatched_x) -> microbatched_y`.

    stacked_params: pytree with leading dim n_stages (one chunk per
    stage). microbatched_x: [n_microbatches, mb, ...].

    Schedule: one lax.scan over m + p - 1 ticks. Every tick computes the
    local stage (one stage-equivalent of FLOPs), ppermutes the
    activation to the next device, and stage 0 ingests the next
    microbatch. Backward is jax autodiff through the scan: the reverse
    replays the schedule in reverse (cooldown/warmup swap), which IS the
    1F1B-shaped backward, scheduled by XLA with the ppermute overlapping
    the next tick's compute.

    Must be called inside a shard_map manual over `axis_name` with
    `check_vma=True`, where each rank holds its leading-dim slice. The
    ring's carries are typed varying over `axis_name` plus whatever manual
    axes `microbatched_x` already varies over, and `stage_fn` must hand
    back an activation of the type it was given (as it must the shape).

    with_aux=True: `stage_fn(chunk_params, x) -> (y, aux_scalar)` and each
    microbatch's aux accumulates ALONG ITS JOURNEY — a per-slot f32 rides
    the same ppermute ring as the activation (zeroed at ingestion, summed
    per stage hop, emitted with the final activation). This is how the MoE
    load-balancing loss circulates under pipeline parallelism (the
    reference accumulates it per stage in the 1F1B loop). Returns
    (outputs, aux_per_microbatch [m]).

    interleave>1 is NOT supported here: with scan-synchronous ticks the
    bubble is (v*p-1)/(m+v*p-1), strictly worse than v=1 — measured
    +14% step time at v=2 on the A/B harness (tools/ab_pipeline.py,
    perf/pipeline_ab.json). Virtual-stage interleaving genuinely helps
    only under the host-driven schedule, where it lives:
    parallel.host_pipeline.HostPipeline (measured -21% at v=2).

    schedule_stats=True: the scan additionally counts USEFUL stage-tick
    slots in-jit (stage s holds a real microbatch on ticks
    [s, s+m) — the warmup/cooldown slots compute on garbage, which IS
    the bubble) and returns (outputs, {"busy", "ticks", "stages"}) —
    busy psum'd over the pp axis, so
    1 - busy / (stages·ticks) is the MEASURED schedule bubble the
    train.bubble_fraction gauge publishes
    (parallel/pipeline_train.py). Mutually exclusive with with_aux
    (the MoE path has no consumer for it yet).
    """
    if interleave != 1:
        raise ValueError(
            "spmd_pipeline no longer takes interleave>1: the scan-"
            "synchronous formulation makes virtual stages a strict "
            "throughput loss (see perf/pipeline_ab.json). Use "
            "parallel.host_pipeline.HostPipeline for interleaved 1F1B.")
    if schedule_stats and with_aux:
        raise ValueError("schedule_stats does not compose with with_aux")
    p = n_stages

    def pipelined(local_params, x_mb):
        # local_params leading dim is 1 (this rank's chunk)
        chunk = jax.tree_util.tree_map(lambda a: a[0], local_params)
        stage = jax.lax.axis_index(axis_name)
        n_ticks = n_microbatches + p - 1
        mb_shape = x_mb.shape[1:]
        perm = [(i, (i + 1) % p) for i in range(p)]
        # scan and cond need one vma type per carry: the activation's own
        # axes plus the ring's
        ring_axes = tuple(jax.typeof(x_mb).vma | {axis_name})

        # with_aux is a trace-time constant: the aux ring (its carry,
        # ppermute) exists ONLY when requested — the dense pipeline
        # carries no dead collectives
        def tick(carry, t):
            busy = None
            if with_aux:
                state, aux_state, outputs, aux_out = carry
            elif schedule_stats:
                state, outputs, busy = carry
            else:
                state, outputs = carry
            # stage 0 ingests microbatch t (clamped); every other stage
            # keeps its circulating activation
            idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = vary(
                jax.lax.dynamic_index_in_dim(x_mb, idx, 0, keepdims=False),
                ring_axes)
            inp = jnp.where(stage == 0, inject, state)
            # the last stage finishes hop p-1: emit microbatch t - (p-1)
            out_idx = t - (p - 1)
            emit = jnp.logical_and(stage == p - 1, out_idx >= 0)
            if with_aux:
                aux_in = jnp.where(stage == 0, 0.0, aux_state)
                out, aux_delta = stage_fn(chunk, inp)
                aux_new = aux_in + aux_delta
                outputs, aux_out = jax.lax.cond(
                    emit,
                    lambda o, a: (
                        jax.lax.dynamic_update_index_in_dim(
                            o, out, jnp.maximum(out_idx, 0), 0),
                        jax.lax.dynamic_update_index_in_dim(
                            a, aux_new, jnp.maximum(out_idx, 0), 0)),
                    lambda o, a: (o, a), outputs, aux_out)
            else:
                out = stage_fn(chunk, inp)
                outputs = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, out, jnp.maximum(out_idx, 0), 0),
                    lambda o: o, outputs)
            # the ring hop p-1 -> 0 delivers a finished activation to
            # stage 0, where the next tick's injection overwrites it
            state = jax.lax.ppermute(out, axis_name, perm)
            if with_aux:
                aux_state = jax.lax.ppermute(aux_new, axis_name, perm)
                return (state, aux_state, outputs, aux_out), None
            if schedule_stats:
                # a stage-tick slot is USEFUL iff this stage holds a
                # real microbatch: stage s works on mb (t - s) — in
                # range exactly for t in [s, s+m)
                useful = jnp.logical_and(t >= stage,
                                         t < stage + n_microbatches)
                busy = busy + useful.astype(busy.dtype)
                return (state, outputs, busy), None
            return (state, outputs), None

        state0 = vary(jnp.zeros(mb_shape, x_mb.dtype), ring_axes)
        outputs0 = vary(jnp.zeros((n_microbatches,) + mb_shape, x_mb.dtype),
                        ring_axes)
        if with_aux:
            aux0 = vary(jnp.zeros((), jnp.float32), ring_axes)
            aux_out0 = vary(jnp.zeros((n_microbatches,), jnp.float32),
                            ring_axes)
            (_, _, outputs, aux_out), _ = jax.lax.scan(
                tick, (state0, aux0, outputs0, aux_out0),
                jnp.arange(n_ticks))
        elif schedule_stats:
            busy0 = vary(jnp.zeros((), jnp.float32), (axis_name,))
            (_, outputs, busy), _ = jax.lax.scan(
                tick, (state0, outputs0, busy0), jnp.arange(n_ticks))
        else:
            (_, outputs), _ = jax.lax.scan(
                tick, (state0, outputs0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; masked psum broadcasts
        # them to every pp rank so the loss is computable everywhere
        if p > 1:
            mask = (stage == p - 1).astype(outputs.dtype)
            outputs = jax.lax.psum(outputs * mask, axis_name)
            if with_aux:
                aux_out = jax.lax.psum(
                    aux_out * (stage == p - 1).astype(aux_out.dtype),
                    axis_name)
        if with_aux:
            return outputs, aux_out
        if schedule_stats:
            stats = {"busy": jax.lax.psum(busy, axis_name),
                     "ticks": float(n_ticks), "stages": float(p)}
            return outputs, stats
        return outputs

    return pipelined


def pipeline_forward(stage_fn, stacked_params, x_mb, n_stages,
                     n_microbatches, mesh=None, interleave: int = 1,
                     remat=True, with_aux: bool = False):
    """Run the SPMD pipeline as a global computation via shard_map.

    stacked_params: global arrays with leading dim n_stages (stage s =
    layers [s*per:(s+1)*per]). x_mb: [n_micro, micro_batch, ...] global
    input. Only the 'pp' axis goes manual; dp/mp/fsdp shardings inside
    stage_fn stay under GSPMD (partial-auto shard_map). interleave must
    be 1 (see spmd_pipeline; HostPipeline owns virtual stages).
    with_aux: stage_fn returns (y, aux_scalar); result is (y_mb, aux [m]).
    """
    mesh = mesh or get_mesh()
    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)
    piped = spmd_pipeline(body, n_stages, n_microbatches,
                          interleave=interleave, with_aux=with_aux)
    param_specs = jax.tree_util.tree_map(lambda _: P("pp"), stacked_params)
    # check_vma=True is load-bearing: partial-manual shard_map with
    # check_vma=False is broken in jax 0.9 (its internal _unmatch builds a
    # spec over ALL mesh axes and rejects itself). The masked-psum output
    # broadcast makes the result genuinely replicated over pp, so the vma
    # check passes.
    sm = jax.shard_map(
        piped, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=(P(), P()) if with_aux else P(),
        axis_names={"pp"},
        check_vma=True)
    return sm(stacked_params, x_mb)


class LayerDesc:
    """reference pp_layers.py LayerDesc — deferred layer construction."""

    def __init__(self, layer_class, *args, **kwargs):
        self.layer_class = layer_class
        self.args = args
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_class(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    def __init__(self, key, layer_class, forward_func=None,
                 shared_weight_attr="weight", *args, **kwargs):
        super().__init__(layer_class, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer:
    """reference pp_layers.py PipelineLayer (887 LoC actor-sliced version).

    TPU redesign: builds ALL layers in one process (single-controller), and
    partitions them into `num_stages` segments. Under GSPMD the segments
    stay one program; when the segments are homogeneous the model can use
    spmd_pipeline for true pipelining. seg_method mirrors the reference's
    'uniform' / 'layer:<cls>' splitting.
    """

    def __init__(self, layers, num_stages=None, topology=None,
                 loss_fn=None, seg_method="uniform", recompute_interval=0,
                 **kwargs):
        from ..nn.layer import Layer as NNLayer
        from ..nn.layers.container import LayerList
        descs = list(layers)
        self._loss_fn = loss_fn
        self.num_stages = num_stages or 1
        built = []
        for d in descs:
            if isinstance(d, LayerDesc):
                built.append(d.build_layer())
            elif callable(d) and not isinstance(d, NNLayer):
                built.append(d)
            else:
                built.append(d)
        self._layers_all = built
        bounds = self._segment(len(built), self.num_stages)
        self.segments = [built[bounds[i]:bounds[i + 1]]
                         for i in range(self.num_stages)]
        # single-controller: this object runs ALL stages (GSPMD partitions)
        holder = LayerList([l for l in built if isinstance(l, NNLayer)])
        self._holder = holder

    @staticmethod
    def _segment(n, stages):
        per = n // stages
        rem = n % stages
        bounds = [0]
        for i in range(stages):
            bounds.append(bounds[-1] + per + (1 if i < rem else 0))
        return bounds

    def parameters(self):
        return self._holder.parameters()

    def named_parameters(self, *a, **k):
        return self._holder.named_parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._holder.state_dict(*a, **k)

    def set_state_dict(self, sd, *a, **k):
        return self._holder.set_state_dict(sd, *a, **k)

    def train(self):
        self._holder.train()
        return self

    def eval(self):
        self._holder.eval()
        return self

    def forward(self, x):
        for f in self._layers_all:
            x = f(x)
        return x

    __call__ = forward

    def get_stage_from_index(self, idx):
        for s, seg in enumerate(self.segments):
            base = sum(len(x) for x in self.segments[:s])
            if base <= idx < base + len(seg):
                return s
        return self.num_stages - 1
