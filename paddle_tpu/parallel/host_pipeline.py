"""Host-driven pipeline parallelism: per-stage compiled fns + 1F1B loop.

Reference analog: the FleetExecutor/PipelineParallel host schedule —
1F1B and its interleaved virtual-stage variant
(python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:188,
565) issuing per-stage programs with P2P activation exchange
(pp_utils/p2p_communication.py:733).

TPU-native translation (single-controller): each chunk of layers is a
separately-jitted function whose parameters live on one device of the
'pp' axis; the host loop issues forward/backward calls in 1F1B order and
JAX's async dispatch + per-device FIFO queues realize the overlap — a
transfer becomes the data dependence that used to be a NCCL P2P, and the
device starts a microbatch the moment its input lands. The backward
recomputes the stage forward (jax.vjp inside the jitted bwd), which is
the reference's recompute-in-1F1B memory behavior.

This is the multi-executable alternative to parallel.pipeline's
single-program SPMD formulation. Trade-offs, measured in
tools/ab_pipeline.py (results in perf/pipeline_ab.json):
- the SPMD scan is one XLA program — no per-call dispatch cost, works
  inside jit/grad, and is what the planner runs (this path issues
  O(m * v * p) dispatches a step);
- the host loop supports TRUE interleaved virtual stages: a microbatch
  makes v shorter hops around the ring, so warmup shrinks and the bubble
  is ~(p-1)/(v*m) instead of the scan formulation's (v*p-1)/(m+v*p-1),
  which strictly worsens with v. Interleave>1 therefore lives HERE, not
  in spmd_pipeline.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from .mesh import get_mesh

__all__ = ["HostPipeline", "stage_devices"]


def stage_devices(mesh=None, axis: str = "pp"):
    """One representative device per pp rank (the first along every other
    mesh axis)."""
    import numpy as np
    mesh = mesh or get_mesh()
    idx = mesh.axis_names.index(axis)
    arr = np.moveaxis(mesh.devices, idx, 0)
    # arr[i] is a bare Device for a 1-D (pure-pp) mesh; ravel handles both
    return [np.ravel(arr[i])[0] for i in range(arr.shape[0])]


class HostPipeline:
    """Build-once host-scheduled pipeline; call `grads` per step.

    stage_fn(chunk_params, x) -> y. Chunk c's parameters are placed on
    pp device c % n_stages, so interleave>1 round-robins chunks exactly
    like the reference's virtual stages. The per-stage executables are
    created once here and reused every step (jax.jit caches on the
    committed device: p forward + p backward compiles total).

    Scope: pure-pp, single-controller-local. Each stage runs on ONE
    device (the first along every other mesh axis) — on a hybrid
    dp x pp x mp mesh the other axes sit idle here; hybrid topologies
    pipeline through parallel.pipeline's SPMD formulation, which keeps
    dp/mp under GSPMD inside each stage.
    """

    def __init__(self, stage_fn: Callable, loss_fn: Callable,
                 n_stages: int, n_microbatches: int, interleave: int = 1,
                 mesh=None):
        self.p = n_stages
        self.v = interleave
        self.m = n_microbatches
        self.n_chunks = n_stages * interleave
        self.devs = stage_devices(mesh, "pp")

        @jax.jit
        def fwd(params, x):
            # x is NOT donated: the same buffer is held in `acts` until
            # this microbatch's backward replays the stage
            return stage_fn(params, x)

        # dy is consumed at its only use, so its buffer is donated and
        # dx aliases it (same shape/dtype for equal-width stages) —
        # one fewer activation-sized live buffer per in-flight backward.
        # x is NOT donated even though acts has popped it: for chunk 0
        # the device_put in issue_fwd is a no-op when the microbatch
        # already lives on stage 0, so the saved activation IS the
        # caller's input buffer and donating it would invalidate x_mb
        # between steps. params stay undonated (reused every microbatch).
        @functools.partial(jax.jit, donate_argnums=(2,))
        def bwd(params, x, dy):
            # recompute-in-backward: vjp replays the stage forward
            _, pull = jax.vjp(stage_fn, params, x)
            return pull(dy)

        # y (the last stage's output) is consumed here; dy aliases it
        @functools.partial(jax.jit, donate_argnums=(0,))
        def loss_and_grad(y):
            return jax.value_and_grad(loss_fn)(y)

        self._fwd, self._bwd, self._lg = fwd, bwd, loss_and_grad

    def place(self, stacked_params) -> List:
        """Split the stacked (leading dim = n_chunks, natural order)
        param pytree into per-chunk trees pinned to their stage device.
        Accepts any pytree, like pipeline_forward does."""
        leaves, _ = jax.tree_util.tree_flatten(stacked_params)
        for a in leaves:
            if a.shape[0] != self.n_chunks:
                raise ValueError(
                    f"a param leaf has leading dim {a.shape[0]}, "
                    f"expected n_stages*interleave={self.n_chunks}")
        return [jax.tree_util.tree_map(
                    lambda a: jax.device_put(a[c], self.devs[c % self.p]),
                    stacked_params)
                for c in range(self.n_chunks)]

    def grads(self, chunk_params: List[Dict], x_mb):
        """One 1F1B step -> (mean microbatch loss, per-chunk grad list).

        Host-level 1F1B: tick t injects microbatch t's forward chain
        and, once the pipeline is full, drains microbatch t-(p-1)'s
        backward chain. Issue order is the schedule; per-device FIFO
        queues overlap the execution. Activations are held per
        (microbatch, chunk) until their backward consumes them — the
        host-side analog of the reference's p2p buffer bookkeeping.
        """
        p, m, n_chunks = self.p, self.m, self.n_chunks
        acts: Dict = {}
        losses = []
        grads: List = [None] * n_chunks

        def issue_fwd(i):
            x = x_mb[i]
            for c in range(n_chunks):
                # the P2P hop: an async device_put onto the next stage's
                # device is the transfer the reference does over NCCL
                x = jax.device_put(x, self.devs[c % p])
                acts[(i, c)] = x
                x = self._fwd(chunk_params[c], x)
            return x

        def issue_bwd(i, y):
            lval, dy = self._lg(y)
            losses.append(lval)
            for c in reversed(range(n_chunks)):
                dy = jax.device_put(dy, self.devs[c % p])
                x = acts.pop((i, c))
                dparams, dy = self._bwd(chunk_params[c], x, dy)
                grads[c] = dparams if grads[c] is None else \
                    jax.tree_util.tree_map(jnp.add, grads[c], dparams)

        outs: Dict[int, jax.Array] = {}
        for t in range(m + p - 1):
            if t < m:
                outs[t] = issue_fwd(t)
            done = t - (p - 1)
            if done >= 0:
                issue_bwd(done, outs.pop(done))

        loss = jnp.mean(jnp.stack([jax.device_put(l, self.devs[0])
                                   for l in losses]))
        inv_m = 1.0 / m
        grads = [jax.tree_util.tree_map(lambda g: g * inv_m, g)
                 for g in grads]
        return loss, grads

    def gather_stacked(self, grads: List):
        """Per-chunk grad list -> stacked host-side arrays in natural
        chunk order (for parity checks / host optimizers). Accepts any
        pytree, mirroring place()."""
        import numpy as np
        return jax.tree_util.tree_map(
            lambda *leaves: np.stack(
                [np.asarray(jax.device_get(l)) for l in leaves]),
            *grads)
