"""Mixture-of-Experts: gates, capacity-based dispatch, expert parallelism.

Reference analog: the incubate MoE stack —
/root/reference/python/paddle/incubate/distributed/models/moe/moe_layer.py:261
(MoELayer over global_scatter/global_gather NCCL all-to-all) and the gate zoo
moe/gate/{naive,switch,gshard}_gate.py.

TPU-native redesign: the GShard dense-dispatch formulation. Routing builds
one-hot dispatch/combine tensors [T, E, C] (C = capacity); token->expert
transport is the einsum contraction 'td,tec->ecd' whose expert axis is
sharded over the 'ep' mesh axis — XLA GSPMD lowers the contraction to the
ICI all-to-all that the reference performs with NCCL global_scatter. No
host-driven routing, fully jit/vjp compatible, static shapes (dropped
tokens beyond capacity contribute zero, exactly like the reference's
capacity overflow).

Beside it, for serving: a DROPLESS layer (`sigmoid_topk`,
`dropless_experts`) that is told which experts of the published count
it holds, routes over all of them, and computes its own experts' part
for exactly the tokens that chose them — sort by expert, one grouped
matmul per projection (`jax.lax.ragged_dot`). No capacity, so a token's
output never depends on which other tokens share its step. What the
absent experts would add is left out: there is no exchange here and no
code that stands in for one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import get_mesh, constraint as mesh_constraint


def compute_capacity(num_tokens: int, num_experts: int,
                     capacity_factor: float, min_capacity: int = 4) -> int:
    """Per-expert token slots (reference switch/gshard capacity rule)."""
    cap = int(np.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def topk_gating(probs, k: int, capacity: int, normalize: bool = None):
    """GShard top-k routing with per-expert capacity.

    probs: [T, E] softmax gate probabilities.
    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights,
    aux_loss scalar). Tokens assigned past an expert's capacity are
    dropped (their dispatch/combine rows are zero).

    normalize: renormalize combine weights over the token's KEPT choices.
    Default: True for k>1 (GShard top-2 semantics), False for k=1 —
    Switch-Transformer scales the expert output by the RAW gate
    probability so the router receives gradient through the task loss.
    """
    if normalize is None:
        normalize = k > 1
    T, E = probs.shape
    remaining = probs
    prior_count = jnp.zeros((E,), probs.dtype)
    dispatch = jnp.zeros((T, E, capacity), probs.dtype)
    gate_kept = jnp.zeros((T,), probs.dtype)
    combine = jnp.zeros((T, E, capacity), probs.dtype)

    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                   # [T]
        mask = jax.nn.one_hot(idx, E, dtype=probs.dtype)       # [T, E]
        pos = jnp.cumsum(mask, axis=0) - 1.0 + prior_count[None, :]
        pos_tok = jnp.sum(pos * mask, axis=-1)                 # [T]
        keep = (pos_tok < capacity).astype(probs.dtype)        # [T]
        kept_mask = mask * keep[:, None]
        prior_count = prior_count + jnp.sum(kept_mask, axis=0)
        gate_val = jnp.sum(probs * kept_mask, axis=-1)         # [T]
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                              dtype=probs.dtype)               # [T, C]
        d = kept_mask[:, :, None] * slot[:, None, :]           # [T, E, C]
        dispatch = dispatch + d
        combine = combine + gate_val[:, None, None] * d
        gate_kept = gate_kept + gate_val
        remaining = remaining * (1.0 - mask)

    if normalize:
        denom = jnp.maximum(gate_kept, 1e-9)
        combine = combine / denom[:, None, None]

    # load-balancing aux loss (switch eq. 4 / gshard): E * <f_e * p_e>
    me = jnp.mean(probs, axis=0)                               # mean prob
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=probs.dtype)
    ce = jnp.mean(top1, axis=0)                                # token frac
    aux_loss = E * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def sigmoid_topk(router_logits, k: int, normalize: bool = True, bias=None,
                 scale: float = 1.0):
    """Sigmoid selection over ALL published experts: router_logits
    [T, E] -> (choice [T, k] int32, weight [T, k] float32). The scores
    are sigmoids, the k largest are chosen, and with `normalize` the
    chosen scores are divided by their sum. Float32 throughout: a
    near-tie for the k-th place must fall the way the reference's does.
    `bias` [E] (a `noaux_tc` router's `e_score_correction_bias`) is added
    to the scores for the CHOICE alone: the weights are the chosen
    experts' own scores. `scale` (`routed_scaling_factor`) multiplies the
    weights last."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    if bias is None:
        weight, choice = jax.lax.top_k(scores, k)
    else:
        _, choice = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        weight = jnp.take_along_axis(scores, choice, axis=-1)
    if normalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if scale != 1.0:
        weight = weight * scale
    return choice.astype(jnp.int32), weight


# rows up to which every held expert computes every row (`dense`): the
# step is then bound by reading the experts' weights once, which the
# dense form does and nothing else; the sorted form's sort, gathers and
# grouped matmuls cost more than they save (PERF.md section 6).
# A row is 2 FLOPs a bf16 weight, so the dense form stays bound by the
# weights up to ~240 rows on a v5e (197 TFLOP/s over 819 GB/s); at 128
# rows the sorted form took ~12.5 of a 31.7-ms tick on a v5e, and its
# time follows how the seed's routing groups the rows (PERF.md section 6)
DENSE_FORM_ROWS = 128


def swiglu(g, u, limit: Optional[float] = None):
    """The gated-SiLU product silu(g) * u of a gate and an up projection;
    with `limit` (a config's `swiglu_limit`) clamped first:
    silu(min(g, limit)) * clip(u, -limit, limit)."""
    if limit is None:
        return jax.nn.silu(g) * u
    return jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit)


def dropless_experts(x, choice, weight, gate_w, up_w, down_w, *,
                     first: int = 0, live=None, layer=None,
                     limit: Optional[float] = None):
    """The held experts' part of a gated-SiLU expert layer, no drops.

    x [T, D]; choice / weight [T, k] from `sigmoid_topk` (indices over
    the published experts); gate_w / up_w [Eh, D, F], down_w [Eh, F, D]:
    the Eh experts `first .. first + Eh - 1` that live here. Every
    (token, choice) pair that names a held expert is computed —
    `sum_k weight_k * E_k(x)` over the held choices — and the others
    add nothing. `live` [T] bool marks the rows that are real tokens
    (bucket padding and idle slots are not): they are computed like any
    other, only the load leaves them out. With `layer` (an index) the
    weights are a stack [L, Eh, ...] and layer `layer`'s experts are
    meant: the grouped matmuls then run over the whole stack with every
    other layer's groups empty, so that no layer's 400 MB is sliced out
    into a buffer of its own first. `limit` clamps the gated product
    (`swiglu`).

    -> (y [T, D] float32, load [Eh] int32: live pairs on each held
    expert).

    Two forms, chosen by the static row count. Up to DENSE_FORM_ROWS
    every held expert computes every row and the weights select (0 for
    an expert a row did not choose). Past it the pairs are sorted by
    held expert (absent experts' pairs last, in a group no matmul
    reads), the rows gathered once, three grouped matmuls
    (`jax.lax.ragged_dot`) run over the sorted rows, and the results
    are gathered back and summed per token; a row budget of T x k holds
    every case."""
    T, k = choice.shape
    held = gate_w.shape[-3]
    local = choice.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    if live is None:
        live = jnp.ones((T,), bool)
    load = jnp.bincount(jnp.where(jnp.repeat(live, k), local, held),
                        length=held + 1)[:held].astype(jnp.int32)
    if T <= DENSE_FORM_ROWS:
        if layer is not None:
            gate_w, up_w, down_w = gate_w[layer], up_w[layer], down_w[layer]
        h = swiglu(jnp.einsum("td,edf->etf", x, gate_w),
                   jnp.einsum("td,edf->etf", x, up_w), limit)
        out = jnp.einsum("etf,efd->etd", h, down_w,
                         preferred_element_type=jnp.float32)
        mix = jnp.sum(jnp.where(
            local.reshape(T, k, 1) == jnp.arange(held), weight[..., None],
            0.0), axis=1)                                   # [T, Eh]
        return jnp.einsum("etd,te->td", out, mix,
                          precision=jax.lax.Precision.HIGHEST), load
    order = jnp.argsort(local, stable=True)                 # [T*k]
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    if layer is not None:
        stack = gate_w.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((stack * held,), jnp.int32), sizes, (layer * held,))
        gate_w, up_w, down_w = (w.reshape((stack * held,) + w.shape[2:])
                                for w in (gate_w, up_w, down_w))
    rows = jnp.take(x, order // k, axis=0)                  # [T*k, D]
    h = swiglu(jax.lax.ragged_dot(rows, gate_w, sizes),
               jax.lax.ragged_dot(rows, up_w, sizes), limit)
    out = jax.lax.ragged_dot(h, down_w, sizes,
                             preferred_element_type=jnp.float32)
    back = jnp.argsort(order)                               # pair -> row
    # rows past the held groups are whatever the grouped matmul left
    # there: select, do not multiply, so that nothing of them survives
    pair = jnp.where((local < held)[:, None],
                     jnp.take(out, back, axis=0)
                     * weight.reshape(-1)[:, None], 0.0)
    return pair.reshape(T, k, -1).sum(axis=1), load


@dataclasses.dataclass
class GateSpec:
    """Gate zoo entry (reference moe/gate/*.py)."""
    name: str
    top_k: int
    use_capacity: bool


GATES = {
    "naive": GateSpec("naive", 1, False),    # dense masked, no drops
    "switch": GateSpec("switch", 1, True),   # top-1 + capacity
    "gshard": GateSpec("gshard", 2, True),   # top-2 + capacity
}


def moe_ffn(x, gate_w, up_w, up_b, down_w, down_b, *,
            gate: str = "switch", capacity_factor: float = 1.25,
            ep_axis: str = "ep"):
    """Expert-parallel MoE FFN on [B, S, D] activations.

    gate_w [D, E]; up_w [E, D, F]; up_b [E, F]; down_w [E, F, D];
    down_b [E, D]. Expert (E) dims sharded on `ep_axis` make GSPMD lower
    the dispatch einsums to all-to-all over ICI.
    Returns (y [B, S, D], aux_loss scalar).
    """
    B, S, D = x.shape
    E = gate_w.shape[-1]
    spec = GATES[gate]
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)

    if not spec.use_capacity:
        # dense masked form (naive gate): every expert sees every token
        top1 = jnp.argmax(probs, -1)
        onehot = jax.nn.one_hot(top1, E, dtype=x.dtype)
        gate_val = jnp.take_along_axis(
            probs, top1[:, None], -1)[:, 0].astype(x.dtype)
        xe = jnp.einsum("td,te->etd", xt, onehot)
        h = jax.nn.gelu(jnp.einsum("etd,edf->etf", xe,
                                   up_w.astype(x.dtype))
                        + up_b[:, None, :].astype(x.dtype))
        ye = jnp.einsum("etf,efd->etd", h, down_w.astype(x.dtype)) \
            + down_b[:, None, :].astype(x.dtype)
        y = jnp.einsum("etd,te->td", ye, onehot) * gate_val[:, None]
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top1, E, dtype=probs.dtype), axis=0)
        aux = E * jnp.sum(me * ce)
        return y.reshape(B, S, D), aux.astype(jnp.float32)

    C = compute_capacity(T, E, capacity_factor)
    dispatch, combine, aux = topk_gating(probs, spec.top_k, C)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # token -> expert transport: [T,D] x [T,E,C] -> [E,C,D] (the GSPMD
    # all-to-all when E is ep-sharded and T is dp-sharded)
    xe = jnp.einsum("td,tec->ecd", xt, dispatch)
    xe = mesh_constraint(xe, P(ep_axis, None, None))
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, up_w.astype(x.dtype))
                    + up_b[:, None, :].astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, down_w.astype(x.dtype)) \
        + down_b[:, None, :].astype(x.dtype)
    ye = mesh_constraint(ye, P(ep_axis, None, None))
    y = jnp.einsum("ecd,tec->td", ye, combine)
    return y.reshape(B, S, D), aux.astype(jnp.float32)


class MoELayer:
    """nn-level MoE layer (reference MoELayer, moe_layer.py:261).

    Single-controller: holds the gate + stacked expert weights; experts'
    leading axis is sharded on the 'ep' mesh axis when a mesh is active.
    forward(x [B,S,D]) -> [B,S,D]; the last aux (load-balancing) loss is
    available as .aux_loss — add `layer.aux_loss * coeff` to the train
    loss like the reference's gate loss.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str = "switch", capacity_factor: float = 1.25,
                 seed: int = 0, dtype=jnp.float32):
        from ..nn.parameter import Parameter
        if gate not in GATES:
            raise ValueError(f"unknown gate {gate!r}; options: "
                             f"{sorted(GATES)}")
        self.gate = gate
        self.capacity_factor = float(capacity_factor)
        self.num_experts = num_experts
        k = jax.random.split(jax.random.PRNGKey(seed), 4)
        E, D, F = num_experts, d_model, d_hidden
        std = 0.02

        def norm(key, shape, scale=std):
            return (jax.random.normal(key, shape, jnp.float32) *
                    scale).astype(dtype)

        from .mesh import shard_value
        specs = {
            "gate_w": P(None, None),
            "up_w": P("ep", None, None),
            "up_b": P("ep", None),
            "down_w": P("ep", None, None),
            "down_b": P("ep", None),
        }
        raw = {
            "gate_w": norm(k[0], (D, E)),
            "up_w": norm(k[1], (E, D, F)),
            "up_b": jnp.zeros((E, F), dtype),
            "down_w": norm(k[2], (E, F, D)),
            "down_b": jnp.zeros((E, D), dtype),
        }
        mesh = get_mesh()
        if mesh is not None and "ep" in mesh.axis_names:
            raw = {n: shard_value(v, specs[n], mesh)
                   for n, v in raw.items()}
        self._params = {n: Parameter(v, name=f"moe.{n}")
                        for n, v in raw.items()}
        self.aux_loss = None
        self.training = True

    def parameters(self):
        return list(self._params.values())

    def named_parameters(self, *a, **k):
        return list(self._params.items())

    def forward(self, x):
        from ..framework.dispatch import apply
        names = list(self._params)

        def _fwd(xv, *pvals, _gate=None, _cap=None):
            p = dict(zip(names, pvals))
            y, aux = moe_ffn(xv, p["gate_w"], p["up_w"], p["up_b"],
                             p["down_w"], p["down_b"], gate=_gate,
                             capacity_factor=_cap)
            return y, aux

        y, aux = apply("moe_layer", _fwd, x,
                       *[self._params[n] for n in names],
                       _gate=self.gate, _cap=self.capacity_factor)
        self.aux_loss = aux
        return y

    __call__ = forward
