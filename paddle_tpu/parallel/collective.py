"""Functional collectives.

Reference analog: python/paddle/distributed/communication/ (all_reduce,
all_gather, ... over ProcessGroupNCCL, process_group.h:53-430).

TPU-native, two modes:
1. *In-trace* (inside shard_map manual regions): thin wrappers over
   lax.psum/all_gather/ppermute/all_to_all — XLA lowers to ICI collectives.
2. *Eager on global arrays*: a "collective" reorganizes a global jax.Array
   across a mesh axis; implemented as a jitted shard_map computation over
   the group's axis. With no mesh (single chip) they are identities on the
   global value, matching the reference's world_size==1 fast path.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.tensor import Tensor
from .mesh import get_mesh
from .topology import CommGroup


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _axis_of(group) -> Optional[str]:
    if group is None:
        mesh = get_mesh()
        if mesh is None:
            return None
        # default group = all axes
        return tuple(mesh.axis_names)
    if isinstance(group, CommGroup):
        return group.axis_name
    return group


def _psum_like(x, axis, op):
    if op == ReduceOp.SUM:
        return jax.lax.psum(x, axis)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axis)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(x, axis)
    raise ValueError(f"unsupported reduce op {op}")


# ---------------------------------------------------------------- in-trace
def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name):
    return jax.lax.pmax(x, axis_name)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


# ------------------------------------------------------ eager global-array
#
# Convention (the TPU-native reading of the reference's per-rank API,
# process_group.h:53-430): the reference's "rank i's local tensor" maps to
# shard i of a global jax.Array along the group's mesh axis. Each eager
# collective is a shard_map computation whose per-shard behavior equals the
# reference's per-rank behavior. A tensor REPLICATED over the group axis is
# the world_size==1 degenerate case (every rank already holds the global
# value) and takes the documented fast path.

def _group_info(group):
    """(mesh, axes-tuple, group_size) or (None, None, 1) when groupless."""
    mesh = get_mesh()
    axis = _axis_of(group)
    if mesh is None or axis is None:
        return None, None, 1
    axes = axis if isinstance(axis, tuple) else (axis,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None, None, 1
    n = int(np.prod([mesh.shape[a] for a in axes]))
    return mesh, axes, n


def _is_sharded_on(value, axes) -> bool:
    """True when the value's DIM 0 is sharded over (any of) `axes` — the
    collectives' per-rank-local := dim-0-shard convention. A tensor sharded
    on the group axis along a non-leading dim is not a per-rank layout."""
    sharding = getattr(value, "sharding", None)
    if sharding is None:
        return False
    try:
        spec = sharding.spec
    except Exception:
        return False
    if not len(spec):
        return False
    lead = spec[0]
    lead = lead if isinstance(lead, (tuple, list)) else (lead,)
    return any(a in lead for a in axes if a is not None)


def _shmap(fn, mesh, axes, in_specs, out_specs):
    # check_vma=True: partial-manual shard_map with check_vma=False is
    # broken in jax 0.9 (see parallel/pipeline.py)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(axes),
                         check_vma=True)


@functools.lru_cache(maxsize=256)
def _cached_allreduce(mesh, axes, op):
    fn = _shmap(lambda s: _psum_like(s, axes, op), mesh, axes,
                in_specs=P(axes), out_specs=P())
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _cached_reduce_scatter(mesh, axes, op, n):
    def _rs(*locals_):
        stacked = jnp.concatenate(locals_, axis=0)       # [n*k, ...]
        if op in (ReduceOp.SUM, ReduceOp.AVG):
            out = jax.lax.psum_scatter(stacked, axes[0],
                                       scatter_dimension=0, tiled=True)
            return out / n if op == ReduceOp.AVG else out
        # MAX/MIN/PROD have no psum_scatter analog: gather, reduce, slice
        g = jax.lax.all_gather(stacked, axes[0])         # [n, n*k, ...]
        red = {ReduceOp.MAX: jnp.max, ReduceOp.MIN: jnp.min,
               ReduceOp.PROD: jnp.prod}[op](g, axis=0)
        k = stacked.shape[0] // n
        i = jax.lax.axis_index(axes[0])
        return jax.lax.dynamic_slice_in_dim(red, i * k, k, 0)

    fn = _shmap(_rs, mesh, axes,
                in_specs=tuple(P(axes) for _ in range(n)),
                out_specs=P(axes))
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _cached_all_to_all(mesh, axes, n):
    def _a2a(*locals_):
        stacked = jnp.stack(locals_, axis=0)              # [n, k, ...]
        ex = jax.lax.all_to_all(stacked, axes[0], split_axis=0,
                                concat_axis=0)
        return tuple(ex[e] for e in range(n))

    fn = _shmap(_a2a, mesh, axes,
                in_specs=tuple(P(axes) for _ in range(n)),
                out_specs=tuple(P(axes) for _ in range(n)))
    return jax.jit(fn)


def _wrap_like(value, like: Tensor) -> Tensor:
    return Tensor(value, stop_gradient=like.stop_gradient)


def _guard_inplace(tensor, op_name: str):
    """Eager collectives mutate their argument in place (the reference's
    semantics). A tensor with recorded tape history would silently diverge
    from its backward snapshot — the reference's NCCL ops have the same
    hazard but no tape; here we can catch it."""
    if getattr(tensor, "_node", None) is not None and \
            not tensor.stop_gradient:
        raise RuntimeError(
            f"paddle_tpu.distributed.{op_name} mutates its tensor in "
            f"place, but this tensor has recorded autograd history — the "
            f"mutation would diverge from the tape's saved value. Use "
            f"in-graph collectives (mesh sharding / shard_map psum) for "
            f"differentiable code, or call {op_name} on a detached "
            f"tensor (.detach()).")


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce across the group: shard i of the result-forming view is
    op(shards). Sharded [n*k, ...] input -> replicated [k, ...] output
    value (every rank holds the reduced local). Replicated input is the
    world_size==1 fast path (identity). Inside shard_map use psum."""
    mesh, axes, n = _group_info(group)
    if mesh is None or n == 1:
        return tensor
    val = tensor._value
    if not _is_sharded_on(val, axes):
        return tensor
    _guard_inplace(tensor, "all_reduce")     # guards only real mutation
    tensor._value = _cached_allreduce(mesh, axes, op)(val)
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """tensor sharded on the group axis -> list of the n shard values (the
    reference's per-rank receive list). Replicated tensor -> [tensor] * n
    (every rank contributed the same value)."""
    mesh, axes, n = _group_info(group)
    if mesh is None or n == 1:
        tensor_list.append(tensor)
        return tensor_list
    val = tensor._value
    if not _is_sharded_on(val, axes) or val.shape[0] % n != 0:
        tensor_list.extend([tensor] * n)
        return tensor_list
    k = val.shape[0] // n
    # the global array IS the gathered result; expose per-rank slices as
    # replicated values
    gathered = jax.device_put(
        val, jax.sharding.NamedSharding(mesh, P()))
    tensor_list.extend(
        _wrap_like(gathered[i * k:(i + 1) * k], tensor) for i in range(n))
    return tensor_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Every rank's local becomes rank-src's local: sharded input [n*k,...]
    -> every shard replaced by shard src. Replicated input: identity (all
    ranks already hold the same global value — reference world_size==1)."""
    mesh, axes, n = _group_info(group)
    if mesh is None or n == 1:
        return tensor
    val = tensor._value
    if not _is_sharded_on(val, axes) or val.shape[0] % n != 0:
        return tensor
    k = val.shape[0] // n
    _guard_inplace(tensor, "broadcast")      # guards only real mutation
    src_shard = jnp.broadcast_to(val[src * k:(src + 1) * k],
                                 (n,) + (k,) + val.shape[1:])
    tensor._value = src_shard.reshape(val.shape)
    tensor._value = jax.device_put(
        tensor._value, jax.sharding.NamedSharding(
            mesh, P(axes, *([None] * (val.ndim - 1)))))
    return tensor


def barrier(group=None):
    jax.block_until_ready(jnp.zeros(()))


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Rank i receives tensor_list[i] (as held by rank src): the result is
    the concat of tensor_list sharded on the group axis — shard i ==
    tensor_list[i]."""
    _guard_inplace(tensor, 'scatter')
    mesh, axes, n = _group_info(group)
    if not tensor_list:
        return tensor
    if mesh is None or n == 1:
        tensor._value = tensor_list[0]._value
        return tensor
    if len(tensor_list) != n:
        raise ValueError(
            f"scatter needs len(tensor_list)=={n} (group size), got "
            f"{len(tensor_list)}")
    cat = jnp.concatenate([t._value for t in tensor_list], axis=0)
    tensor._value = jax.device_put(
        cat, jax.sharding.NamedSharding(
            mesh, P(axes, *([None] * (cat.ndim - 1)))))
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Only rank dst's value is defined by the reference; we give every
    rank the reduced value (a superset of the contract)."""
    return all_reduce(tensor, op, group, sync_op)


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """out shard i = op over ranks j of tensor_list[j-th shard][i].
    Each tensor_list[e] sharded on the group axis contributes its shards;
    result is sharded on the group axis with shard i = op_j list_j[i].
    Replicated elements degrade to elementwise op of the list (the
    world_size==1 path)."""
    _guard_inplace(tensor, 'reduce_scatter')
    def _np_reduce(vals):
        red = {ReduceOp.SUM: sum, ReduceOp.AVG: sum,
               ReduceOp.MAX: lambda vs: functools.reduce(jnp.maximum, vs),
               ReduceOp.MIN: lambda vs: functools.reduce(jnp.minimum, vs),
               ReduceOp.PROD: lambda vs: functools.reduce(
                   jnp.multiply, vs)}[op](vals)
        return red / len(vals) if op == ReduceOp.AVG else red

    mesh, axes, n = _group_info(group)
    if mesh is None or n == 1:
        tensor._value = _np_reduce([t._value for t in tensor_list])
        return tensor
    if len(tensor_list) != n:
        raise ValueError(
            f"reduce_scatter needs len(tensor_list)=={n}, got "
            f"{len(tensor_list)}")
    vals = [t._value for t in tensor_list]
    if not all(_is_sharded_on(v, axes) for v in vals):
        tensor._value = _np_reduce(vals)
        return tensor
    if len(axes) != 1:
        raise ValueError("reduce_scatter supports single-axis groups")
    tensor._value = _cached_reduce_scatter(mesh, axes, op, n)(*vals)
    return tensor


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """out element e, shard i = in element i, shard e (the reference's
    rank-i-receives-in_list_j[i] exchange). Replicated elements degrade to
    the list transpose (identity on a world of one)."""
    mesh, axes, n = _group_info(group)
    if mesh is None or n == 1:
        out_tensor_list.extend(in_tensor_list)
        return out_tensor_list
    if len(in_tensor_list) != n:
        raise ValueError(
            f"all_to_all needs len(in_tensor_list)=={n}, got "
            f"{len(in_tensor_list)}")
    if len(axes) != 1:
        raise ValueError("all_to_all supports single-axis groups")
    vals = [t._value for t in in_tensor_list]
    if not all(_is_sharded_on(v, axes) for v in vals):
        out_tensor_list.extend(in_tensor_list)
        return out_tensor_list

    outs = _cached_all_to_all(mesh, axes, n)(*vals)
    out_tensor_list.extend(
        _wrap_like(o, in_tensor_list[0]) for o in outs)
    return out_tensor_list


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv: use the pipeline schedule "
        "(paddle_tpu.parallel.pipeline) — on TPU p2p is a ppermute inside "
        "the compiled program, not a host-driven NCCL call")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv: use the pipeline schedule "
        "(paddle_tpu.parallel.pipeline)")


def new_group(ranks=None, backend=None, timeout=None):
    mesh = get_mesh()
    n = len(ranks) if ranks else (jax.device_count())
    return CommGroup(None, mesh, rank=0, nranks=n)


def get_group(gid=0):
    mesh = get_mesh()
    return CommGroup(None, mesh, rank=0,
                     nranks=jax.device_count())


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(tensor._value)
    return tensor
