"""Eager op dispatch with a compiled-computation cache.

Reference analog: the PHI kernel registry/factory
(/root/reference/paddle/phi/core/kernel_registry.h:406, kernel_factory.h:314)
plus the generated ad_func layer (eager_gen.py:210).

TPU-native design: an "op" is a pure jax-traceable function. Eager execution
jit-compiles each (op, static-args) closure once and reuses the XLA executable
(jax.jit's aval cache handles shapes/dtypes) — the registry maps to compiled
artifacts instead of hand-written per-backend kernels. When inputs are already
jax Tracers (i.e. we are inside a `paddle_tpu.jit.to_static` trace or a jax
transform), the op body is inlined into the outer trace instead.

Every apply() also performs tape recording (see framework/autograd.py), so
gradients exist in both eager and traced modes from the same code path.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from .autograd import TapeNode, is_grad_enabled
from .tensor import Tensor

_OP_REGISTRY: Dict[str, Callable] = {}
_JIT_CACHE: Dict[Tuple, Callable] = {}
_amp_mod = None
_static_graph_mode = None   # cached static.program.in_static_graph_mode
_record_apply = None
_MONITOR = None             # cached counter handles (hot path: one call +
#                             one lock-add per dispatch, no registry lookup)


class _DispatchMonitor:
    __slots__ = ("cache_hit", "cache_miss", "nan_inf_trip")

    def __init__(self):
        from ..profiler import monitor as _m
        self.cache_hit = _m.counter("dispatch_cache_hit")
        self.cache_miss = _m.counter("dispatch_cache_miss")
        self.nan_inf_trip = _m.counter("dispatch_nan_inf_trip")


def _mon() -> "_DispatchMonitor":
    global _MONITOR
    if _MONITOR is None:
        _MONITOR = _DispatchMonitor()
    return _MONITOR


def _check_nan_inf(name, out_vals):
    """FLAGS_check_nan_inf numerical sanitizer (reference:
    paddle/fluid/eager/nan_inf_utils.cc). The per-output finiteness
    flags are stacked on device and pulled in ONE batched transfer —
    the naive per-output `bool(...)` is one device sync per float
    output; the error names the producing op and every offending output
    index."""
    outs = out_vals if isinstance(out_vals, (tuple, list)) else (out_vals,)
    idx, flags = [], []
    for i, v in enumerate(outs):
        if np.issubdtype(np.dtype(v.dtype), np.floating):
            idx.append(i)
            flags.append(jnp.isfinite(v).all())
    if not flags:
        return
    finite = np.asarray(jax.device_get(jnp.stack(flags)))
    if not finite.all():
        bad = [o for o, f in zip(idx, finite) if not f]
        _mon().nan_inf_trip.add()
        raise FloatingPointError(
            f"nan/inf detected in output(s) {bad} of op '{name}'")

# Toggle: disable per-op jit (debugging / op-by-op numpy-style execution).
_eager_jit = True


def set_eager_jit(flag: bool):
    global _eager_jit
    _eager_jit = bool(flag)


def register_op(name: str, fn: Callable):
    _OP_REGISTRY[name] = fn
    return fn


def get_op(name: str) -> Callable:
    return _OP_REGISTRY[name]


def op_names():
    return sorted(_OP_REGISTRY)


def _freeze(x):
    """Make a static arg hashable for the cache key."""
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, np.dtype):
        return ("npdtype", x.name)
    if isinstance(x, np.ndarray):
        return ("nparr", x.shape, x.dtype.name, x.tobytes())
    if isinstance(x, (int, float, complex)):
        # 1 == 1.0 == True and they hash alike: without the type, `x + 1`
        # on an int tensor would reuse the closure that baked in `1.0`
        return (type(x), x)
    return x


def _thaw_static(x):
    if isinstance(x, list):
        return tuple(_thaw_static(v) for v in x)
    return x


class _Lit:
    """Marks a positional literal baked into the compiled closure."""
    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v


def apply(name: str, fn: Callable, *args, _nondiff_outputs=(), **static):
    """Run op `fn(*args, **static)`; record a tape node if grads are needed.

    args entries may be Tensor (traced input), jax array / np array (traced),
    or python scalars / None / tuples (baked literals). `static` kwargs are
    always baked. `_nondiff_outputs`: indices of outputs excluded from vjp
    (e.g. argmax indices).

    Static-graph mode (paddle.enable_static + Program recording): the op is
    appended to the default Program instead of executing; shapes come from
    jax.eval_shape. Same fn, two consumers — the reference's dygraph/static
    duality with one kernel corpus.
    """
    static = {k: _thaw_static(v) for k, v in static.items()}

    # import deferred once to dodge the framework<->static cycle, then
    # cached: this is the hottest path in eager mode
    global _static_graph_mode, _record_apply
    if _static_graph_mode is None:
        from ..static.program import in_static_graph_mode, record_apply
        _static_graph_mode = in_static_graph_mode
        _record_apply = record_apply
    if _static_graph_mode():
        return _record_apply(name, fn, args, static,
                             nondiff_outputs=_nondiff_outputs)

    input_tensors = []   # Tensor objects, in positional order of array slots
    arg_plan = []        # per arg: _Lit or slot index
    vals = []
    for a in args:
        if isinstance(a, Tensor):
            arg_plan.append(len(vals))
            vals.append(a._value)
            input_tensors.append(a)
        elif isinstance(a, (jax.Array, jax.core.Tracer)):
            arg_plan.append(len(vals))
            vals.append(a)
            input_tensors.append(Tensor(a, stop_gradient=True))
        elif isinstance(a, np.ndarray):
            v = jnp.asarray(a)
            arg_plan.append(len(vals))
            vals.append(v)
            input_tensors.append(Tensor(v, stop_gradient=True))
        else:
            arg_plan.append(_Lit(a))

    plan_key = tuple(("L", _freeze(p.v)) if isinstance(p, _Lit) else ("S", p)
                     for p in arg_plan)
    # Key on (op name, fn qualname) rather than fn identity: ops are often
    # (re)defined in local scopes, and identity-keying would recompile every
    # call. Discipline: one op name ↔ one behavior.
    cache_key = (name, getattr(fn, "__module__", None),
                 getattr(fn, "__qualname__", repr(fn)), plan_key,
                 tuple(sorted((k, _freeze(v)) for k, v in static.items())))

    closure = _JIT_CACHE.get(cache_key)
    if closure is None:
        _mon().cache_miss.add()

        def raw(*arrs, _plan=tuple(arg_plan), _static=static, _fn=fn):
            full = [p.v if isinstance(p, _Lit) else arrs[p] for p in _plan]
            return _fn(*full, **_static)
        raw._raw = raw
        _JIT_CACHE[cache_key] = raw
        closure = raw
    else:
        _mon().cache_hit.add()

    # AMP autocast (O1/O2 allow/deny lists — reference eager_amp_auto_cast.h)
    global _amp_mod
    if _amp_mod is None:
        from .. import amp as _amp
        _amp_mod = _amp
    if _amp_mod.amp_state().enabled:
        vals = _amp_mod.maybe_autocast_inputs(name, vals)

    tracing = any(isinstance(v, jax.core.Tracer) for v in vals)
    try:
        if tracing or not _eager_jit:
            out_vals = closure(*vals)
        else:
            jitted = getattr(closure, "_jitted", None)
            if jitted is None:
                jitted = jax.jit(closure)
                closure._jitted = jitted
            out_vals = jitted(*vals)
            from .flags import flag as _flag
            if _flag("check_nan_inf", False):
                _check_nan_inf(name, out_vals)
    except FloatingPointError:
        raise
    except Exception as e:
        # Enforce-style op context frame (reference
        # paddle/phi/core/enforce.h "[operator < x > error]"): name the
        # failing op and its input signature on the exception itself
        shapes = ", ".join(f"{tuple(v.shape)}:{np.dtype(v.dtype).name}"
                           for v in vals)
        if hasattr(e, "add_note"):
            e.add_note(f"[operator < {name} > error] "
                       f"input signature: ({shapes})")
        raise

    multi = isinstance(out_vals, (tuple, list))
    outs = tuple(out_vals) if multi else (out_vals,)

    # capture recording for jit.to_static's discovery pre-pass
    from ..jit.trace_context import active_capture
    cap = active_capture()

    grad_needed = (is_grad_enabled() and any(
        (not t.stop_gradient) and dtypes.is_differentiable(t.dtype)
        for t in input_tensors))

    out_tensors = tuple(Tensor(v, stop_gradient=not grad_needed) for v in outs)

    if grad_needed:
        diff_in = [(not t.stop_gradient) and dtypes.is_differentiable(t.dtype)
                   for t in input_tensors]
        diff_out = [dtypes.is_differentiable(np.dtype(v.dtype))
                    and i not in _nondiff_outputs
                    for i, v in enumerate(outs)]
        for i, m in enumerate(diff_out):
            if not m:
                out_tensors[i].stop_gradient = True
        if any(diff_out):
            node = TapeNode(
                name=name,
                closure=getattr(closure, "_raw", closure),
                saved_vals=tuple(vals),
                inputs=input_tensors,
                diff_in_mask=diff_in,
                diff_out_mask=diff_out,
                out_avals=[(v.shape, np.dtype(v.dtype)) for v in outs],
            )
            for i, t in enumerate(out_tensors):
                if diff_out[i]:
                    t._node = node
                    t._out_idx = i

    if cap is not None:
        cap.on_apply(input_tensors, out_tensors)

    if not multi:
        return out_tensors[0]
    return list(out_tensors)


def defop(name: str, n_outputs: int = 1, nondiff_outputs=()):
    """Decorator: register `fn` and return a Tensor-level wrapper.

    The wrapped function receives the same positional args; Tensor args flow
    through the tape, everything else is baked static.
    """
    def deco(fn):
        register_op(name, fn)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return apply(name, fn, *args, _nondiff_outputs=nondiff_outputs,
                         **kwargs)
        wrapper._op_name = name
        wrapper._raw_fn = fn
        return wrapper
    return deco


def raw_value(x):
    """Unwrap a Tensor (or pass through arrays/scalars)."""
    return x._value if isinstance(x, Tensor) else x


def as_tensor(x) -> Tensor:
    return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
