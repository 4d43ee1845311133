"""Weight-only int8 quantization for the stacked-scan serving params.

Reference analog: the weight-only half of the PTQ driver
(python/paddle/static/quantization/post_training_quantization.py:1,
weight_quantize_type='channel_wise_abs_max') applied at Predictor load —
no calibration pass needed because only WEIGHTS quantize; activations
stay in the compute dtype and the dequant rides inside the matmul
(kernels/quant_matmul.py).

TPU-native shape: the serving engines (inference/serving.py) hold each
family's params as ONE pytree with per-layer weights stacked on a
leading layer axis (models/gpt.py, models/llama.py). Quantization is
therefore a LEAF REWRITE, not a graph pass: every matmul weight in the
family's QUANT_LEAVES table is replaced by an int8 `<name>_q` plus a
per-output-channel fp32 `<name>_scale` (int8.quantize_weight_stacked —
the stacked vectorization of quantize_weight), the fp leaf is dropped
(that drop IS the HBM saving), and the tied LM head gets a transposed
int8 copy (`head_q` [D, V] + `head_scale` [V]) while `wte` stays fp for
the embedding gather — embeddings and norms never quantize. The cached
forwards route through kernels/quant_matmul.leaf_matmul, which detects
the `_q` pair per leaf, so eager/jit/spec-draft/paged/tp paths all pick
the quantized matmul up from the TREE, not from a flag.

Tensor-parallel serving: the rewritten tree extends the family's
SERVING_PARAM_SPECS naturally — `<name>_q` inherits the fp weight's
spec (same shape), and its scales shard with the weight's OUTPUT-
CHANNEL axis (column-parallel weights carry tp on the output dim, so
their scales are tp-sharded; row-parallel weights shard the reduction
dim, so their scales replicate). The head copy flips the vocab-parallel
embedding spec onto its transposed layout.

The sibling rewrite, `round_serving_params`, is always on and needs no
knob: whatever stays floating point is rounded to the compute dtype
once, at build, where the cached forward would round it on every call
(COMPUTE_LEAVES). Shapes and specs do not change.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .int8 import _Q, quantize_weight, quantize_weight_stacked

__all__ = ["QUANT_LEAVES", "HEAD_LEAF", "COMPUTE_LEAVES",
           "quantize_serving_params", "round_serving_params",
           "tree_bytes"]

# family -> the stacked [L, ..., N] matmul leaves that quantize (the
# attention qkv/proj and MLP in/out weights; biases, norms, embeddings
# and the MoE expert stacks stay fp). Leaves absent from a given params
# tree (e.g. the dense-MLP names on a MoE config) are skipped.
QUANT_LEAVES: Dict[str, tuple] = {
    "gpt": ("qkv_w", "attn_out_w", "mlp_up_w", "mlp_down_w"),
    "llama": ("q_w", "k_w", "v_w", "o_w", "gate_w", "up_w", "down_w"),
}

# both flagship decoders tie the LM head to the token embedding; the
# head quantizes as a separate TRANSPOSED int8 copy so the embedding
# gather stays fp (and so the head runs the same [K, N] kernel layout
# as the block matmuls)
HEAD_LEAF = "wte"

# family -> the leaves its cached forward rounds WHOLE to the compute
# dtype (`.astype(x.dtype)`) before the first use: the matmul stacks
# through kernels/quant_matmul.leaf_matmul, their biases, and `wte`, the
# tied head (its embedding rows are gathered first and rounded after,
# which commutes). Norm scales and biases are not here: `_ln` /
# `_rmsnorm` compute on them in float32. Nor is gpt's `wpe`: the forward
# converts only the rows it slices out, and on the TPU the prefill's
# embed fusion adds those rows UNROUNDED (XLA folds the slice's
# f32->bf16->f32 convert pair away), so a table rounded ahead of time
# serves other logits than the table as handed (GPT-1.3B on a v5e: 87%
# of a 256-token prefill's logits move, by up to 0.07). A family with no
# entry (cohere2_moe: bf16 as stored) is left alone. jamba's are the
# matmul stacks of both mixers and the MLP, the convolution and the
# embedding: stored in bf16 they pass through; `a_log`, `d`, `dt_b` and
# the norm scales are float32 operands of float32 arithmetic and are not
# here.
COMPUTE_LEAVES: Dict[str, tuple] = {
    "gpt": QUANT_LEAVES["gpt"] + ("qkv_b", "attn_out_b", "mlp_up_b",
                                  "mlp_down_b", "wte"),
    "llama": QUANT_LEAVES["llama"] + ("wte",),
    "jamba": ("in_w", "conv_w", "conv_b", "x_w", "dt_w", "out_w",
              "q_w", "k_w", "v_w", "o_w", "gate_w", "up_w", "down_w",
              "wte"),
}


def _entry(spec, i: int):
    """spec[i] with PartitionSpec's implicit-None tail made explicit."""
    return spec[i] if spec is not None and i < len(spec) else None


def quantize_serving_params(params: dict, family: str,
                            specs: Optional[dict] = None
                            ) -> Tuple[dict, dict, dict]:
    """Rewrite a serving params tree to weight-only int8.

    Returns (qparams, qspecs, info):
    - qparams: the input tree with every QUANT_LEAVES[family] leaf
      replaced by `<name>_q` (int8, same shape) + `<name>_scale`
      (fp32 [L, N]), plus `head_q` [D, V] int8 + `head_scale` [V] for
      the tied LM head (`wte` itself stays, fp, for the embedding).
    - qspecs: `specs` extended with PartitionSpecs for the new leaves
      (weight spec inherited; scale spec = (layer axis, output axis);
      head spec = the embedding spec transposed) — feeds the serving
      engine's _shard_params under mesh=.
    - info: {"fp_bytes", "quant_bytes", "per_layer", "head",
      "quant_leaf_names"} — the telemetry/bench surface
      (serving.quant_weights_bytes / fp_weights_bytes gauges and the
      per-tick quant_matmuls accounting).
    """
    leaves = QUANT_LEAVES.get(family)
    if leaves is None:
        raise ValueError(
            f"family {family!r} has no weight-only quant leaf table "
            f"(QUANT_LEAVES covers {sorted(QUANT_LEAVES)}); a custom "
            "family must register its stacked matmul leaves there "
            "before serving with quant=")
    fp_bytes = tree_bytes(params)
    out = dict(params)
    qspecs = dict(specs or {})
    done = []
    for name in leaves:
        if name not in params:
            continue
        w_q, scale = quantize_weight_stacked(np.asarray(params[name]))
        del out[name]
        out[name + "_q"] = jnp.asarray(w_q)
        # stored scales are the ready DEQUANT multiplier (w ~ w_q *
        # scale), i.e. abs-max / 127 — quant_matmul applies them raw
        out[name + "_scale"] = jnp.asarray(scale / _Q)
        wspec = qspecs.pop(name, P())
        qspecs[name + "_q"] = wspec
        # scale [L, N]: the stacked layer axis + the weight's OUTPUT-
        # CHANNEL (last) axis — tp-sharded exactly when the weight's
        # output dim is (column-parallel), replicated when the tp split
        # sits on the reduction dim (row-parallel)
        qspecs[name + "_scale"] = P(_entry(wspec, 0),
                                    _entry(wspec, np.ndim(params[name])
                                           - 1))
        done.append(name)
    head = 0
    if HEAD_LEAF in params:
        w = np.asarray(params[HEAD_LEAF], np.float32).T       # [D, V]
        head_q, head_scale = quantize_weight(w, channel_axis=1)
        out["head_q"] = jnp.asarray(head_q)
        out["head_scale"] = jnp.asarray(head_scale / _Q)
        espec = qspecs.get(HEAD_LEAF, P())
        # the vocab-parallel embedding spec, transposed onto [D, V]
        out_axis = _entry(espec, 0)
        qspecs["head_q"] = P(_entry(espec, 1), out_axis)
        qspecs["head_scale"] = P(out_axis)
        head = 1
    quant_bytes = tree_bytes(out)
    info = {"fp_bytes": int(fp_bytes), "quant_bytes": int(quant_bytes),
            "per_layer": len(done), "head": head,
            "quant_leaf_names": tuple(done)}
    return out, qspecs, info


def tree_bytes(params: dict) -> int:
    """Bytes of a params tree, from shapes and dtypes (no transfer)."""
    return sum(int(v.nbytes) for v in params.values())


@functools.partial(jax.jit, static_argnames=("dtype",))
def _round_on_device(leaves: dict, dtype):
    return {name: v.astype(dtype) for name, v in leaves.items()}


def round_serving_params(params: dict, family: str, cfg) -> dict:
    """Round a serving params tree to the compute dtype ONCE, at build:
    every COMPUTE_LEAVES[family] leaf whose dtype is wider than
    `cfg.dtype` becomes `leaf.astype(cfg.dtype)` — the cast the cached
    forward would otherwise make inside every decode tick and every
    prefill (on a float32 GPT-1.3B tree: 5.25 GB read and 2.42 GB
    written a tick before any work). Rounding is element-wise, so it
    commutes with the forward's row gathers and layer slices and every
    program computes on the operands it computed on before; the
    forward's own `astype` calls become no-ops.

    What is done follows from the tree, not from a knob: a leaf already
    at (or under) the compute width, an int8 pair, a norm leaf and a
    family with no table pass through as the same objects, and a tree
    with nothing to round comes back as the SAME dict, so a second
    application is free. Device leaves are rounded in one jitted call
    where they live; host (numpy) leaves are rounded on the host, so a
    tree on its way to `_shard_params` is never staged on one device.
    After `quantize_serving_params`, not before: the int8 pairs are
    made from the leaves as handed."""
    names = COMPUTE_LEAVES.get(family)
    dtype = getattr(cfg, "dtype", None)
    if names is None or dtype is None:
        return params
    dtype = jnp.dtype(dtype)
    wide = {n: params[n] for n in names
            if n in params
            and jnp.issubdtype(params[n].dtype, jnp.floating)
            and jnp.dtype(params[n].dtype).itemsize > dtype.itemsize}
    if not wide:
        return params
    on_device = {n: v for n, v in wide.items() if isinstance(v, jax.Array)}
    out = dict(params)
    out.update({n: np.asarray(v).astype(dtype) for n, v in wide.items()
                if n not in on_device})
    if on_device:
        out.update(_round_on_device(on_device, dtype))
    return out
