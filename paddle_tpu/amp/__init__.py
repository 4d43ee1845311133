"""Automatic mixed precision.

Reference analog: python/paddle/amp/auto_cast.py:646 (+ C++ eager amp at
/root/reference/paddle/fluid/eager/amp_utils.h) and GradScaler
(python/paddle/amp/grad_scaler.py:41).

TPU-native: the compute dtype is bfloat16 (MXU-native), which needs NO loss
scaling — GradScaler keeps the fp16 dynamic-scaling machinery for API parity
but is an identity at scale=1 under bf16. auto_cast applies the reference's
O1 allow/deny-list semantics inside the dispatch layer, so it works the same
eagerly and under to_static traces.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np
import jax.numpy as jnp

from ..framework import dtype as dtypes
from ..framework.tensor import Tensor
from .grad_scaler import GradScaler, AmpScaler  # noqa: F401

# O1 lists (reference: python/paddle/static/amp/fp16_lists.py white/black)
WHITE_LIST = {
    "matmul", "mm", "linear", "linear_nobias", "conv1d_op", "conv2d_op",
    "conv3d_op", "conv1d_transpose_op", "conv2d_transpose_op",
    "conv3d_transpose_op", "einsum", "mv", "addmm",
    "sdpa_op", "flash_attention_kernel", "memory_efficient_attention_op",
}
BLACK_LIST = {
    "exp", "square", "log", "mean", "sum", "cosine_similarity_op", "softmax",
    "log_softmax", "cross_entropy_hard", "cross_entropy_soft",
    "layer_norm_op", "rms_norm_op", "batch_norm_train", "batch_norm_eval",
    "group_norm_op", "instance_norm_op", "logsumexp", "erf", "erfinv",
    "pow", "mse_loss_op", "l1_loss_op", "bce_loss_op", "bce_logits_op",
    "kl_div_op", "nll_loss_gather",
}


class _AmpState(threading.local):
    def __init__(self):
        self.enabled = False
        self.dtype = dtypes.bfloat16
        self.level = "O1"
        self.custom_white = set()
        self.custom_black = set()


_state = _AmpState()


def amp_state():
    return _state


def _cast_value(v, dt):
    if np.dtype(v.dtype) == dtypes.float32:
        return v.astype(dt)
    return v


def maybe_autocast_inputs(op_name, vals):
    """Called by framework.dispatch.apply before execution."""
    if not _state.enabled:
        return vals
    white = (WHITE_LIST | _state.custom_white) - _state.custom_black
    if _state.level == "O2":
        black = BLACK_LIST | _state.custom_black
        if op_name in black:
            return [v.astype(jnp.float32)
                    if np.dtype(v.dtype) == _state.dtype else v for v in vals]
        return [_cast_value(v, _state.dtype) for v in vals]
    if op_name in white:
        return [_cast_value(v, _state.dtype) for v in vals]
    black = BLACK_LIST | _state.custom_black
    if op_name in black:
        return [v.astype(jnp.float32)
                if np.dtype(v.dtype) == _state.dtype else v for v in vals]
    return vals


class auto_cast:
    """paddle.amp.auto_cast context (reference: amp/auto_cast.py:646)."""

    def __init__(self, enable=True, custom_white_list=None,
                 custom_black_list=None, level="O1", dtype="bfloat16",
                 use_promote=True):
        self.enable = enable
        self.level = level
        self.dtype = dtypes.convert_dtype(dtype)
        self.white = set(custom_white_list or ())
        self.black = set(custom_black_list or ())

    def __enter__(self):
        self._prev = (_state.enabled, _state.dtype, _state.level,
                      _state.custom_white, _state.custom_black)
        _state.enabled = self.enable
        _state.dtype = self.dtype
        _state.level = self.level
        _state.custom_white = self.white
        _state.custom_black = self.black
        return self

    def __exit__(self, *exc):
        (_state.enabled, _state.dtype, _state.level, _state.custom_white,
         _state.custom_black) = self._prev
        return False


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O1", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """O2 decoration: cast model params to the amp dtype (reference:
    amp/auto_cast.py amp_decorate)."""
    dt = dtypes.convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            m.to(dtype=dt)
    if optimizers is None:
        return models
    return models, optimizers


class debugging:
    """paddle.amp.debugging shim (reference: python/paddle/amp/debugging.py).
    check_numerics of a tensor; the global FLAGS_check_nan_inf path lives in
    framework.dispatch."""

    @staticmethod
    def check_numerics(tensor, op_type="", var_name="",
                       debug_mode=None):
        import numpy as _np
        arr = tensor.numpy()
        if not _np.isfinite(arr).all():
            raise FloatingPointError(
                f"nan/inf detected in {op_type}:{var_name}")
        return tensor

    @staticmethod
    def enable_operator_stats_collection():
        pass

    @staticmethod
    def disable_operator_stats_collection():
        pass


def is_float16_supported(device=None):
    """reference amp/__init__.py is_float16_supported — TPUs compute in
    bf16 natively; fp16 storage works but matmul paths prefer bf16."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def is_bfloat16_supported(device=None):
    """reference amp/__init__.py is_bfloat16_supported — always true on
    TPU (the native mixed-precision dtype) and on CPU via XLA."""
    return True
