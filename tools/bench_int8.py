"""int8 vs bf16 matmul microbench row: the real-int8 path's on-chip
rate next to the bf16 MXU rate.

Times three variants of the serving matmul shape [B*S, D] @ [D, 4D]
chained through a lax.scan (one dispatch for many hops):
  - bf16 @ bf16 -> f32 accumulate (the fp serving path)
  - int8 @ int8 -> i32 accumulate (raw MXU int8 rate)
  - the full Int8Linear op (quantize epilogue + int8 dot + dequant)
Emits one JSON line per variant.
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

M, K, N = 8192, 1024, 4096
REPS = 64      # hops per dispatch: ~4.4 TFLOP, far above dispatch cost


def log(m):
    print(f"[int8bench] {m}", file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)


from bench_util import chained_ms, force as _force  # noqa: E402


def main():
    devs = jax.devices()
    log(f"backend {devs[0].platform} ({devs[0].device_kind})")

    # all three micro rows run through chained_ms (a single
    # [8192,1024]@[1024,4096] dispatch is too short to time on its
    # own). The slice back to [:, :K] adds one copy per hop to
    # BOTH paths, so the bf16-vs-int8 ratio is unaffected.
    fl_hop = 2.0 * M * K * N

    # bf16 path (1/K-weight row-mean keeps magnitudes neutral)
    b16 = jnp.full((K, N), 1.0 / K, jnp.bfloat16)
    ms = chained_ms(
        lambda h: jax.lax.dot_general(
            h, b16, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[:, :K].astype(jnp.bfloat16),
        jnp.full((M, K), 0.5, jnp.bfloat16), length=REPS, iters=3)
    emit({"metric": "matmul_bf16", "ms": round(ms, 3),
          "tflops": round(fl_hop / (ms * 1e-3) / 1e12, 1),
          "backend": devs[0].platform})

    # raw int8 path
    b8 = jnp.ones((K, N), jnp.int8)
    ms = chained_ms(
        lambda h: jnp.clip(jax.lax.dot_general(
            h, b8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)[:, :K],
            -127, 127).astype(jnp.int8),
        jnp.ones((M, K), jnp.int8), length=REPS, iters=3)
    emit({"metric": "matmul_int8", "ms": round(ms, 3),
          "tops": round(fl_hop / (ms * 1e-3) / 1e12, 1),
          "backend": devs[0].platform})

    # full Int8Linear op (quant + int8 dot + dequant epilogue);
    # 1/K output scale keeps the f32 carry at 0.5 across hops
    from paddle_tpu.quantization.int8 import _int8_linear
    w_q = jnp.ones((K, N), jnp.int8)
    w_scale = jnp.full((N,), 1.0 / K, jnp.float32)
    bias = jnp.zeros((N,), jnp.float32)
    raw = _int8_linear._raw_fn
    try:
        ms = chained_ms(
            lambda h: raw(h, w_q, bias, jnp.float32(1.0),
                          w_scale)[:, :K].astype(jnp.float32),
            jnp.full((M, K), 0.5, jnp.float32), length=REPS, iters=3)
        emit({"metric": "int8_linear_op", "ms": round(ms, 3),
              "tops": round(fl_hop / (ms * 1e-3) / 1e12, 1),
              "backend": devs[0].platform})
    except Exception as e:
        emit({"metric": "int8_linear_op", "error": repr(e)[:160]})

    bench_decode(devs)


def bench_decode(devs):
    """KV-cache single-token decode, fp32 weights vs weight-only int8
    (incubate.FusedMultiTransformer.weight_only_quant) — decode is
    weight-HBM-bound, so int8 weights should approach a 4x step-time cut
    vs f32 on chip. The decode steps are CHAINED inside one jit via
    lax.scan (an eager per-token loop would measure host dispatch, not
    the chip)."""
    import functools
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.incubate.fused_multi_transformer import _stack_forward
    paddle.seed(0)
    B, D, L, MAXLEN, STEPS = 8, 1024, 24, 1024, 16
    model = FusedMultiTransformer(embed_dim=D, num_heads=16,
                                  dim_feedforward=4 * D, num_layers=L)
    rng = np.random.RandomState(0)
    prefix = paddle.to_tensor(rng.randn(B, 512, D).astype(np.float32) * .1)
    x0 = jnp.asarray(rng.randn(B, 1, D).astype(np.float32) * .1)

    def decode_ms(m, caches, label):
        pv = [t._value for t in m._scan_inputs()]

        @jax.jit
        def chained(x, kc, vc, *pvv):
            def step(carry, t):
                x, kc, vc = carry
                y, kc, vc = _stack_forward(x, kc, vc, pvv, 512 + t,
                                           m.num_heads, m.head_dim,
                                           m.activation)
                return (y, kc, vc), None
            (y, kc, vc), _ = jax.lax.scan(
                step, (x, kc, vc), jnp.arange(STEPS))
            return y

        kc, vc = caches[0]._value, caches[1]._value
        out = chained(x0, kc, vc, *pv)
        _force(out)                                        # compile
        t0 = time.perf_counter()
        out = chained(x0, kc, vc, *pv)
        _force(out)
        ms = (time.perf_counter() - t0) / STEPS * 1e3
        emit({"metric": label, "ms_per_token": round(ms, 3),
              "chained_steps": STEPS, "backend": devs[0].platform})
        return ms

    try:
        caches = model.gen_cache(batch=B, max_len=MAXLEN)
        _, caches = model(prefix, caches=caches, time_step=0)
        fp_ms = decode_ms(model, caches, "decode_fp32")
        model.weight_only_quant()
        q_ms = decode_ms(model, caches, "decode_weight_only_int8")
        emit({"metric": "decode_speedup_int8_vs_fp32",
              "x": round(fp_ms / q_ms, 2), "backend": devs[0].platform})
    except Exception as e:
        emit({"metric": "decode_bench", "error": repr(e)[:200]})


if __name__ == "__main__":
    main()
