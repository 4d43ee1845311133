"""Pallas / flash-attention kernel parity tests.

Reference test strategy analog: OpTest numpy-parity + check_grad
(test/legacy_test/eager_op_test.py) applied to the flash_attn op
(reference: python/paddle/nn/functional/flash_attention.py:125).

The Pallas kernel runs in interpreter mode on CPU; numerics are compared
against the O(S²) dense softmax reference, and gradients against jax.grad of
the dense reference.
"""
import numpy as np
import pytest
import functools
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import (
    _blockwise_attention_lse, _dense_reference, _flash_mha, _flash_bwd)
from paddle_tpu.kernels.pallas_attention import mha_fwd


def _rand_qkv(B=2, S=256, H=4, D=64, Skv=None, seed=0):
    rng = np.random.RandomState(seed)
    Skv = S if Skv is None else Skv
    q = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, Skv, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, Skv, H, D).astype(np.float32) * 0.5
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _dense_lse(q, k, v, causal):
    import math
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bshd,bthd->bhst", q * scale, k)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    m = jnp.max(s, -1)
    return m + jnp.log(jnp.sum(jnp.exp(s - m[..., None]), -1))


class TestBlockwise:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _rand_qkv()
        out, lse = _blockwise_attention_lse(q, k, v, causal)
        ref = _dense_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(_dense_lse(q, k, v, causal)),
                                   rtol=1e-4, atol=1e-5)

    def test_cross_attention_shapes(self):
        q, k, v = _rand_qkv(S=128, Skv=320)
        out, _ = _blockwise_attention_lse(q, k, v, False)
        ref = _dense_reference(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


class TestPallasKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_interpret(self, causal):
        q, k, v = _rand_qkv(B=1, S=256, H=2, D=64)
        out, lse = mha_fwd(q, k, v, causal=causal, interpret=True)
        ref = _dense_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(_dense_lse(q, k, v, causal)),
                                   rtol=1e-4, atol=1e-5)

    def test_unaligned_seq_padding(self):
        q, k, v = _rand_qkv(B=1, S=200, H=2, D=64, Skv=200)
        out, _ = mha_fwd(q, k, v, causal=True, interpret=True)
        ref = _dense_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_autotuned_blocks_512_256(self):
        # the committed autotune winner (perf/autotune.json fwd 512/256)
        # exercises the uneven block_q != block_k masking path — parity
        # must hold at the blocks production actually runs
        q, k, v = _rand_qkv(B=1, S=1024, H=2, D=64)
        out, lse = mha_fwd(q, k, v, causal=True, block_q=512,
                           block_k=256, interpret=True)
        ref = _dense_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(_dense_lse(q, k, v, True)),
                                   rtol=1e-4, atol=1e-5)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense_autodiff(self, causal):
        q, k, v = _rand_qkv(B=1, S=128, H=2, D=32)

        def loss_flash(q, k, v):
            return jnp.sum(_flash_mha(q, k, v, causal) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense_reference(q, k, v, causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4, err_msg=name)

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_matches_dense_autodiff(self, causal):
        """Hand-tiled Pallas backward (interpret mode) vs jax.grad of the
        dense reference."""
        from paddle_tpu.kernels.pallas_attention import mha_fwd, mha_bwd
        q, k, v = _rand_qkv(B=1, S=256, H=2, D=64)
        out, lse = mha_fwd(q, k, v, causal=causal, interpret=True)
        do = jnp.ones_like(out) * 2.0 * out      # d/dout of sum(out**2)
        dq, dk, dv = mha_bwd(q, k, v, out, lse, do, causal=causal,
                             interpret=True)

        def loss_dense(q, k, v):
            return jnp.sum(_dense_reference(q, k, v, causal) ** 2)

        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip((dq, dk, dv), gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4, err_msg=name)

    def test_pallas_bwd_unaligned_seq_padding(self):
        """Padded q rows must not pollute dk/dv (lse pad kills their p)."""
        from paddle_tpu.kernels.pallas_attention import mha_fwd, mha_bwd
        q, k, v = _rand_qkv(B=1, S=200, H=2, D=64, Skv=200)
        out, lse = mha_fwd(q, k, v, causal=True, interpret=True)
        do = jnp.full_like(out, 0.7)
        dq, dk, dv = mha_bwd(q, k, v, out, lse, do, causal=True,
                             interpret=True)

        def loss_dense(q, k, v):
            return jnp.sum(_dense_reference(q, k, v, True) * 0.7)

        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip((dq, dk, dv), gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4, err_msg=name)

    def test_tensor_level_backward(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        q = np.random.rand(1, 64, 2, 16).astype(np.float32)
        qt = paddle.to_tensor(q, stop_gradient=False)
        out, _ = F.flash_attention(qt, qt, qt, causal=True)
        out.sum().backward()
        assert qt.grad is not None
        assert not np.allclose(qt.grad.numpy(), 0)


def _as_tpu(monkeypatch):
    """Steer the gates to their TPU branch on this CPU, and run the
    tiled kernels they pick in the TPU interpreter."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import pallas_attention as pa
    calls = []

    def interpreted(q, k, v, causal=False):
        calls.append(q.shape)
        return tiled(q, k, v, causal, True)

    tiled = pa.tiled_mha
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "tiled_mha", interpreted)
    return calls


class TestTiledFlash:
    """The training attention's tiled kernels (pallas_attention.tiled_mha)
    in interpret mode against the dense reference and its autodiff."""

    # (S, H, D, dtype, causal): the train cell's head shape, a 128-wide
    # head, the encoders' S=512, and tile edges of 128 / 256 / 512
    CASES = [(1024, 2, 64, "bfloat16", True), (1024, 2, 64, "bfloat16", False),
             (1024, 2, 64, "float32", True), (1024, 2, 64, "float32", False),
             (512, 1, 128, "float32", True), (512, 1, 128, "bfloat16", False),
             (512, 2, 64, "float32", False), (512, 4, 64, "bfloat16", False),
             (768, 2, 64, "float32", True), (384, 2, 128, "float32", True)]

    @pytest.mark.parametrize("S,H,D,dtype,causal", CASES)
    def test_forward_and_backward_match_dense(self, S, H, D, dtype, causal):
        from paddle_tpu.kernels.pallas_attention import tiled_mha
        q, k, v = (x.astype(dtype) for x in _rand_qkv(B=1, S=S, H=H, D=D))
        do = _rand_qkv(B=1, S=S, H=H, D=D, seed=1)[0].astype(dtype)
        f32 = jnp.float32

        def run(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(do)

        got = run(lambda q, k, v: tiled_mha(q, k, v, causal, True))
        want = run(lambda q, k, v: _dense_reference(q, k, v, causal))
        tol = dict(rtol=1e-3, atol=1e-4) if dtype == "float32" \
            else dict(rtol=3e-2, atol=3e-2)
        for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a.astype(f32)),
                                       np.asarray(b.astype(f32)),
                                       err_msg=name, **tol)

    # every attention shape the repo's models make: (S, D) of the GPT
    # ladder, llama, BERT / ERNIE-ViL text, ViT (197 patches: ragged)
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("S,D,tile", [
        (1024, 64, 512), (2048, 64, 512), (2048, 128, 512), (4096, 128, 512),
        (512, 64, 512), (768, 64, 256), (384, 64, 128), (128, 64, 128),
        (197, 64, None), (64, 64, None), (1000, 128, None),
        (1024, 32, None), (1024, 256, None)])
    def test_tile_rule(self, S, D, tile, dtype):
        from paddle_tpu.kernels import pallas_attention as pa
        assert pa.tiled_tile(S, D, dtype) == tile
        if tile is not None:
            assert S % tile == 0 and tile % pa.LANES == 0
            assert pa.tiled_vmem_bytes(
                S, tile, jnp.dtype(dtype).itemsize) <= pa.TILED_VMEM_BUDGET

    def test_tile_rule_refuses_what_does_not_fit_vmem(self):
        from paddle_tpu.kernels import pallas_attention as pa
        assert pa.tiled_tile(16384, 128, "float32") is None
        assert pa.tiled_tile(1024, 64, "float16") is None

    def test_engaged_call_runs_the_kernels(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa
        calls = _as_tpu(monkeypatch)
        q, k, v = _rand_qkv(B=1, S=256, H=2, D=64)
        assert fa._tiled_engages(q, k, v)
        got = jax.grad(lambda q: jnp.sum(
            fa.flash_attention_fn(q, k, v, causal=True) ** 2))(q)
        want = jax.grad(lambda q: jnp.sum(
            _dense_reference(q, k, v, True) ** 2))(q)
        assert calls == [q.shape]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("why", ["ragged_S", "cross_attention",
                                     "odd_head_group", "mesh", "kv_len",
                                     "pallas_off"])
    def test_refused_call_keeps_the_blockwise_path(self, why, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.parallel.mesh import build_mesh, use_mesh
        import contextlib
        calls = _as_tpu(monkeypatch)
        shape = dict(ragged_S=dict(S=200), cross_attention=dict(Skv=512),
                     odd_head_group=dict(H=3)).get(why, {})
        q, k, v = _rand_qkv(**{**dict(B=1, S=256, H=2, D=64), **shape})
        causal = why != "cross_attention"
        kv_len = 100 if why == "kv_len" else None
        if why == "pallas_off":
            monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
        ambient = use_mesh(build_mesh({"dp": 2}, jax.devices()[:2])) \
            if why == "mesh" else contextlib.nullcontext()
        with ambient:
            # context_parallel's kv_len calls enter at _flash_mha, past
            # the predicate (which would admit this shape)
            assert fa._tiled_engages(q, k, v) == (why == "kv_len")
            got = fa._flash_mha(q, k, v, causal, kv_len) if kv_len \
                else fa.flash_attention_fn(q, k, v, causal=causal)
        assert calls == []
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(_dense_reference(q, k, v, causal, kv_len)),
            rtol=1e-4, atol=1e-5)


class TestPallasCrossEntropy:
    """Fused softmax-CE kernel (kernels/pallas_ce.py) vs the jax oracle,
    interpret mode."""

    def _data(self, T=50, V=700, seed=0):
        rng = np.random.RandomState(seed)
        logits = jnp.asarray(rng.randn(T, V).astype(np.float32) * 3)
        tgt = jnp.asarray(rng.randint(0, V, T), jnp.int32)
        return logits, tgt

    def test_forward_parity(self):
        from paddle_tpu.kernels.pallas_ce import ce_with_logits
        logits, tgt = self._data()
        loss = ce_with_logits(logits, tgt, True)
        lse = jax.scipy.special.logsumexp(logits, -1)
        ref = lse - logits[jnp.arange(50), tgt]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gradient_parity(self):
        from paddle_tpu.kernels.pallas_ce import ce_with_logits
        logits, tgt = self._data()

        def f_k(x):
            return jnp.mean(ce_with_logits(x, tgt, True))

        def f_r(x):
            l = jax.scipy.special.logsumexp(x.astype(jnp.float32), -1)
            return jnp.mean(l - x[jnp.arange(50), tgt])

        np.testing.assert_allclose(np.asarray(jax.grad(f_k)(logits)),
                                   np.asarray(jax.grad(f_r)(logits)),
                                   rtol=1e-4, atol=1e-6)

    def test_bf16_and_tile_aligned(self):
        from paddle_tpu.kernels.pallas_ce import ce_with_logits
        logits, tgt = self._data(T=128, V=1024, seed=3)
        lb = logits.astype(jnp.bfloat16)
        loss = ce_with_logits(lb, tgt, True)
        lf = lb.astype(jnp.float32)
        ref = jax.scipy.special.logsumexp(lf, -1) - \
            lf[jnp.arange(128), tgt]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_fused_softmax_ce_dispatch_seam(self, monkeypatch):
        """Drive the PUBLIC entry through the kernel branch (interpret
        mode) and compare against the same entry's jax branch — this
        exercises the reshape/dispatch seam, not just the kernel."""
        from paddle_tpu.models import losses
        from paddle_tpu.kernels import pallas_ce
        logits, tgt = self._data(T=24, V=600, seed=5)
        logits3 = logits.reshape(2, 12, 600)
        tgt3 = tgt.reshape(2, 12)
        jax_val = float(losses.fused_softmax_ce(logits3, tgt3))

        monkeypatch.setattr(losses, "_pallas_ce_enabled", lambda: True)
        monkeypatch.setattr(
            pallas_ce, "ce_with_logits",
            functools.partial(pallas_ce.ce_with_logits, interpret=True))
        kernel_val = float(losses.fused_softmax_ce(logits3, tgt3))
        assert abs(jax_val - kernel_val) < 1e-5

    def test_fused_softmax_ce_mask_through_kernel(self, monkeypatch):
        from paddle_tpu.models import losses
        from paddle_tpu.kernels import pallas_ce
        logits, tgt = self._data(T=24, V=600, seed=7)
        logits3 = logits.reshape(2, 12, 600)
        tgt3 = tgt.reshape(2, 12)
        mask = (jnp.arange(12) < 7)[None, :].repeat(2, 0)
        jax_val = float(losses.fused_softmax_ce(logits3, tgt3,
                                                valid_mask=mask))
        monkeypatch.setattr(losses, "_pallas_ce_enabled", lambda: True)
        monkeypatch.setattr(
            pallas_ce, "ce_with_logits",
            functools.partial(pallas_ce.ce_with_logits, interpret=True))
        kernel_val = float(losses.fused_softmax_ce(logits3, tgt3,
                                                   valid_mask=mask))
        assert abs(jax_val - kernel_val) < 1e-5


class TestPallasFusedCE:
    """One-pass CE+grad kernel (pallas_ce.ce_fused_train / _ce_fused):
    loss AND d_logits out of one launch, vs the jax oracle, interpret
    mode."""

    def _data(self, T=50, V=700, seed=11):
        rng = np.random.RandomState(seed)
        logits = jnp.asarray(rng.randn(T, V).astype(np.float32) * 3)
        tgt = jnp.asarray(rng.randint(0, V, T), jnp.int32)
        return logits, tgt

    def test_loss_matches_two_pass_kernel(self):
        from paddle_tpu.kernels.pallas_ce import (ce_fused_train,
                                                  ce_with_logits)
        logits, tgt = self._data()
        fused = ce_fused_train(logits, tgt, True)
        two_pass = ce_with_logits(logits, tgt, True)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(two_pass),
                                   rtol=1e-6, atol=1e-6)

    def test_grad_parity_vs_jax_oracle(self):
        """The folded backward (saved d_logits × cotangent) against
        jax.grad of the dense logsumexp form."""
        from paddle_tpu.kernels.pallas_ce import ce_fused_train
        logits, tgt = self._data()

        def f_k(x):
            return jnp.mean(ce_fused_train(x, tgt, True))

        def f_r(x):
            l = jax.scipy.special.logsumexp(x.astype(jnp.float32), -1)
            return jnp.mean(l - x[jnp.arange(50), tgt])

        np.testing.assert_allclose(np.asarray(jax.grad(f_k)(logits)),
                                   np.asarray(jax.grad(f_r)(logits)),
                                   rtol=1e-4, atol=1e-6)

    def test_bf16_unaligned_padding(self):
        from paddle_tpu.kernels.pallas_ce import ce_fused_train
        logits, tgt = self._data(T=37, V=900, seed=13)
        lb = logits.astype(jnp.bfloat16)

        def f_k(x):
            return jnp.sum(ce_fused_train(x, tgt, True)
                           * jnp.arange(37, dtype=jnp.float32))

        def f_r(x):
            lf = x.astype(jnp.float32)
            per = jax.scipy.special.logsumexp(lf, -1) - \
                lf[jnp.arange(37), tgt]
            return jnp.sum(per * jnp.arange(37, dtype=jnp.float32))

        np.testing.assert_allclose(float(f_k(lb)), float(f_r(lb)),
                                   rtol=1e-3)
        np.testing.assert_allclose(
            np.asarray(jax.grad(f_k)(lb)).astype(np.float32),
            np.asarray(jax.grad(f_r)(lb)).astype(np.float32),
            rtol=0.1, atol=0.05)

    def test_constant_selects_fused_impl(self, monkeypatch):
        """losses.fused_softmax_ce routes onto ce_fused_train ONLY when
        losses.CE_FUSED_GRAD is set."""
        from paddle_tpu.models import losses
        from paddle_tpu.kernels import pallas_ce
        logits, tgt = self._data(T=24, V=600, seed=17)
        logits3 = logits.reshape(2, 12, 600)
        tgt3 = tgt.reshape(2, 12)
        jax_val = float(losses.fused_softmax_ce(logits3, tgt3))

        monkeypatch.setattr(losses, "_pallas_ce_enabled", lambda: True)
        monkeypatch.setattr(losses, "CE_FUSED_GRAD", True)
        seen = []
        real = pallas_ce.ce_fused_train

        def spy(x, t, interpret=False):
            seen.append("fused")
            return real(x, t, True)
        monkeypatch.setattr(pallas_ce, "ce_fused_train", spy)
        fused_val = float(losses.fused_softmax_ce(logits3, tgt3))
        assert seen == ["fused"]
        assert abs(jax_val - fused_val) < 1e-5


class TestPallasFusedUpdate:
    """Fused AdamW/AMP master-update kernel (kernels/pallas_update.py)
    vs the models.gpt.apply_adamw oracle, interpret mode."""

    def _tree(self, seed=0, dtype=jnp.float32):
        rng = np.random.RandomState(seed)

        def t(*shape):
            return jnp.asarray(rng.randn(*shape).astype(np.float32))
        params = {"w": t(33, 257).astype(dtype), "b": t(64),
                  "s": t(3, 5, 7)}
        grads = {"w": t(33, 257).astype(dtype), "b": t(64),
                 "s": t(3, 5, 7)}
        opt = {"m": jax.tree_util.tree_map(
                   lambda p: t(*p.shape), params),
               "v": jax.tree_util.tree_map(
                   lambda p: jnp.abs(t(*p.shape)), params),
               "step": jnp.asarray(4.0, jnp.float32)}
        return params, grads, opt

    def test_parity_vs_oracle(self):
        from paddle_tpu.models.gpt import apply_adamw
        from paddle_tpu.kernels.pallas_update import fused_apply_adamw
        params, grads, opt = self._tree()
        ref_p, ref_o = apply_adamw(grads, params, opt, 1e-3)
        got_p, got_o = fused_apply_adamw(grads, params, opt, 1e-3,
                                         interpret=True)
        for k in params:
            np.testing.assert_allclose(np.asarray(got_p[k]),
                                       np.asarray(ref_p[k]),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(got_o["m"][k]),
                                       np.asarray(ref_o["m"][k]),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(got_o["v"][k]),
                                       np.asarray(ref_o["v"][k]),
                                       rtol=1e-6, atol=1e-7)
        assert float(got_o["step"]) == float(ref_o["step"])

    def test_parity_bf16_master_math(self):
        """bf16 params keep f32 moments and f32 master math — the AMP
        master-update contract."""
        from paddle_tpu.models.gpt import apply_adamw
        from paddle_tpu.kernels.pallas_update import fused_apply_adamw
        params, grads, opt = self._tree(seed=3, dtype=jnp.bfloat16)
        ref_p, ref_o = apply_adamw(grads, params, opt, 3e-4,
                                   weight_decay=0.05)
        got_p, got_o = fused_apply_adamw(grads, params, opt, 3e-4,
                                         weight_decay=0.05,
                                         interpret=True)
        assert got_p["w"].dtype == jnp.bfloat16
        assert got_o["m"]["w"].dtype == jnp.float32
        for k in params:
            np.testing.assert_allclose(
                np.asarray(got_p[k]).astype(np.float32),
                np.asarray(ref_p[k]).astype(np.float32),
                rtol=1e-2, atol=1e-3)
            np.testing.assert_allclose(np.asarray(got_o["v"][k]),
                                       np.asarray(ref_o["v"][k]),
                                       rtol=1e-6, atol=1e-7)

    def test_off_by_default_and_kill_switch(self, monkeypatch):
        """apply_adamw stays on the jax path, on the TPU too, while
        FUSED_UPDATE is off; the global kill switch vetoes it when on."""
        from paddle_tpu.kernels import pallas_update
        assert not pallas_update.fused_update_enabled()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert not pallas_update.fused_update_enabled()
        monkeypatch.setattr(pallas_update, "FUSED_UPDATE", True)
        assert pallas_update.fused_update_enabled()
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
        assert not pallas_update.fused_update_enabled()


class TestKillSwitchGates:
    """The kill-switch family must stay layered: global > attention-only
    > backward-only, with the CE kernel on the global gate only."""

    def test_attn_kill_leaves_ce_enabled(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS_ATTN", "1")
        assert not fa._pallas_attn_enabled()
        assert not fa._pallas_bwd_enabled()
        assert fa._pallas_enabled()      # CE gate path stays live

    def test_global_kill_covers_all(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
        assert not fa._pallas_enabled()
        assert not fa._pallas_attn_enabled()
        assert not fa._pallas_bwd_enabled()

    def test_env_blocks_outrank_autotune_cache(self, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.kernels import autotune
        q = jnp.zeros((8, 1024, 16, 64), jnp.bfloat16)
        sig = fa._flash_sig(q, q, True)
        monkeypatch.setattr(autotune, "_CACHE",
                            {f"flash_fwd::{sig}": [512, 256],
                             f"flash_bwd::{sig}": [256, 256]})
        monkeypatch.setattr(autotune, "_loaded", True)
        assert fa._tuned_blocks(q, q, True) == (512, 256)
        assert fa._tuned_blocks_bwd(q, q, True) == (256, 256)
        monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "256")
        assert fa._tuned_blocks(q, q, True) is None
        assert fa._tuned_blocks_bwd(q, q, True) == (256, 256)
        monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_BWD_K", "128")
        assert fa._tuned_blocks_bwd(q, q, True) is None

    def test_attn_impl_selector(self, monkeypatch):
        import jax
        from paddle_tpu.kernels import flash_attention as fa
        calls = []
        monkeypatch.setattr(fa, "_jax_flash_mha",
                            lambda q, k, v, c: calls.append("jax") or v)
        monkeypatch.setattr(fa, "_flash_mha",
                            lambda q, k, v, c: calls.append("own") or v)
        q = jnp.zeros((1, 8, 2, 4), jnp.float32)
        fa._dispatch_mha(q, q, q, True)
        assert calls == ["own"]          # default impl
        monkeypatch.setattr(fa, "_attn_impl", lambda: "jax_flash")
        fa._dispatch_mha(q, q, q, True)
        # CPU backend: upstream TPU kernel must NOT be selected
        expected = "jax" if jax.default_backend() == "tpu" else "own"
        assert calls[-1] == expected


class TestUpstreamImpls:
    """The upstream jax.experimental attention kernels against the
    dense oracle, interpret mode on CPU."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_splash_matches_dense(self, causal):
        from paddle_tpu.kernels import flash_attention as fa
        q, k, v = _rand_qkv(B=2, S=256, H=4, D=64)
        got = np.asarray(fa._splash_mha(q, k, v, causal, interpret=True))
        want = np.asarray(fa._dense_reference(q, k, v, causal))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    def test_splash_full_train_step_interpret(self, monkeypatch):
        """The whole GPT train step (scan over layers, dots_flash remat,
        AdamW) must trace and differentiate through the upstream splash
        kernel — catches custom_vjp x checkpoint x vmap interactions on
        CPU before any chip time is spent racing it."""
        import functools
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                           init_opt_state, train_step)
        monkeypatch.setattr(
            fa, "flash_attention_fn",
            lambda q, k, v, causal=False: fa._splash_mha(
                q, k, v, causal, interpret=True))
        cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                        num_heads=2, max_seq_len=128, dtype=jnp.float32,
                        sequence_parallel=False, remat=True,
                        remat_policy="dots_flash")
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 256)
        step = jax.jit(functools.partial(train_step, cfg=cfg, lr=1e-4))
        loss, params2, opt2 = step(params, opt, toks)
        assert np.isfinite(float(loss))
        # gradient really flowed: the AdamW first moment is grad-derived
        # (a params delta alone would also come from weight decay)
        m_wte = float(jnp.abs(opt2["m"]["wte"]).max())
        assert m_wte > 0
