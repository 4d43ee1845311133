"""Op parity tests vs numpy — the OpTest analog
(reference: test/legacy_test/eager_op_test.py:377 check_output/check_grad).
Each op runs eagerly AND under jit (to_static), compared against numpy, plus
numeric-vs-analytic gradient checks on a sample of ops.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit


def check(pd_fn, np_fn, *arrays, rtol=1e-5, atol=1e-6, grad_idx=None):
    tensors = [paddle.to_tensor(a, stop_gradient=False) for a in arrays]
    out = pd_fn(*tensors)
    expect = np_fn(*arrays)
    np.testing.assert_allclose(out.numpy(), expect, rtol=rtol, atol=atol)

    # jit path parity
    sfn = paddle.jit.to_static(lambda *ts: pd_fn(*ts))
    out_jit = sfn(*tensors)
    np.testing.assert_allclose(out_jit.numpy(), expect, rtol=rtol, atol=atol)

    # analytic-vs-numeric gradient (OpTest.check_grad analog)
    if grad_idx is not None:
        loss = out.sum()
        loss.backward()
        g = tensors[grad_idx].grad.numpy()
        eps = 1e-3
        a = arrays[grad_idx].astype(np.float64)
        num = np.zeros_like(a)
        flat = a.reshape(-1)
        for i in range(min(flat.size, 8)):
            up, dn = flat.copy(), flat.copy()
            up[i] += eps
            dn[i] -= eps
            args_u = list(arrays)
            args_u[grad_idx] = up.reshape(a.shape).astype(arrays[grad_idx].dtype)
            args_d = list(arrays)
            args_d[grad_idx] = dn.reshape(a.shape).astype(arrays[grad_idx].dtype)
            num.reshape(-1)[i] = (np_fn(*args_u).sum() -
                                  np_fn(*args_d).sum()) / (2 * eps)
        np.testing.assert_allclose(g.reshape(-1)[:8], num.reshape(-1)[:8],
                                   rtol=1e-2, atol=1e-2)


A = np.random.rand(3, 4).astype(np.float32) + 0.5
B = np.random.rand(3, 4).astype(np.float32) + 0.5
M1 = np.random.rand(3, 4).astype(np.float32)
M2 = np.random.rand(4, 5).astype(np.float32)


class TestBinary:
    def test_add(self):
        check(paddle.add, np.add, A, B, grad_idx=0)

    def test_subtract(self):
        check(paddle.subtract, np.subtract, A, B, grad_idx=1)

    def test_multiply(self):
        check(paddle.multiply, np.multiply, A, B, grad_idx=0)

    def test_divide(self):
        check(paddle.divide, np.divide, A, B, grad_idx=0)

    def test_pow(self):
        check(paddle.pow, np.power, A, B)

    def test_maximum(self):
        check(paddle.maximum, np.maximum, A, B)

    def test_matmul(self):
        check(paddle.matmul, np.matmul, M1, M2, grad_idx=0)

    def test_matmul_transpose(self):
        out = paddle.matmul(paddle.to_tensor(M1), paddle.to_tensor(M1),
                            transpose_y=True)
        np.testing.assert_allclose(out.numpy(), M1 @ M1.T, rtol=1e-5)

    def test_scalar_broadcast(self):
        x = paddle.to_tensor(A)
        np.testing.assert_allclose((x + 1.5).numpy(), A + 1.5, rtol=1e-6)
        np.testing.assert_allclose((2.0 * x).numpy(), 2.0 * A, rtol=1e-6)
        np.testing.assert_allclose((1.0 / x).numpy(), 1.0 / A, rtol=1e-5)


def test_equal_literals_of_different_type_do_not_share_a_closure():
    """1 == 1.0 == True and they hash alike; the dispatch cache keys a
    baked-in literal by its type too, or `x + 1` on an int tensor would
    reuse the closure compiled for `x + 1.0` and come back float."""
    i = paddle.to_tensor(np.array([3], np.int32))
    assert (paddle.to_tensor(np.array([3.0], np.float32)) + 1.0).dtype \
        == paddle.float32
    out = i + 1
    assert out.dtype == paddle.int32 and int(out.numpy()[0]) == 4
    traced = paddle.jit.to_static(lambda t: t + 1)(i)
    assert traced.dtype == paddle.int32 and int(traced.numpy()[0]) == 4


class TestUnary:
    @pytest.mark.parametrize("name,npfn", [
        ("exp", np.exp), ("log", np.log), ("sqrt", np.sqrt),
        ("tanh", np.tanh), ("sin", np.sin), ("cos", np.cos),
        ("abs", np.abs), ("square", np.square), ("log1p", np.log1p),
    ])
    def test_elementwise(self, name, npfn):
        check(getattr(paddle, name), npfn, A, grad_idx=0)

    @pytest.mark.parametrize("name,npfn", [
        ("floor", np.floor), ("ceil", np.ceil),
    ])
    def test_elementwise_discontinuous(self, name, npfn):
        # floor/ceil are piecewise-constant: finite differences blow up
        # near integer boundaries, so assert the analytic zero gradient.
        check(getattr(paddle, name), npfn, A)
        x = paddle.to_tensor(A, stop_gradient=False)
        getattr(paddle, name)(x).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.zeros_like(A))

    def test_sigmoid(self):
        import paddle_tpu.nn.functional as F
        check(F.sigmoid, lambda x: 1 / (1 + np.exp(-x)), A)

    def test_clip(self):
        out = paddle.clip(paddle.to_tensor(A), 0.6, 1.0)
        np.testing.assert_allclose(out.numpy(), np.clip(A, 0.6, 1.0))

    def test_rsqrt(self):
        check(paddle.rsqrt, lambda x: 1.0 / np.sqrt(x), A, rtol=1e-4)


class TestReduce:
    def test_sum(self):
        check(lambda x: paddle.sum(x), lambda x: np.sum(x), A, grad_idx=0)
        check(lambda x: paddle.sum(x, axis=1),
              lambda x: np.sum(x, axis=1), A)
        check(lambda x: paddle.sum(x, axis=[0, 1], keepdim=True),
              lambda x: np.sum(x, axis=(0, 1), keepdims=True), A)

    def test_mean_max_min_prod(self):
        check(lambda x: paddle.mean(x, axis=0),
              lambda x: np.mean(x, axis=0), A, grad_idx=0)
        check(lambda x: paddle.max(x, axis=1),
              lambda x: np.max(x, axis=1), A)
        check(lambda x: paddle.min(x), lambda x: np.min(x), A)
        check(lambda x: paddle.prod(x, axis=0),
              lambda x: np.prod(x, axis=0), A)

    def test_var_std(self):
        check(lambda x: paddle.var(x), lambda x: np.var(x, ddof=1), A,
              rtol=1e-4)
        check(lambda x: paddle.std(x, unbiased=False),
              lambda x: np.std(x), A, rtol=1e-4)

    def test_logsumexp(self):
        from scipy.special import logsumexp as sls
        check(lambda x: paddle.logsumexp(x, axis=1),
              lambda x: sls(x, axis=1), A, rtol=1e-5)

    def test_cumsum(self):
        check(lambda x: paddle.cumsum(x, axis=1),
              lambda x: np.cumsum(x, axis=1), A, grad_idx=0)

    def test_all_any(self):
        m = A > 0.8
        t = paddle.to_tensor(m)
        assert paddle.all(t).item() == np.all(m)
        assert paddle.any(t).item() == np.any(m)
        np.testing.assert_array_equal(
            paddle.any(t, axis=0).numpy(), np.any(m, axis=0))


class TestInplaceAndAutograd:
    def test_grad_accumulation(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        y = x * 2.0
        z = x * 3.0
        (y.sum() + z.sum()).backward()
        np.testing.assert_allclose(x.grad.numpy(), np.full_like(A, 5.0))

    def test_retain_graph(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        y = (x * x).sum()
        y.backward(retain_graph=True)
        g1 = x.grad.numpy().copy()
        x.clear_grad()
        y.backward()
        np.testing.assert_allclose(x.grad.numpy(), g1)

    def test_released_graph_errors(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        y = (x * x).sum()
        y.backward()
        with pytest.raises(RuntimeError):
            y.backward()

    def test_no_grad(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        with paddle.no_grad():
            y = x * 2
        assert y.stop_gradient

    def test_paddle_grad(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        y = paddle.to_tensor(B, stop_gradient=False)
        z = (x * y).sum()
        gx, = paddle.grad(z, [x], retain_graph=False)
        np.testing.assert_allclose(gx.numpy(), B)

    def test_stop_gradient_cut(self):
        x = paddle.to_tensor(A, stop_gradient=False)
        y = (x * 2).detach()
        z = y * 3
        assert z.stop_gradient

    def test_second_use_after_inplace_param_update(self):
        # tape snapshots values: mutating a leaf after forward must not
        # corrupt backward (TensorWrapper semantics)
        x = paddle.to_tensor(A, stop_gradient=False)
        y = (x * x).sum()
        x._value = paddle.zeros(x.shape)._value  # simulate optimizer step
        y.backward()
        np.testing.assert_allclose(x.grad.numpy(), 2 * A, rtol=1e-5)


class TestLuUnpackCdist:
    """lu_unpack + cdist (reference tensor/linalg.py:2205, cdist)."""

    def test_lu_unpack_reconstructs(self):
        rng = np.random.RandomState(0)
        A = rng.randn(5, 5).astype(np.float32)
        lu, piv = paddle.linalg.lu(paddle.to_tensor(A))
        P, L, U = paddle.linalg.lu_unpack(lu, piv)
        np.testing.assert_allclose(P.numpy() @ L.numpy() @ U.numpy(), A,
                                   atol=1e-5)
        # L unit-lower-triangular, U upper-triangular
        np.testing.assert_allclose(np.diag(L.numpy()), 1.0, atol=1e-6)
        assert np.allclose(np.tril(U.numpy(), -1), 0.0)

    def test_lu_unpack_batched_and_rect(self):
        rng = np.random.RandomState(1)
        B = rng.randn(3, 4, 4).astype(np.float32)
        lu, piv = paddle.linalg.lu(paddle.to_tensor(B))
        P, L, U = paddle.linalg.lu_unpack(lu, piv)
        np.testing.assert_allclose(
            np.einsum("bij,bjk,bkl->bil", P.numpy(), L.numpy(),
                      U.numpy()), B, atol=1e-5)
        R = rng.randn(5, 3).astype(np.float32)
        lu, piv = paddle.linalg.lu(paddle.to_tensor(R))
        P, L, U = paddle.linalg.lu_unpack(lu, piv)
        assert L.numpy().shape == (5, 3) and U.numpy().shape == (3, 3)
        np.testing.assert_allclose(P.numpy() @ L.numpy() @ U.numpy(), R,
                                   atol=1e-5)

    def test_lu_unpack_flags(self):
        A = np.eye(3, dtype=np.float32)
        lu, piv = paddle.linalg.lu(paddle.to_tensor(A))
        P, L, U = paddle.linalg.lu_unpack(lu, piv, unpack_ludata=False)
        assert L is None and U is None and P is not None

    def test_cdist_matches_scipy(self):
        import scipy.spatial.distance as sd
        rng = np.random.RandomState(2)
        x = rng.randn(4, 3).astype(np.float32)
        y = rng.randn(6, 3).astype(np.float32)
        for p in (1.0, 2.0, 3.0, float("inf")):
            got = paddle.cdist(paddle.to_tensor(x), paddle.to_tensor(y),
                               p=p).numpy()
            want = (sd.cdist(x, y, "chebyshev") if np.isinf(p)
                    else sd.cdist(x, y, "minkowski", p=p))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_cdist_batched(self):
        import scipy.spatial.distance as sd
        rng = np.random.RandomState(3)
        xb = rng.randn(2, 4, 3).astype(np.float32)
        yb = rng.randn(2, 5, 3).astype(np.float32)
        got = paddle.cdist(paddle.to_tensor(xb),
                           paddle.to_tensor(yb)).numpy()
        assert got.shape == (2, 4, 5)
        np.testing.assert_allclose(got[1], sd.cdist(xb[1], yb[1]),
                                   rtol=1e-4, atol=1e-5)

    def test_cdist_zero_distance_gradients_finite(self):
        # regression: sqrt'(0)=inf made cdist(x,x) backprop NaN
        x = paddle.to_tensor(np.array([[0., 0.], [1., 1.]], np.float32),
                             stop_gradient=False)
        paddle.cdist(x, x).sum().backward()
        assert np.isfinite(x.grad.numpy()).all(), x.grad.numpy()
