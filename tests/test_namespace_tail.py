"""Top-level namespace tail (reference python/paddle/__init__.py
__all__): numpy/torch oracles for the op tail, in-place semantics,
framework shims, and the completeness assertion itself."""
import ast
import os

import numpy as np
import pytest
import torch

import paddle_tpu as paddle

rng = np.random.RandomState(0)


class TestMathTail:
    def test_quantile_and_nan(self):
        x = rng.randn(4, 6).astype(np.float32)
        t = paddle.to_tensor(x)
        np.testing.assert_allclose(paddle.quantile(t, 0.3).numpy(),
                                   np.quantile(x, 0.3), rtol=1e-5)
        np.testing.assert_allclose(
            paddle.quantile(t, 0.5, axis=1).numpy(),
            np.quantile(x, 0.5, axis=1), rtol=1e-5)
        xn = x.copy()
        xn[0, 0] = np.nan
        np.testing.assert_allclose(
            paddle.nanquantile(paddle.to_tensor(xn), 0.4).numpy(),
            np.nanquantile(xn, 0.4), rtol=1e-5)

    def test_diff_sgn_frexp(self):
        d = rng.randn(5).astype(np.float32)
        np.testing.assert_allclose(
            paddle.diff(paddle.to_tensor(d)).numpy(), np.diff(d),
            rtol=1e-6)
        np.testing.assert_allclose(
            paddle.diff(paddle.to_tensor(d), prepend=paddle.to_tensor(
                np.zeros(1, np.float32))).numpy(),
            np.diff(d, prepend=0.0), rtol=1e-6)
        c = (rng.randn(4) + 1j * rng.randn(4)).astype(np.complex64)
        np.testing.assert_allclose(
            paddle.sgn(paddle.to_tensor(c)).numpy(),
            torch.sgn(torch.tensor(c)).numpy(), rtol=1e-5)
        x = rng.randn(4, 6).astype(np.float32)
        m, e = paddle.frexp(paddle.to_tensor(x))
        np.testing.assert_allclose(m.numpy() * 2.0 ** e.numpy(), x,
                                   rtol=1e-6)

    def test_trapezoid_polar_vander(self):
        y = rng.randn(6).astype(np.float32)
        xs = np.sort(rng.rand(6).astype(np.float32))
        np.testing.assert_allclose(
            paddle.trapezoid(paddle.to_tensor(y),
                             paddle.to_tensor(xs)).numpy(),
            np.trapezoid(y, xs), rtol=1e-5)
        np.testing.assert_allclose(
            paddle.cumulative_trapezoid(paddle.to_tensor(y),
                                        paddle.to_tensor(xs)).numpy(),
            torch.cumulative_trapezoid(torch.tensor(y),
                                       torch.tensor(xs)).numpy(),
            rtol=1e-4, atol=1e-6)
        mag = np.abs(rng.randn(4)).astype(np.float32)
        ang = rng.randn(4).astype(np.float32)
        np.testing.assert_allclose(
            paddle.polar(paddle.to_tensor(mag),
                         paddle.to_tensor(ang)).numpy(),
            torch.polar(torch.tensor(mag), torch.tensor(ang)).numpy(),
            rtol=1e-5, atol=1e-6)
        v = rng.randn(4).astype(np.float32)
        np.testing.assert_allclose(
            paddle.vander(paddle.to_tensor(v), 3).numpy(),
            np.vander(v, 3), rtol=1e-5)


class TestManipulationTail:
    def test_vsplit_take_unflatten_tolist(self):
        x = rng.randn(4, 6).astype(np.float32)
        t = paddle.to_tensor(x)
        parts = paddle.vsplit(t, 2)
        assert len(parts) == 2 and tuple(parts[0].shape) == (2, 6)
        with pytest.raises(ValueError):
            paddle.vsplit(paddle.to_tensor(np.zeros(3, np.float32)), 3)
        np.testing.assert_allclose(
            paddle.take(t, paddle.to_tensor(
                np.array([0, 7, -1]))).numpy(),
            x.ravel()[[0, 7, -1]])
        np.testing.assert_allclose(
            paddle.take(t, paddle.to_tensor(np.array([100, -100])),
                        mode="wrap").numpy(),
            x.ravel()[[100 % 24, -100 % 24]])
        with pytest.raises(ValueError):
            paddle.take(t, paddle.to_tensor(np.array([99])))
        assert tuple(paddle.unflatten(t, 1, [2, 3]).shape) == (4, 2, 3)
        assert paddle.tolist(t) == x.tolist()

    def test_inplace_family(self):
        a = paddle.to_tensor(np.zeros((3, 2), np.float32))
        paddle.index_add_(a, paddle.to_tensor(np.array([0, 2])), 0,
                          paddle.to_tensor(np.ones((2, 2), np.float32)))
        np.testing.assert_allclose(a.numpy(),
                                   [[1, 1], [0, 0], [1, 1]])
        b = paddle.to_tensor(np.zeros((2, 2), np.float32))
        paddle.index_put_(
            b, (paddle.to_tensor(np.array([0, 1])),
                paddle.to_tensor(np.array([1, 0]))),
            paddle.to_tensor(np.array([5.0, 7.0], np.float32)))
        np.testing.assert_allclose(b.numpy(), [[0, 5], [7, 0]])
        s = paddle.to_tensor(np.zeros((3, 2), np.float32))
        paddle.scatter_(s, paddle.to_tensor(np.array([1])),
                        paddle.to_tensor(
                            np.full((1, 2), 9.0, np.float32)))
        np.testing.assert_allclose(s.numpy()[1], 9.0)
        t = paddle.to_tensor(np.array([0.5], np.float32))
        paddle.tanh_(t)
        np.testing.assert_allclose(t.numpy(), np.tanh([0.5]), rtol=1e-6)


class TestShims:
    def test_rng_state_guard_param(self):
        st = paddle.get_cuda_rng_state()
        paddle.set_cuda_rng_state(st)
        paddle.disable_signal_handler()
        with paddle.LazyGuard():
            assert paddle.LazyGuard._active
            p = paddle.create_parameter([3, 4], "float32")
        assert not paddle.LazyGuard._active
        assert tuple(p.shape) == (3, 4)
        paddle.check_shape([1, 2, 3])
        with pytest.raises(TypeError):
            paddle.check_shape([1, "x"])

    @pytest.mark.skipif(not os.path.isdir("/root/reference"),
                        reason="the reference tree is not mounted here")
    def test_reference_top_level_all_complete(self):
        src = open("/root/reference/python/paddle/__init__.py").read()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Assign) and getattr(
                    node.targets[0], "id", "") == "__all__":
                ref = [getattr(e, "value", None)
                       for e in node.value.elts]
        missing = [r for r in ref if r and not hasattr(paddle, r)]
        assert not missing, missing


class TestTensorMethodSurface:
    def test_reference_method_list_complete(self):
        import os
        from paddle_tpu.framework.tensor import Tensor
        ref = open(os.path.join(os.path.dirname(
            paddle.__file__), "tensor", "reference_methods.txt")).read() \
            .split()
        missing = sorted(set(n for n in ref if not hasattr(Tensor, n)))
        assert not missing, missing

    def test_method_dispatch_and_grads(self):
        t = paddle.to_tensor(np.array([[4.0, 9.0]], np.float32))
        np.testing.assert_allclose(t.sqrt().numpy(),
                                   np.sqrt(t.numpy()), rtol=1e-6)
        assert t.is_floating_point()
        g = paddle.to_tensor(np.array([4.0], np.float32),
                             stop_gradient=False)
        g.sqrt().backward()
        np.testing.assert_allclose(g.grad.numpy(), [0.25])

    def test_inplace_method_family(self):
        x = paddle.to_tensor(np.array([4.0, 9.0], np.float32))
        x.sqrt_()
        np.testing.assert_allclose(x.numpy(), [2.0, 3.0])
        x.round_()
        np.testing.assert_allclose(x.numpy(), [2.0, 3.0])
        s = paddle.to_tensor(np.array([0.0], np.float32))
        s.sigmoid_()
        np.testing.assert_allclose(s.numpy(), [0.5])
