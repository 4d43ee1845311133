"""nn/nn.functional long-tail parity (reference python/paddle/nn +
nn/functional __all__): torch oracles for the loss/pool/warp families,
brute-force lattice check for rnnt, protocol test for beam search."""
import os

import numpy as np
import pytest
import torch
import torch.nn.functional as TF

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.tensor import Tensor

rng = np.random.RandomState(0)


class TestMaskAndUnpool:
    def test_2d_mask_unpool_vs_torch(self):
        xt = rng.randn(2, 3, 8, 8).astype(np.float32)
        out, mask = F.max_pool2d(paddle.to_tensor(xt), 2, 2,
                                 return_mask=True)
        tout, tmask = TF.max_pool2d(torch.tensor(xt), 2, 2,
                                    return_indices=True)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(mask.numpy(), tmask.numpy())
        np.testing.assert_allclose(
            F.max_unpool2d(out, mask, 2, 2).numpy(),
            TF.max_unpool2d(tout, tmask, 2, 2).numpy())

    def test_1d_3d_mask_unpool_vs_torch(self):
        x1 = rng.randn(2, 3, 10).astype(np.float32)
        o1, m1 = F.max_pool1d(paddle.to_tensor(x1), 2, 2,
                              return_mask=True)
        to1, tm1 = TF.max_pool1d(torch.tensor(x1), 2, 2,
                                 return_indices=True)
        np.testing.assert_array_equal(m1.numpy(), tm1.numpy())
        np.testing.assert_allclose(
            F.max_unpool1d(o1, m1, 2, 2).numpy(),
            TF.max_unpool1d(to1, tm1, 2, 2).numpy())
        x3 = rng.randn(1, 2, 4, 4, 4).astype(np.float32)
        o3, m3 = F.max_pool3d(paddle.to_tensor(x3), 2, 2,
                              return_mask=True)
        to3, tm3 = TF.max_pool3d(torch.tensor(x3), 2, 2,
                                 return_indices=True)
        np.testing.assert_array_equal(m3.numpy(), tm3.numpy())
        np.testing.assert_allclose(
            F.max_unpool3d(o3, m3, 2, 2).numpy(),
            TF.max_unpool3d(to3, tm3, 2, 2).numpy())

    def test_overlapping_windows_with_padding(self):
        xt = rng.randn(1, 1, 5, 5).astype(np.float32)
        out, mask = F.max_pool2d(paddle.to_tensor(xt), 3, 2, padding=1,
                                 return_mask=True)
        tout, tmask = TF.max_pool2d(torch.tensor(xt), 3, 2, padding=1,
                                    return_indices=True)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(mask.numpy(), tmask.numpy())

    def test_adaptive_max_pool3d(self):
        x = rng.randn(1, 2, 4, 4, 4).astype(np.float32)
        got = F.adaptive_max_pool3d(paddle.to_tensor(x), 2).numpy()
        want = TF.adaptive_max_pool3d(torch.tensor(x), 2).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-6)


class TestLossZoo:
    def test_losses_vs_torch(self):
        inp = rng.randn(5, 4).astype(np.float32)
        lab = rng.randint(0, 4, 5).astype(np.int64)
        np.testing.assert_allclose(
            F.multi_margin_loss(paddle.to_tensor(inp),
                                paddle.to_tensor(lab)).numpy(),
            TF.multi_margin_loss(torch.tensor(inp),
                                 torch.tensor(lab)).numpy(), rtol=1e-5)
        y2 = np.sign(rng.randn(5, 4)).astype(np.float32)
        np.testing.assert_allclose(
            F.soft_margin_loss(paddle.to_tensor(inp),
                               paddle.to_tensor(y2)).numpy(),
            TF.soft_margin_loss(torch.tensor(inp),
                                torch.tensor(y2)).numpy(), rtol=1e-5)
        ml = (rng.rand(5, 4) > 0.5).astype(np.float32)
        np.testing.assert_allclose(
            F.multi_label_soft_margin_loss(
                paddle.to_tensor(inp), paddle.to_tensor(ml)).numpy(),
            TF.multilabel_soft_margin_loss(
                torch.tensor(inp), torch.tensor(ml)).numpy(), rtol=1e-5)

    def test_nll_family_vs_torch(self):
        pred = np.abs(rng.randn(6).astype(np.float32)) + 0.1
        tgt = np.abs(rng.randn(6).astype(np.float32))
        for full in (False, True):
            np.testing.assert_allclose(
                F.poisson_nll_loss(paddle.to_tensor(pred),
                                   paddle.to_tensor(tgt),
                                   full=full).numpy(),
                TF.poisson_nll_loss(torch.tensor(pred),
                                    torch.tensor(tgt),
                                    full=full).numpy(), rtol=1e-5)
        var = np.abs(rng.randn(6).astype(np.float32)) + 0.1
        np.testing.assert_allclose(
            F.gaussian_nll_loss(paddle.to_tensor(pred),
                                paddle.to_tensor(tgt),
                                paddle.to_tensor(var)).numpy(),
            TF.gaussian_nll_loss(torch.tensor(pred), torch.tensor(tgt),
                                 torch.tensor(var)).numpy(), rtol=1e-4)

    def test_triplet_and_pairwise_vs_torch(self):
        a = rng.randn(4, 8).astype(np.float32)
        p = rng.randn(4, 8).astype(np.float32)
        n = rng.randn(4, 8).astype(np.float32)
        np.testing.assert_allclose(
            F.triplet_margin_with_distance_loss(
                paddle.to_tensor(a), paddle.to_tensor(p),
                paddle.to_tensor(n)).numpy(),
            TF.triplet_margin_with_distance_loss(
                torch.tensor(a), torch.tensor(p),
                torch.tensor(n)).numpy(), rtol=1e-4, atol=1e-5)
        for pp in (1.0, 2.0, float("inf")):
            np.testing.assert_allclose(
                F.pairwise_distance(paddle.to_tensor(a),
                                    paddle.to_tensor(p), p=pp).numpy(),
                TF.pairwise_distance(torch.tensor(a), torch.tensor(p),
                                     p=pp).numpy(),
                rtol=1e-4, atol=1e-5)

    def test_rnnt_loss_brute_force(self):
        from itertools import combinations
        B, T, U, V = 1, 3, 2, 3
        logits = rng.randn(B, T, U + 1, V).astype(np.float32)
        labels = np.array([[1, 2]], np.int64)
        got = float(F.rnnt_loss(
            paddle.to_tensor(logits), paddle.to_tensor(labels),
            paddle.to_tensor(np.array([T], np.int32)),
            paddle.to_tensor(np.array([U], np.int32)),
            blank=0, reduction="none").numpy()[0])
        lp = torch.log_softmax(torch.tensor(logits), dim=-1).numpy()[0]
        total = -np.inf
        for emits in combinations(range(T + U), U):
            t = u = 0
            logp = 0.0
            ok = True
            for step in range(T + U):
                if step in emits:
                    if u >= U or t >= T:
                        ok = False
                        break
                    logp += lp[t, u, labels[0, u]]
                    u += 1
                else:
                    if t >= T:
                        ok = False
                        break
                    logp += lp[t, u, 0]
                    t += 1
            if ok and u == U and t == T:
                total = np.logaddexp(total, logp)
        assert abs(got + total) < 1e-3

    def test_dice_perfect_prediction(self):
        pred = np.zeros((2, 4), np.float32)
        pred[[0, 1], [0, 1]] = 1.0
        lab = np.array([[0], [1]], np.int64)
        assert float(F.dice_loss(paddle.to_tensor(pred),
                                 paddle.to_tensor(lab)).numpy()) < 1e-4

    def test_margin_ce_degenerate_is_ce(self):
        cosines = np.clip(rng.randn(5, 7).astype(np.float32) * 0.3,
                          -1, 1)
        lab = rng.randint(0, 7, 5).astype(np.int64)
        got = float(F.margin_cross_entropy(
            paddle.to_tensor(cosines), paddle.to_tensor(lab),
            margin1=1.0, margin2=0.0, margin3=0.0, scale=10.0).numpy())
        want = float(TF.cross_entropy(torch.tensor(cosines) * 10.0,
                                      torch.tensor(lab)).numpy())
        assert abs(got - want) < 1e-4

    def test_hsigmoid_shapes_and_grad(self):
        x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32),
                             stop_gradient=False)
        w = paddle.to_tensor(rng.randn(19, 8).astype(np.float32),
                             stop_gradient=False)
        lab = paddle.to_tensor(rng.randint(0, 10, 4).astype(np.int64))
        out = F.hsigmoid_loss(x, lab, 10, w)
        assert tuple(out.shape) == (4, 1)
        out.sum().backward()
        assert np.isfinite(x.grad.numpy()).all()
        assert np.isfinite(w.grad.numpy()).all()


class TestWarpsAndMisc:
    def test_affine_grid_vs_torch(self):
        theta = rng.randn(2, 2, 3).astype(np.float32)
        for ac in (True, False):
            got = F.affine_grid(paddle.to_tensor(theta), [2, 3, 4, 5],
                                align_corners=ac).numpy()
            want = TF.affine_grid(torch.tensor(theta), (2, 3, 4, 5),
                                  align_corners=ac).numpy()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_temporal_shift(self):
        xts = np.arange(16, dtype=np.float32).reshape(4, 4, 1, 1)
        out = F.temporal_shift(paddle.to_tensor(xts), seg_num=2,
                               shift_ratio=0.25).numpy()
        v = xts.reshape(2, 2, 4, 1, 1)
        exp = v.copy()
        exp[:, :, 0] = np.concatenate(
            [np.zeros((2, 1, 1, 1)), v[:, :-1, 0]], 1)
        exp[:, :, 1] = np.concatenate(
            [v[:, 1:, 1], np.zeros((2, 1, 1, 1))], 1)
        np.testing.assert_allclose(out, exp.reshape(4, 4, 1, 1))

    def test_gather_tree(self):
        ids = np.array([[[2, 2]], [[6, 1]], [[7, 8]]], np.int64)
        parents = np.array([[[0, 0]], [[1, 1]], [[0, 0]]], np.int64)
        got = F.gather_tree(paddle.to_tensor(ids),
                            paddle.to_tensor(parents)).numpy()
        np.testing.assert_array_equal(
            got, np.array([[[2, 2]], [[6, 6]], [[7, 8]]], np.int64))

    def test_class_center_sample(self):
        paddle.seed(5)
        lab = paddle.to_tensor(np.array([3, 7, 3, 1], np.int64))
        rl, sampled = F.class_center_sample(lab, 20, 6)
        s = sampled.numpy()
        assert set([3, 7, 1]).issubset(set(s.tolist())) and len(s) == 6
        assert (s[rl.numpy()] == np.array([3, 7, 3, 1])).all()

    def test_diag_embed_vs_torch(self):
        d = rng.randn(2, 3).astype(np.float32)
        for off in (0, 1, -1):
            np.testing.assert_allclose(
                F.diag_embed(paddle.to_tensor(d), offset=off).numpy(),
                torch.diag_embed(torch.tensor(d), offset=off).numpy())

    def test_inplace_and_pad(self):
        x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32))
        F.relu_(x)
        np.testing.assert_allclose(x.numpy(), [0.0, 2.0])
        F.tanh_(x)
        np.testing.assert_allclose(x.numpy(), np.tanh([0.0, 2.0]),
                                   rtol=1e-6)
        z = F.zeropad2d(paddle.to_tensor(
            np.ones((1, 1, 2, 2), np.float32)), [1, 2, 3, 4])
        assert tuple(z.shape) == (1, 1, 9, 5)


class TestDecodeAndLayers:
    def test_beam_search_forced_sequence(self):
        import jax.numpy as jnp
        V, END = 5, 0
        seq = [3, 1, 0]

        class ToyCell:
            def __call__(self, inputs, states):
                step = int(np.asarray(states._value).ravel()[0])
                logits = np.full((inputs.shape[0], V), -5.0, np.float32)
                logits[:, seq[min(step, len(seq) - 1)]] = 5.0
                return (Tensor(jnp.asarray(logits)),
                        Tensor(states._value + 1))

        dec = nn.BeamSearchDecoder(ToyCell(), start_token=4,
                                   end_token=END, beam_size=2)
        init = Tensor(np.zeros((2, 1), np.int32))
        out, final = nn.dynamic_decode(dec, inits=init, max_step_num=10)
        ids = np.asarray(out._value)
        np.testing.assert_array_equal(ids[0, :, 0], seq)
        np.testing.assert_array_equal(ids[1, :, 0], seq)
        assert np.asarray(final.lengths._value)[:, 0].tolist() == [3, 3]

    def test_layer_wrappers(self):
        paddle.seed(0)
        x = paddle.to_tensor(np.arange(24, dtype=np.float32)
                             .reshape(2, 12))
        assert tuple(nn.Unflatten(1, [3, 4])(x).shape) == (2, 3, 4)
        img = paddle.to_tensor(rng.randn(2, 3, 4, 4).astype(np.float32))
        np.testing.assert_allclose(
            nn.Softmax2D()(img).numpy().sum(axis=1), 1.0, rtol=1e-5)
        inp = paddle.to_tensor(rng.randn(5, 4).astype(np.float32))
        lab = paddle.to_tensor(rng.randint(0, 4, 5).astype(np.int64))
        assert np.isfinite(float(nn.MultiMarginLoss()(inp, lab)
                                 .numpy()))
        hs = nn.HSigmoidLoss(8, 10)
        out = hs(paddle.to_tensor(rng.randn(4, 8).astype(np.float32)),
                 paddle.to_tensor(np.array([0, 3, 9, 5], np.int64)))
        assert tuple(out.shape) == (4, 1)
        xt = paddle.to_tensor(rng.randn(1, 2, 6, 6).astype(np.float32))
        o, m = F.max_pool2d(xt, 2, 2, return_mask=True)
        assert tuple(nn.MaxUnPool2D(2, 2)(o, m).shape) == (1, 2, 6, 6)
        assert issubclass(nn.LSTMCell, nn.RNNCellBase)

    @pytest.mark.skipif(not os.path.isdir("/root/reference"),
                        reason="the reference tree is not mounted here")
    def test_reference_all_complete(self):
        import ast
        src = open("/root/reference/python/paddle/nn/__init__.py").read()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Assign) and getattr(
                    node.targets[0], "id", "") == "__all__":
                ref = [getattr(e, "value", None) for e in node.value.elts]
        missing = [r for r in ref if r and not hasattr(nn, r)]
        assert not missing, missing


class TestReviewRegressions:
    def test_mask_path_honors_ceil_mode(self):
        x = rng.randn(1, 1, 5, 5).astype(np.float32)
        out, mask = F.max_pool2d(paddle.to_tensor(x), 2, 2,
                                 return_mask=True, ceil_mode=True)
        tout, tmask = TF.max_pool2d(torch.tensor(x), 2, 2,
                                    return_indices=True, ceil_mode=True)
        assert tuple(out.shape) == tuple(tout.shape)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(mask.numpy(), tmask.numpy())

    def test_unpool_rejects_inconsistent_output_size(self):
        x = rng.randn(1, 1, 6, 6).astype(np.float32)
        o, m = F.max_pool2d(paddle.to_tensor(x), 2, 2, return_mask=True)
        with pytest.raises(ValueError, match="inconsistent"):
            F.max_unpool2d(o, m, 2, 2, output_size=(4, 4))

    def test_fastemit_scales_emit_gradient(self):
        # value is preserved; emit-logit gradients scale by (1+lambda)
        B, T, U, V = 1, 2, 1, 3
        logits = rng.randn(B, T, U + 1, V).astype(np.float32)
        labels = np.array([[1]], np.int64)
        il = np.array([T], np.int32)
        ll = np.array([U], np.int32)

        def loss(lmbda):
            t = paddle.to_tensor(logits.copy(), stop_gradient=False)
            out = F.rnnt_loss(t, paddle.to_tensor(labels),
                              paddle.to_tensor(il),
                              paddle.to_tensor(ll), blank=0,
                              fastemit_lambda=lmbda, reduction="sum")
            out.backward()
            return float(out.numpy()), t.grad.numpy()

        v0, g0 = loss(0.0)
        v1, g1 = loss(0.5)
        assert abs(v0 - v1) < 1e-5          # value unchanged
        assert not np.allclose(g0, g1)      # gradient differs


class TestSmallShims:
    def test_lbfgs_quadratic(self):
        import jax.numpy as jnp
        from paddle_tpu.nn.parameter import Parameter
        p = Parameter(jnp.asarray([5.0, -3.0], jnp.float32))
        target = np.array([1.0, 2.0], np.float32)
        opt = paddle.optimizer.LBFGS(
            learning_rate=1.0, max_iter=20,
            line_search_fn="strong_wolfe", parameters=[p])

        def closure():
            opt.clear_grad()
            diff = p - paddle.to_tensor(target)
            loss = (diff * diff).sum()
            loss.backward()
            return loss

        loss = opt.step(closure)
        assert float(loss.numpy()) < 1e-8
        np.testing.assert_allclose(p.numpy(), target, atol=1e-4)

    def test_saved_tensors_hooks(self):
        packed, unpacked = [], []

        def pack(t):
            packed.append(tuple(t.shape))
            return np.asarray(t.numpy())

        def unpack(v):
            unpacked.append(v.shape)
            return paddle.to_tensor(v)

        x = paddle.to_tensor(np.array([2.0, 3.0], np.float32),
                             stop_gradient=False)
        with paddle.autograd.saved_tensors_hooks(pack, unpack):
            y = (x * x).sum()
        y.backward()
        np.testing.assert_allclose(x.grad.numpy(), [4.0, 6.0])
        assert packed and unpacked
        # outside the context: hooks no longer fire
        packed.clear()
        x2 = paddle.to_tensor(np.array([1.0], np.float32),
                              stop_gradient=False)
        (x2 * 2).sum().backward()
        assert not packed

    def test_amp_support_flags_and_jit_knobs(self):
        assert paddle.amp.is_bfloat16_supported() is True
        assert isinstance(paddle.amp.is_float16_supported(), bool)
        paddle.jit.set_verbosity(3)
        paddle.jit.set_code_level(100)

    def test_image_backend_and_load(self, tmp_path):
        from PIL import Image
        from paddle_tpu import vision
        arr = np.zeros((4, 4, 3), np.uint8)
        Image.fromarray(arr).save(tmp_path / "t.png")
        assert vision.get_image_backend() == "pil"
        img = vision.image_load(str(tmp_path / "t.png"))
        assert img.size == (4, 4)
        vision.set_image_backend("numpy")
        try:
            out = vision.image_load(str(tmp_path / "t.png"))
            assert out.shape == (4, 4, 3)
        finally:
            vision.set_image_backend("pil")
        with pytest.raises(ValueError):
            vision.set_image_backend("bogus")


class TestClipGradNorm:
    def test_matches_torch(self):
        import torch as _torch
        paddle.seed(0)
        w = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 3).astype(np.float32),
                             stop_gradient=False)
        (w * w * 3).sum().backward()
        g0 = w.grad.numpy().copy()
        total = nn.utils.clip_grad_norm_([w], max_norm=1.0)
        np.testing.assert_allclose(float(total.numpy()),
                                   np.linalg.norm(g0), rtol=1e-5)
        tw = _torch.tensor(np.random.RandomState(0)
                           .randn(4, 3).astype(np.float32),
                           requires_grad=True)
        (tw * tw * 3).sum().backward()
        _torch.nn.utils.clip_grad_norm_([tw], max_norm=1.0)
        np.testing.assert_allclose(w.grad.numpy(), tw.grad.numpy(),
                                   rtol=1e-4)

    def test_inf_norm(self):
        w = paddle.to_tensor(np.array([3.0, -4.0], np.float32),
                             stop_gradient=False)
        (w * w).sum().backward()
        t = nn.utils.clip_grad_norm_([w], 2.0, norm_type=float("inf"))
        assert abs(float(t.numpy()) - 8.0) < 1e-5
        np.testing.assert_allclose(np.abs(w.grad.numpy()).max(), 2.0,
                                   rtol=1e-4)
