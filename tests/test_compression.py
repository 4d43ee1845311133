"""Gradient-compression collectives (parallel/compression.py; reference
fleet/meta_optimizers/{dgc,localsgd,fp16_allreduce}_optimizer.py): wire-
dtype reduction, DGC top-k with error feedback, and local-SGD parameter
averaging — all inside shard_map on the 8-device mesh."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.compression import (
    compressed_psum, dgc_compress, dgc_decompress, dgc_psum,
    local_sgd_sync)
from paddle_tpu.parallel.mesh import build_mesh
from jax import shard_map


def _mesh8():
    return build_mesh({"dp": 8})


class TestCompressedPsum:
    def test_matches_f32_psum_within_bf16_tolerance(self):
        mesh = _mesh8()
        x = jnp.asarray(np.random.RandomState(0).randn(8, 64),
                        jnp.float32)

        def body(xs):
            return compressed_psum(xs[0], "dp")

        got = shard_map(body, mesh=mesh, in_specs=P("dp"),
                            out_specs=P())(x)
        want = x.sum(0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
        assert got.dtype == jnp.float32      # upcast back

    def test_wire_dtype_is_configurable(self):
        mesh = _mesh8()
        x = jnp.ones((8, 4), jnp.float32)
        got = shard_map(
            lambda xs: compressed_psum(xs[0], "dp",
                                       wire_dtype=jnp.float16),
            mesh=mesh, in_specs=P("dp"), out_specs=P())(x)
        np.testing.assert_allclose(np.asarray(got), 8.0)


class TestDGC:
    def test_error_feedback_preserves_all_signal(self):
        """Over many steps, sum(decompressed sends) + final residual ==
        sum(grads) exactly — compression delays signal, never drops it
        (the DGC invariant)."""
        rng = np.random.RandomState(1)
        shape = (10, 10)
        residual = jnp.zeros(shape, jnp.float32)
        total_sent = jnp.zeros(shape, jnp.float32)
        total_grad = np.zeros(shape, np.float32)
        for _ in range(20):
            g = rng.randn(*shape).astype(np.float32)
            total_grad += g
            sent, idx, residual = dgc_compress(jnp.asarray(g), residual,
                                               k_frac=0.05)
            assert sent.shape[0] == 5        # ceil(100 * 0.05)
            total_sent = total_sent + dgc_decompress(sent, idx, shape)
        np.testing.assert_allclose(
            np.asarray(total_sent + residual), total_grad, atol=1e-4)

    def test_topk_sends_largest_magnitudes(self):
        g = jnp.asarray(
            np.array([[0.1, -5.0, 0.2], [3.0, -0.1, 0.05]], np.float32))
        sent, idx, residual = dgc_compress(
            g, jnp.zeros_like(g), k_frac=2 / 6)
        assert set(np.asarray(idx).tolist()) == {1, 3}   # -5.0 and 3.0
        # the sent entries are zeroed in the residual, the rest kept
        r = np.asarray(residual)
        assert r[0, 1] == 0.0 and r[1, 0] == 0.0 and r[0, 2] != 0.0

    def test_bad_k_frac_rejected(self):
        with pytest.raises(ValueError, match="k_frac"):
            dgc_compress(jnp.ones((4,)), jnp.zeros((4,)), k_frac=0.0)

    def test_dgc_psum_sums_members_topk(self):
        mesh = _mesh8()
        rng = np.random.RandomState(2)
        g = jnp.asarray(rng.randn(8, 16), jnp.float32)
        r0 = jnp.zeros((8, 16), jnp.float32)

        def body(gs, rs):
            out, new_r = dgc_psum(gs[0], rs[0], "dp", k_frac=0.25)
            return out, new_r[None]

        out, new_r = shard_map(
            body, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P(), P("dp")))(g, r0)
        # oracle: per-member top-4 of |g|, summed
        want = np.zeros(16, np.float32)
        for m in range(8):
            row = np.asarray(g[m])
            keep = np.argsort(-np.abs(row))[:4]
            want[keep] += row[keep]
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
        # residuals carry exactly the unsent mass
        np.testing.assert_allclose(
            np.asarray(new_r).sum(0) + want, np.asarray(g).sum(0),
            atol=1e-5)


class TestLocalSGD:
    def test_sync_averages_across_replicas(self):
        mesh = _mesh8()
        p = jnp.asarray(np.arange(8, dtype=np.float32)[:, None]
                        * np.ones((8, 3), np.float32))

        def body(ps):
            return local_sgd_sync({"w": ps[0]}, "dp")["w"][None]

        out = shard_map(body, mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp"))(p)
        np.testing.assert_allclose(np.asarray(out),
                                   np.full((8, 3), 3.5), atol=1e-6)

    def test_local_steps_plus_sync_trains(self):
        """Per-replica local SGD with periodic averaging reduces a
        shared quadratic loss (the localsgd training pattern)."""
        mesh = _mesh8()
        rng = np.random.RandomState(3)
        target = jnp.asarray(rng.randn(4), jnp.float32)
        # each replica sees a noisy target; start replicas apart
        noisy = jnp.asarray(target[None] + 0.1 * rng.randn(8, 4),
                            jnp.float32)
        w0 = jnp.asarray(rng.randn(8, 4), jnp.float32)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("dp"), P("dp")),
                           out_specs=P("dp"))
        def run(w, tgt):
            w, tgt = w[0], tgt[0]

            def local(w, _):
                g = 2.0 * (w - tgt)
                return w - 0.1 * g, None

            for _ in range(3):               # 3 rounds of (4 local + sync)
                w, _ = jax.lax.scan(local, w, None, length=4)
                # pmean replicates (vma-invariant); the next scan's carry
                # must be device-varying again. Old jax has no vma typing
                # (and no pcast) — the replicated value carries directly.
                w = local_sgd_sync({"w": w}, "dp")["w"]
                if hasattr(jax.lax, "pcast"):
                    w = jax.lax.pcast(w, "dp", to="varying")
            return w[None]

        w = run(w0, noisy)
        # all replicas equal after the final sync, and near the mean target
        np.testing.assert_allclose(np.asarray(w[0]), np.asarray(w[7]),
                                   atol=1e-6)
        assert float(jnp.mean((w[0] - jnp.mean(noisy, 0)) ** 2)) < 0.01


class TestMultisliceGradSync:
    """fleet.multislice_grad_sync: the strategy-driven entry over the
    compression primitives (reference meta-optimizer toggles applied at
    the explicit cross-slice reduction)."""

    def _run(self, strategy):
        from paddle_tpu.parallel.fleet import multislice_grad_sync
        mesh = build_mesh({"slice": 8})
        rng = np.random.RandomState(5)
        g = jnp.asarray(rng.randn(8, 12), jnp.float32)

        def body(gs):
            synced, res = multislice_grad_sync(
                {"w": gs[0]}, axis_name="slice", strategy=strategy)
            return synced["w"]

        return g, shard_map(body, mesh=mesh, in_specs=P("slice"),
                                out_specs=P())(g)

    def test_default_is_exact_psum(self):
        class S:  # bare strategy: no toggles
            pass
        g, out = self._run(S())
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(g).sum(0), atol=1e-5)

    def test_fp16_allreduce_mode(self):
        class S:
            fp16_allreduce = True
        g, out = self._run(S())
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(g).sum(0), rtol=2e-2,
                                   atol=2e-2)

    def test_dgc_mode_threads_residuals(self):
        from paddle_tpu.parallel.fleet import multislice_grad_sync
        mesh = build_mesh({"slice": 8})
        rng = np.random.RandomState(6)
        g = jnp.asarray(rng.randn(8, 12), jnp.float32)

        class S:
            dgc = True
            dgc_configs = {"sparsity": [0.75]}   # keep 25% -> k=3

        def body(gs):
            synced, res = multislice_grad_sync(
                {"w": gs[0]}, axis_name="slice", strategy=S())
            return synced["w"], res["w"][None]

        out, res = shard_map(
            body, mesh=mesh, in_specs=P("slice"),
            out_specs=(P(), P("slice")))(g)
        # per-member top-3 summed; residual carries the rest
        want = np.zeros(12, np.float32)
        for m in range(8):
            row = np.asarray(g[m])
            keep = np.argsort(-np.abs(row))[:3]
            want[keep] += row[keep]
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(res).sum(0) + want, np.asarray(g).sum(0),
            atol=1e-5)

    def test_dgc_tuple_grads_pytree_not_corrupted(self, monkeypatch):
        """Regression (round-5 advisor): a TUPLE-valued grads pytree —
        the shape jax.grad(..., argnums=(0, 1)) returns — must unzip
        STRUCTURALLY. The old is_leaf=isinstance(x, tuple) sniff treated
        the outer container tuple as one (synced, residual) pair and
        silently returned leaf A's residual as leaf B's gradient
        (shapes matched, so training corrupted with no error). dgc_psum
        is stubbed with a per-leaf marker transform so the unzip is
        isolated from the collective (and from jax-version drift in the
        axis primitives)."""
        from paddle_tpu.parallel import compression
        from paddle_tpu.parallel.fleet import multislice_grad_sync
        monkeypatch.setattr(
            compression, "dgc_psum",
            lambda g, r, axis_name, k_frac: (g * 2.0, g + 100.0))
        rng = np.random.RandomState(7)
        ga = jnp.asarray(rng.randn(4, 3), jnp.float32)
        gb = jnp.asarray(rng.randn(4, 3), jnp.float32)   # same shape: the
        # old bug produced a same-shaped WRONG answer, not a crash

        class S:
            dgc = True
            dgc_configs = {"sparsity": [0.75]}

        synced, res = multislice_grad_sync((ga, gb), axis_name="slice",
                                           strategy=S())
        assert isinstance(synced, tuple) and len(synced) == 2
        assert isinstance(res, tuple) and len(res) == 2
        # each leaf's synced grad is ITS OWN transform (the old sniff
        # returned (2*ga, ga+100) as the whole synced tree)...
        np.testing.assert_allclose(np.asarray(synced[0]),
                                   np.asarray(ga) * 2.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(synced[1]),
                                   np.asarray(gb) * 2.0, atol=1e-6)
        # ...and each residual is its own leaf's error-feedback state
        np.testing.assert_allclose(np.asarray(res[0]),
                                   np.asarray(ga) + 100.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(res[1]),
                                   np.asarray(gb) + 100.0, atol=1e-6)
