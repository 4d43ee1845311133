"""Auto-parallel markup API tests.

Reference test style (SURVEY §4): graph/sharding-transform tests that
build → inspect shardings without real multi-chip hardware (8-device
virtual CPU mesh), plus an Engine end-to-end fit.
Reference: auto_parallel/process_mesh.py:71, interface.py:28,117,
static/engine.py:55,854.
"""
import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.parallel.auto_parallel import (ProcessMesh, shard_tensor,
                                               shard_op, Engine, Strategy,
                                               create_mesh)
from paddle_tpu.parallel.mesh import use_mesh


class TestProcessMesh:
    def test_build_from_nested_ids(self):
        pm = ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                         dim_names=["dp", "mp"])
        assert pm.shape == [2, 4]
        assert pm.dim_names == ["dp", "mp"]
        assert pm.process_ids == list(range(8))
        assert pm.get_dim_size("mp") == 4
        m = pm.mesh
        assert dict(m.shape) == {"dp": 2, "mp": 4}

    def test_build_from_shape(self):
        pm = ProcessMesh(shape=[4, 2], dim_names=["x", "y"])
        assert pm.mesh.shape["x"] == 4

    def test_rank_mismatch_raises(self):
        with pytest.raises(ValueError, match="rank"):
            ProcessMesh([[0, 1]], dim_names=["a", "b", "c"])

    def test_unknown_device_id_raises(self):
        pm = ProcessMesh([[100, 101]], dim_names=["a", "b"])
        with pytest.raises(ValueError, match="device id"):
            _ = pm.mesh

    def test_context_manager_sets_mesh(self):
        from paddle_tpu.parallel.mesh import get_mesh
        pm = ProcessMesh(shape=[8], dim_names=["dp"])
        with pm:
            assert get_mesh() is pm.mesh
        assert get_mesh() is not pm.mesh


class TestShardTensor:
    def test_eager_reshard_lays_out(self):
        pm = ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
        x = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(8, 4))
        out = shard_tensor(x, pm, ["dp", None])
        assert out is x                       # in-place relayout
        sh = x._value.sharding
        assert sh.spec == P("dp", None)
        assert len(x._value.addressable_shards) == 8
        # value unchanged by relayout
        np.testing.assert_array_equal(
            x.numpy(), np.arange(32, dtype=np.float32).reshape(8, 4))

    def test_spec_shorter_than_rank_pads(self):
        pm = ProcessMesh(shape=[8], dim_names=["mp"])
        x = paddle.to_tensor(np.zeros((8, 2, 2), np.float32))
        shard_tensor(x, pm, ["mp"])
        assert x._value.sharding.spec == P("mp", None, None)

    def test_constraint_under_trace(self):
        """Traced: markup becomes a with_sharding_constraint in the graph
        (the Resharder-inside-the-graph form)."""
        pm = ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])

        def f(v):
            return shard_tensor(v * 2.0, pm, ["dp", "mp"])

        with use_mesh(pm.mesh):
            lowered = jax.jit(f).lower(
                jax.ShapeDtypeStruct((8, 8), np.float32))
        txt = lowered.as_text()
        assert "sharding" in txt              # constraint made it into HLO

    def test_markup_recorded_on_tensor(self):
        pm = ProcessMesh(shape=[8], dim_names=["mp"])
        x = paddle.to_tensor(np.zeros((8, 8), np.float32))
        shard_tensor(x, pm, [None, "mp"])
        assert x.sharding_spec == P(None, "mp")


class TestShardOp:
    def test_wraps_and_constrains(self):
        pm = ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])

        def matmul(a, b):
            return paddle.tensor.matmul(a, b)

        sharded_mm = shard_op(matmul, pm,
                              in_shard_specs=[["dp", None], [None, "mp"]],
                              out_shard_specs=[["dp", "mp"]])
        a = paddle.to_tensor(np.ones((8, 16), np.float32))
        b = paddle.to_tensor(np.ones((16, 8), np.float32))
        out = sharded_mm(a, b)
        np.testing.assert_allclose(out.numpy(), np.full((8, 8), 16.0))
        assert out._value.sharding.spec == P("dp", "mp")


class _XorDataset:
    """Tiny learnable dataset for Engine.fit."""

    def __init__(self, n=256):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, 8).astype(np.float32)
        w = rng.randn(8, 3).astype(np.float32)
        self.y = np.argmax(self.x @ w, -1).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class TestEngine:
    def test_fit_evaluate_predict(self):
        import paddle_tpu.nn as nn
        model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 3))
        loss = nn.CrossEntropyLoss()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=model.parameters())
        from paddle_tpu.metric import Accuracy
        engine = Engine(model, loss, opt, metrics=[Accuracy()],
                        strategy=Strategy(mesh_axes={"dp": 8}))
        ds = _XorDataset()
        hist = engine.fit(ds, epochs=2, batch_size=32)
        assert len(hist["loss"]) == 2
        assert hist["loss"][1] < hist["loss"][0]          # it learns
        ev = engine.evaluate(ds, batch_size=32)
        assert ev["acc"] > 0.5
        preds = engine.predict(ds, batch_size=32, steps=2)
        assert len(preds) == 2 and preds[0].shape == (32, 3)

    def test_prepare_shards_marked_params(self):
        import paddle_tpu.nn as nn
        model = nn.Linear(16, 8)
        w = model.parameters()[0]
        w.sharding_spec = P(None, "mp")
        engine = Engine(model,
                        strategy=Strategy(mesh_axes={"dp": 2, "mp": 4}))
        engine.prepare()
        assert w._value.sharding.spec == P(None, "mp")
        b = model.parameters()[1]
        assert b._value.sharding.spec == P()              # replicated

    def test_save_load_roundtrip(self, tmp_path):
        import paddle_tpu.nn as nn
        model = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        engine = Engine(model, nn.MSELoss(), opt,
                        strategy=Strategy(mesh_axes={"dp": 8}))
        engine.prepare()
        w0 = model.parameters()[0].numpy().copy()
        engine.save(str(tmp_path / "m"))
        model.parameters()[0].set_value(np.zeros_like(w0))
        engine.load(str(tmp_path / "m"))
        np.testing.assert_array_equal(model.parameters()[0].numpy(), w0)


class TestEngineGPT:
    def test_engine_fit_gpt_on_hybrid_mesh(self):
        """Engine.fit drives the flagship GPT under dp2×pp2×mp2 markup
        (the acceptance case: Engine on the GPT dryrun config)."""
        import jax.numpy as jnp
        from paddle_tpu.models.gpt import GPTConfig, GPTModel
        from paddle_tpu.parallel.mesh import build_mesh, use_mesh
        mesh = build_mesh({"dp": 2, "pp": 2, "mp": 2})
        with use_mesh(mesh):
            cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=2, ffn_hidden=64, max_seq_len=16,
                            sequence_parallel=False, remat=False,
                            dtype=jnp.float32)
            model = GPTModel(cfg, seed=0)
            import paddle_tpu.nn as nn
            opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=model.parameters())

            def lm_loss(logits, labels):
                return nn.functional.cross_entropy(
                    logits.reshape([-1, cfg.vocab_size]),
                    labels.reshape([-1]))

            engine = Engine(model, lm_loss, opt)

            rng = np.random.RandomState(0)
            toks = rng.randint(0, 64, (16, 17)).astype(np.int64)

            class TokDS:
                def __len__(self):
                    return 16

                def __getitem__(self, i):
                    return toks[i, :-1], toks[i, 1:]

            hist = engine.fit(TokDS(), epochs=2, batch_size=4)
        assert len(hist["loss"]) == 2
        assert np.isfinite(hist["loss"]).all()
        assert hist["loss"][1] < hist["loss"][0]
        # params kept their markup sharding through training
        w = model._params["qkv_w"]
        assert w._value.sharding.spec is not None


class TestMetrics:
    def test_accuracy_topk(self):
        from paddle_tpu.metric import Accuracy
        m = Accuracy(topk=(1, 2))
        pred = np.array([[0.1, 0.7, 0.2], [0.8, 0.1, 0.1]], np.float32)
        lab = np.array([2, 0])
        m.update(m.compute(paddle.to_tensor(pred), paddle.to_tensor(lab)))
        top1, top2 = m.accumulate()
        assert top1 == 0.5 and top2 == 1.0

    def test_precision_recall_auc(self):
        from paddle_tpu.metric import Precision, Recall, Auc
        preds = np.array([0.9, 0.8, 0.2, 0.6], np.float32)
        labels = np.array([1, 0, 0, 1], np.float32)
        p, r, a = Precision(), Recall(), Auc()
        for m in (p, r, a):
            m.update(preds, labels)
        assert p.accumulate() == pytest.approx(2 / 3)
        assert r.accumulate() == 1.0
        assert a.accumulate() > 0.5

    def test_namespace(self):
        assert hasattr(paddle.metric, "Accuracy")
        assert hasattr(paddle.distributed, "shard_tensor")
        assert hasattr(paddle.distributed.fleet, "auto")
