"""End-to-end fleet-API hybrid training test.

Reference analog: the collective fleet suites
(test/collective/fleet/hybrid_parallel_mp_layers.py and
dygraph_hybrid_* tests): fleet.init(strategy) → distributed_model →
distributed_optimizer → N train steps, asserting loss parity with the
single-device run on identical weights/data.
"""
import functools

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet
from paddle_tpu.parallel.mp_layers import (ColumnParallelLinear,
                                           RowParallelLinear)
from paddle_tpu.parallel.topology import get_hybrid_communicate_group


class _TPMLP(nn.Layer):
    """Column→Row parallel MLP + dense head (the reference's
    hybrid_parallel_mp_layers fixture shape)."""

    def __init__(self):
        super().__init__()
        self.col = ColumnParallelLinear(16, 32, gather_output=False)
        self.row = RowParallelLinear(32, 16, input_is_parallel=True)
        self.head = nn.Linear(16, 4)

    def forward(self, x):
        import paddle_tpu.nn.functional as F
        h = F.gelu(self.col(x))
        h = self.row(h)
        return self.head(h)


def _train(model, steps, x, y, lr=0.05, dist=False, strategy=None):
    opt = paddle.optimizer.SGD(learning_rate=lr,
                               parameters=model.parameters())
    if dist:
        model = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(opt, strategy=strategy)
    loss_fn = nn.CrossEntropyLoss()
    losses = []
    for _ in range(steps):
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


class TestFleetHybridE2E:
    def test_dp2_mp2_pp2_loss_parity_with_single_device(self):
        rng = np.random.RandomState(0)
        xb = rng.randn(8, 16).astype(np.float32)
        yb = rng.randint(0, 4, 8).astype(np.int64)
        x = paddle.to_tensor(xb)
        y = paddle.to_tensor(yb)

        # single-device reference
        paddle.seed(7)
        ref_model = _TPMLP()
        init_sd = {k: v.numpy().copy()
                   for k, v in ref_model.state_dict().items()}
        ref_losses = _train(ref_model, 4, x, y)

        # fleet hybrid path on the 8-device mesh, identical weights
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = get_hybrid_communicate_group()
        assert dict(hcg.mesh.shape)["dp"] == 2
        assert dict(hcg.mesh.shape)["mp"] == 2
        assert dict(hcg.mesh.shape)["pp"] == 2

        paddle.seed(7)
        model = _TPMLP()
        model.set_state_dict(init_sd)
        losses = _train(model, 4, paddle.to_tensor(xb),
                        paddle.to_tensor(yb), dist=True, strategy=strategy)

        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4,
                                   atol=2e-5)
        assert losses[-1] < losses[0]

        # TP params actually laid out over mp
        w = model.col.weight
        spec = w._value.sharding.spec
        assert "mp" in str(spec)

    def test_distributed_optimizer_shards_state_with_params(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        model = _TPMLP()
        dm = fleet.distributed_model(model)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=model.parameters())
        dopt = fleet.distributed_optimizer(opt, strategy=strategy)
        x = paddle.to_tensor(np.random.RandomState(1)
                             .randn(8, 16).astype(np.float32))
        loss = dm(x).sum()
        loss.backward()
        dopt.step()
        dopt.clear_grad()
        # moment buffers inherit the parameter's sharding
        w = model.col.weight
        m_state = opt._state[id(w)] if hasattr(opt, "_state") else None
        if m_state is not None:
            for v in m_state.values():
                if hasattr(v, "sharding"):
                    assert v.sharding == w._value.sharding
