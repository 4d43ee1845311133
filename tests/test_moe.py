"""MoE subsystem tests.

Reference analog: incubate MoE tests (test_moe_api.py style) — gate zoo,
capacity semantics, all-to-all dispatch parity, EP sharding on the virtual
8-device mesh.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.parallel.mesh import build_mesh, use_mesh, shard_value, P
from paddle_tpu.parallel.moe import (moe_ffn, topk_gating, compute_capacity,
                                     MoELayer, GATES)


def _mk_weights(E, D, F, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.1),
            jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.1),
            jnp.zeros((E, F), jnp.float32),
            jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.1),
            jnp.zeros((E, D), jnp.float32))


def _dense_reference(x, gate_w, up_w, up_b, down_w, down_b, top_k=1):
    """Numpy-style dense-masked MoE: every expert sees every token.
    top_k=1: Switch semantics — scale by the raw gate probability.
    top_k>1: GShard semantics — weights renormalized over the k chosen.
    Ground truth when capacity is unlimited."""
    B, S, D = x.shape
    xt = np.asarray(x).reshape(-1, D)
    logits = xt @ np.asarray(gate_w)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :top_k]
    y = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        denom = sum(probs[t, e] for e in order[t]) if top_k > 1 else 1.0
        for e in order[t]:
            h = jax.nn.gelu(xt[t] @ np.asarray(up_w)[e] +
                            np.asarray(up_b)[e])
            o = np.asarray(h @ np.asarray(down_w)[e] +
                           np.asarray(down_b)[e])
            y[t] += (probs[t, e] / denom) * o
    return y.reshape(B, S, D)


def test_capacity_rule():
    assert compute_capacity(64, 4, 1.0) == 16
    assert compute_capacity(64, 4, 1.25) == 20
    assert compute_capacity(8, 8, 1.0, min_capacity=4) == 4


@pytest.mark.parametrize("k", [1, 2])
def test_topk_gating_no_drop(k):
    """With capacity >= T every token is fully routed: dispatch sums to k;
    combine sums to the top-1 gate prob (switch, k=1) or to 1 after
    renormalization (gshard, k=2)."""
    rng = np.random.RandomState(0)
    T, E = 16, 4
    probs = jax.nn.softmax(jnp.asarray(rng.randn(T, E).astype(np.float32)))
    dispatch, combine, aux = topk_gating(probs, k, capacity=T)
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2))),
                               np.full(T, k), atol=1e-6)
    want = np.asarray(probs.max(-1)) if k == 1 else np.ones(T)
    np.testing.assert_allclose(np.asarray(combine.sum((1, 2))),
                               want, atol=1e-5)
    assert float(aux) > 0


def test_switch_router_gets_task_gradient():
    """Switch (k=1) must scale outputs by the raw gate prob so d(loss)/
    d(gate_w) is nonzero through the task loss alone (no aux)."""
    B, S, D, F, E = 2, 4, 8, 16, 4
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(B, S, D).astype(np.float32))
    w = _mk_weights(E, D, F)

    def loss(gate_w):
        y, _aux = moe_ffn(x, gate_w, w[1], w[2], w[3], w[4],
                          gate="switch", capacity_factor=4.0)
        return (y * y).sum()
    g = jax.grad(loss)(w[0])
    assert float(jnp.abs(g).max()) > 0


def test_gpt_moe_pipeline_aux_parity():
    """MoE aux loss circulates with the activations under pipeline
    parallelism: pipelined loss == CE(full batch) + w * mean of the
    per-microbatch aux computed by the NON-pipelined path."""
    import functools
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       shard_gpt_params, gpt_loss,
                                       _gpt_forward_impl)
    from paddle_tpu.parallel.mesh import build_mesh, use_mesh
    base = dict(vocab_size=64, hidden_size=16, num_layers=4,
                num_heads=2, ffn_hidden=32, max_seq_len=16,
                sequence_parallel=False, remat=False, num_experts=2,
                moe_gate="switch", moe_aux_weight=0.05, dtype=jnp.float32)
    cfg_nopp = GPTConfig(**base)
    cfg_pp = GPTConfig(**base, pipeline_microbatches=2)
    params = init_gpt_params(cfg_nopp, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, 64)

    # reference: CE on the full batch + w * mean over microbatches of the
    # non-pipelined per-microbatch aux (what the ring accumulates)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, _ = _gpt_forward_impl(params, inp, cfg_nopp)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ce = -float(jnp.mean(jnp.take_along_axis(
        logp, tgt[..., None].astype(jnp.int32), -1)))
    auxes = [float(_gpt_forward_impl(params, inp[i:i + 2], cfg_nopp)[1])
             for i in (0, 2)]
    want = ce + 0.05 * np.mean(auxes)

    mesh = build_mesh({"pp": 2, "ep": 2})
    with use_mesh(mesh):
        sp = shard_gpt_params(params, mesh)
        got = float(jax.jit(functools.partial(gpt_loss, cfg=cfg_pp))(
            sp, tokens))
    assert abs(got - want) < 1e-4, (got, want)
    assert np.mean(auxes) > 0          # the aux actually contributes


def test_gpt_moe_pipeline_trains():
    """num_experts>0 ∧ pp>1 trains instead of erroring: 5 steps on a fixed
    batch, loss decreases, router weights receive gradient."""
    import functools
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       shard_gpt_params, init_opt_state,
                                       train_step)
    from paddle_tpu.parallel.mesh import build_mesh, use_mesh
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=4,
                    num_heads=2, ffn_hidden=32, max_seq_len=16,
                    sequence_parallel=False, remat=True, num_experts=2,
                    moe_aux_weight=0.01, dtype=jnp.float32,
                    pipeline_microbatches=2)
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    with use_mesh(mesh):
        params = shard_gpt_params(init_gpt_params(cfg, jax.random.PRNGKey(0)),
                                  mesh)
        g0 = np.asarray(params["gate_w"])
        opt = init_opt_state(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, 64)
        step = jax.jit(functools.partial(train_step, cfg=cfg, lr=1e-2))
        losses = []
        for _ in range(5):
            loss, params, opt = step(params, opt, tokens)
            losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert not np.allclose(np.asarray(params["gate_w"]), g0)  # router moved


def test_topk_gating_capacity_drops():
    """Adversarial gates routing every token to expert 0: only `capacity`
    tokens survive."""
    T, E, C = 8, 4, 2
    probs = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (T, 1))
    dispatch, combine, _ = topk_gating(probs, 1, capacity=C)
    assert float(dispatch.sum()) == C          # 2 tokens kept
    # kept tokens are the first C (cumsum order), rest dropped
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2)))[:C], 1.0)
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2)))[C:], 0.0)


@pytest.mark.parametrize("gate,k", [("switch", 1), ("gshard", 2)])
def test_moe_ffn_parity_vs_dense(gate, k):
    """With capacity >= T the capacity-dispatch result equals the dense
    masked computation."""
    B, S, D, F, E = 2, 8, 16, 32, 4
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(B, S, D).astype(np.float32))
    w = _mk_weights(E, D, F)
    y, aux = moe_ffn(x, *w, gate=gate, capacity_factor=float(E))
    want = _dense_reference(x, *w, top_k=k)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)


def test_moe_ffn_grads_flow():
    B, S, D, F, E = 2, 4, 8, 16, 4
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(B, S, D).astype(np.float32))
    w = _mk_weights(E, D, F)

    def loss(up_w):
        y, aux = moe_ffn(x, w[0], up_w, w[2], w[3], w[4],
                         gate="switch", capacity_factor=2.0)
        return (y * y).sum() + aux
    g = jax.grad(loss)(w[1])
    assert float(jnp.abs(g).max()) > 0


def test_moe_ep_sharded_parity():
    """EP-sharded run on an 8-device mesh equals the unsharded run."""
    B, S, D, F, E = 4, 8, 16, 32, 4
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(B, S, D).astype(np.float32))
    w = _mk_weights(E, D, F)
    y0, _ = moe_ffn(x, *w, gate="switch", capacity_factor=2.0)

    mesh = build_mesh({"dp": 2, "ep": 4})
    with use_mesh(mesh):
        specs = [P(None, None), P("ep", None, None), P("ep", None),
                 P("ep", None, None), P("ep", None)]
        ws = [shard_value(v, s, mesh) for v, s in zip(w, specs)]
        xs = shard_value(x, P("dp", None, None), mesh)
        y1, _ = jax.jit(lambda x, *w: moe_ffn(
            x, *w, gate="switch", capacity_factor=2.0))(xs, *ws)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-4, atol=1e-5)


def test_moe_layer_api():
    import paddle_tpu as paddle
    layer = MoELayer(d_model=16, d_hidden=32, num_experts=4, gate="switch")
    x = paddle.to_tensor(
        np.random.RandomState(4).randn(2, 4, 16).astype(np.float32),
        stop_gradient=False)
    y = layer(x)
    assert tuple(y.shape) == (2, 4, 16)
    assert layer.aux_loss is not None
    loss = (y * y).sum()
    loss.backward()
    assert layer.parameters()[1].grad is not None


def test_moe_layer_unknown_gate_raises():
    with pytest.raises(ValueError):
        MoELayer(8, 16, 2, gate="nope")


def test_gpt_moe_uses_capacity_and_aux():
    """The flagship MoE path reads expert_capacity_factor and adds the aux
    loss (different capacity factors give different losses on adversarially
    skewed data is hard to guarantee; assert aux wiring instead)."""
    from paddle_tpu.models.gpt import GPTConfig, init_gpt_params, gpt_loss
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                    num_heads=2, ffn_hidden=32, max_seq_len=16,
                    sequence_parallel=False, remat=False,
                    num_experts=4, dtype=jnp.float32, moe_aux_weight=0.0)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 64)
    l0 = float(gpt_loss(params, tokens, cfg))
    cfg_aux = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, ffn_hidden=32, max_seq_len=16,
                        sequence_parallel=False, remat=False,
                        num_experts=4, dtype=jnp.float32,
                        moe_aux_weight=10.0)
    l1 = float(gpt_loss(params, tokens, cfg_aux))
    assert l1 > l0      # aux term present and positive
