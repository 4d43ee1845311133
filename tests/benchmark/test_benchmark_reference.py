"""The plain reference is checked BEFORE it judges anything on the chip
(ISSUE 27): at a tiny float32 size on the CPU it agrees with the program's
forward pass and with one train_step's loss, gradient and update — and its
lower-precision controls do not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt as ref
from benchmark.tiny import MODEL
from benchmark.weights import make_gpt_params, model_shapes

KW = dict(num_heads=MODEL["num_heads"], eps=MODEL["layer_norm_eps"])
HYPER = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1)


@pytest.fixture(scope="module")
def setup():
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=MODEL["vocab_size"],
                    hidden_size=MODEL["hidden_size"],
                    num_layers=MODEL["num_layers"],
                    num_heads=MODEL["num_heads"],
                    max_seq_len=MODEL["max_seq_len"], dtype=jnp.float32)
    params = make_gpt_params(MODEL, 2 ** 31 + 77)
    tokens = np.random.default_rng(1).integers(
        0, MODEL["vocab_size"], (4, 33)).astype(np.int32)
    return cfg, params, tokens


def test_weights_have_the_programs_leaves_and_follow_the_seed(setup):
    from paddle_tpu.models.gpt import init_gpt_params
    cfg, params, _ = setup
    theirs = jax.eval_shape(
        lambda: init_gpt_params(cfg, jax.random.PRNGKey(0)))
    assert {k: v.shape for k, v in params.items()} == \
        {k: v.shape for k, v in theirs.items()} == \
        {k: tuple(v) for k, v in model_shapes(MODEL).items()}
    assert all(v.dtype == jnp.float32 for v in params.values())
    again = make_gpt_params(MODEL, 2 ** 31 + 77)
    other = make_gpt_params(MODEL, 2 ** 31 + 78)
    assert all(np.array_equal(params[k], again[k]) for k in params)
    assert not np.array_equal(params["wte"], other["wte"])
    # biases and norm offsets are drawn, so the comparison covers them
    assert float(jnp.abs(params["qkv_b"]).max()) > 0


def test_reference_forward_agrees_with_gpt_forward(setup):
    from paddle_tpu.models.gpt import gpt_forward
    cfg, params, tokens = setup
    want = ref.forward(params, tokens[:, :-1], **KW)
    got = gpt_forward(params, tokens[:, :-1], cfg)
    assert want.dtype == jnp.float32 and want.shape == (4, 32, 640)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_reference_loss_gradient_and_update_agree_with_train_step(setup):
    from paddle_tpu.models.gpt import init_opt_state, train_step
    cfg, params, tokens = setup
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    loss, grads = jax.value_and_grad(
        lambda p: ref.loss_sum(p, tokens, **KW) / n)(params)
    got_loss, got_params, got_opt = train_step(
        params, init_opt_state(params), tokens, cfg, **HYPER)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    for name, g in grads.items():
        theirs = got_opt["m"][name] / (1.0 - HYPER["beta1"])
        assert float(jnp.abs(theirs - g).max()) <= \
            1e-5 * float(jnp.abs(g).max()) + 1e-9, name
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    want_params, _, _ = ref.adamw(params, grads, zeros, zeros, 1.0, **HYPER)
    for name in params:
        gap = abs(float(jnp.linalg.norm(got_params[name] - params[name]))
                  - float(jnp.linalg.norm(want_params[name] - params[name])))
        assert gap <= 1e-3 * float(jnp.linalg.norm(
            want_params[name] - params[name])), name


def test_blocks_of_rows_add_up_to_the_whole_batch(setup):
    _, params, tokens = setup
    whole = float(ref.loss_sum(params, tokens, **KW))
    parts = sum(float(ref.loss_sum(params, tokens[r:r + 2], **KW))
                for r in (0, 2))
    assert parts == pytest.approx(whole, rel=1e-6)


@pytest.mark.parametrize("precision,least", [("bfloat16", 1e-4),
                                             ("fp8", 3e-3)])
def test_lower_precisions_leave_the_reference(setup, precision, least):
    _, params, tokens = setup
    want = ref.forward(params, tokens[:, :-1], **KW)
    low = ref.forward(params, tokens[:, :-1], precision=precision, **KW)
    gap = float(jnp.abs(low - want).max())
    assert gap > least
    with pytest.raises(ValueError):
        ref.forward(params, tokens[:, :-1], precision="int3", **KW)
