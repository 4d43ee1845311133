"""The jamba2 cell's own parts on the CPU: a small cell of the jamba family
through run.drive() (`correct` true; false with a fault planted in the
PROGRAM — the recurrent state not carried from tick to tick, a padded
prompt allowed to advance the state — and false when the reference is told
to leave the inner norms out), the controls the limit must refuse, the
benchmark's reference against the program's tests' reference (neither
imports the other; a test may read both), and the runner's arithmetic."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_jamba as flops
from benchmark import harness
from benchmark import run as bench_run
from benchmark import weights_jamba as weights
from benchmark.correct import serve_jamba as correct
from benchmark.reference import jamba as ref
from benchmark.runners import serve_jamba as runner
from benchmark.tiny import tiny_cell

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jamba_reference as tests_ref  # noqa: E402

CELL = "jamba2-3b-serve.reason-offline"
SEED = 2 ** 31 + 3535


def _drive(capsys, cell, **kw):
    rc = bench_run.drive(cell, SEED, 1.0, False, jax.devices()[:1], **kw)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_a_small_cell_is_correct_and_fits_the_published_keys(capsys):
    cell = tiny_cell(CELL)
    arch = runner.architecture(cell["config"])
    # the published keys made to fit two layers of 64: [mamba, attention]
    assert (arch["attn_layer_period"], arch["attn_layer_offset"]) == (2, 1)
    assert ref.layer_types(arch) == ("mamba", "attention")
    assert arch["mamba_dt_rank"] == 64 and arch["num_kv_heads"] == 1
    assert arch["head_dim"] == 16 and arch["mamba_d_state"] == 16
    line, err = _drive(capsys, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["notes"]["compiles_in_window"] == 0
    assert line["notes"]["served_tokens_compared"] > 0
    assert "correct = True" in err


def test_the_engine_holds_each_kind_of_state_in_the_precision_stated():
    """`correct` cannot see the recurrent state's precision: the control
    that rounds the reference's state to bfloat16 after every step flips
    no served token on the chip (benchmark/limits/). What holds the
    program to the configuration's `precision` is this."""
    cell = tiny_cell(CELL)
    stated = harness.load_cell(CELL)["config"]["precision"]
    cell["config"]["precision"] = stated    # the tiny cell computes in f32
    router = runner.build(cell, SEED)
    try:
        cache = router.replicas[0].eng._cache
        assert {kind: str(cache[kind].dtype)
                for kind in ("k", "v", "ssm", "conv")} == {
            "k": stated["kv_cache"], "v": stated["kv_cache"],
            "ssm": stated["ssm_state"], "conv": stated["conv_state"]}
        assert stated["ssm_state"] == "float32"
    finally:
        router.close()


def _state_not_carried(monkeypatch):
    from paddle_tpu.models import jamba as program
    real = program.selective_state_update

    def broken(s, *args, live=None):
        return real(s, *args, live=live)[0], s
    monkeypatch.setattr(program, "selective_state_update", broken)


def _padding_advances(monkeypatch):
    from paddle_tpu.models import jamba as program
    real = program.selective_scan
    monkeypatch.setattr(
        program, "selective_scan",
        lambda *a, chunk: real(*a[:-1], jnp.int32(a[0].shape[0]),
                               chunk=chunk))


def _fault_cell():
    """A cell in which a fault of the state shows: 256 wide, every prompt
    12 to 15 positions short of its bucket, answers of 36 to 44 tokens.
    The real cell's limit sits between the bf16 program's readings and
    the faults' at the published widths over thousands of steps; a
    float32 run at this size reads 0 when it is right and 1e-4 .. 4e-4
    with either fault, so here the limit is 1e-5."""
    cell = tiny_cell(CELL)
    cell["config"]["model"].update(hidden_size=256, ffn_hidden=512)
    cell["traffic"]["lengths"].update(
        prompt={"median": 18, "sigma": 0.05, "min": 17, "max": 20},
        output={"median": 40, "sigma": 0.05, "min": 36, "max": 44},
        warm_prompt_lengths=[20])
    cell["limits"]["numbers"]["logit_gap_mean"]["limit"] = 1e-5
    return cell


@pytest.mark.parametrize("plant", [None, _state_not_carried,
                                   _padding_advances],
                         ids=["no_fault", "state_not_carried",
                              "padding_advances"])
def test_a_fault_planted_in_the_program_is_not_correct(plant, capsys,
                                                       monkeypatch):
    if plant is None:
        line, _ = _drive(capsys, _fault_cell())
        assert line["correct"] is True and line["failed"] == 0
        return
    plant(monkeypatch)
    jax.clear_caches()          # the faulted traces are no other test's
    try:
        line, err = _drive(capsys, _fault_cell())
    finally:
        jax.clear_caches()
    assert line["correct"] is False and line["failed"] == 0
    assert "OVER" in err
    assert line["compared"]["logit_gap_mean"][0] > 5e-5


def test_a_reference_told_to_skip_the_inner_norms_is_not_correct(capsys):
    line, err = _drive(capsys, _fault_cell(), told={"inner_norms": False})
    assert line["correct"] is False and "OVER" in err


def _small_arch(**kw):
    arch = runner.architecture(tiny_cell(CELL)["config"])
    arch.update(num_layers=4, attn_layer_period=4, attn_layer_offset=1,
                vocab_size=512, **kw)
    return arch


def test_the_controls_read_far_above_the_reference_proper():
    """The reference in the program's place with float8 operands and with
    each planted fault: each serves tokens whose gap against the reference
    proper is far from the 0 the reference itself reads. The cell's limit
    is set from these readings ON THE CHIP at the cell's own size
    (benchmark/limits/, PERF.md: fp8 6e-2, the faults 0.13 .. 0.43, over
    28 layers and thousands of decoded steps); here the model is as wide
    and the answers as long as a test run can hold, so the two faults of
    the mixer's arithmetic pass the cell's limit, and float8 operands and
    the two faults of the carried state — which grow with depth and with
    the steps a request decodes, 12 here — are held to floors of their
    own."""
    limit = harness.load_cell(CELL)["limits"]["numbers"][
        "logit_gap_mean"]["limit"]
    arch = _small_arch(hidden_size=256, ffn_hidden=512, num_heads=4,
                       head_dim=64, mamba_dt_rank=16)
    arch["vocab_size"] = 8192
    params = weights.make_params(arch, SEED)
    rng = np.random.default_rng(7)
    sample = []
    for n in (30, 19):                      # buckets of 32: 2 and 13 pads
        prompt = rng.integers(0, 8192, n).astype(np.int32)
        tokens = [0] * 12
        for j in range(12):             # the reference's own greedy tokens
            rows = correct.served_rows(params, arch, prompt, tokens[:j + 1])
            tokens[j] = int(np.asarray(rows[j]).argmax())
        sample.append({"prompt": prompt, "tokens": tokens, "max_new": 12})
    out = correct.reference_numbers(arch, SEED, sample,
                                    control="+".join(correct.CONTROLS))
    assert out["logit_gap_mean"] == 0.0 == out["logit_gap_max"]
    assert out["served_tokens_compared"] == 24
    floors = {"fp8": 2e-4, "state_not_carried": 2e-5,
              "padding_advances": 2e-5, "no_inner_norms": limit,
              "conv_shifted": limit}
    for control, floor in floors.items():
        assert out[f"control_{control}_logit_gap_mean"] > floor, control
    # a state rounded to bfloat16 flips no token in 12 steps: what it
    # costs shows over the thousands a request decodes on the chip
    assert out["control_state_bf16_logit_gap_mean"] >= 0.0
    assert 0 < out["control_state_bf16_logit_move_max"] \
        < out["control_fp8_logit_move_mean"]
    assert set(floors) | {"state_bf16"} == set(correct.CONTROLS)
    # a request cut short, or a token outside the vocabulary, is no answer
    req = sample[0]
    whole = list(req["tokens"])
    req["tokens"] = whole[:-1]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")
    req["tokens"] = whole[:-1] + [8192]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")


def test_the_two_references_agree_and_the_faults_move_the_logits():
    arch = _small_arch()
    params = weights.make_params(arch, 5, "float32")
    # scaled up so that logits are O(1): the embedding is drawn at 0.002
    params = {k: v * 30.0 if k == "wte" else v for k, v in params.items()}
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, 192),
                         jnp.int32)                 # 3 blocks of query rows
    with jax.default_matmul_precision("highest"):
        ours = ref.forward(params, tokens, arch)
    theirs = tests_ref.forward(
        params, tokens, num_heads=arch["num_heads"],
        num_kv_heads=arch["num_kv_heads"], period=arch["attn_layer_period"],
        offset=arch["attn_layer_offset"], eps=arch["layer_norm_eps"])
    assert float(jnp.abs(theirs).max()) > 0.3
    np.testing.assert_allclose(ours, theirs, atol=2e-5, rtol=0)
    rows = ref.logits_rows(params, tokens, jnp.asarray([100, 7, 191]), arch)
    np.testing.assert_allclose(rows, ours[jnp.asarray([100, 7, 191])],
                               atol=1e-6, rtol=0)
    spread = float(ours.std())
    for fault in ({"inner_norms": False}, {"conv_shift": 1},
                  {"frozen_from": 100}, {"hidden_keys": (90, 100)}):
        other = ref.forward(params, tokens, arch, **fault)
        moved = np.abs(np.asarray(other - ours)).max(axis=-1)
        assert moved.max() > 0.01 * spread, fault
        if "frozen_from" in fault or "hidden_keys" in fault:
            # nothing before the fault's first position moves, and the
            # positions right after it do
            assert moved[:90].max() < 1e-6
            assert moved[100:110].max() > 0.01 * spread, fault
    # the recurrent state rounded to bfloat16 after every step: it moves
    # the logits, and over 192 positions by less than any fault does
    other = ref.forward(params, tokens, arch, state="bfloat16")
    moved = np.abs(np.asarray(other - ours)).max()
    assert 1e-4 * spread < moved < 0.01 * spread


def test_weights_have_the_programs_leaves_in_the_stored_types():
    from paddle_tpu.models import jamba as program
    arch = _small_arch()
    cfg = runner.program_config(tiny_cell(CELL)["config"], arch)
    assert weights.shapes(arch) == program.param_shapes(cfg)
    assert set(weights.F32_LEAVES) == set(program.F32_LEAVES)
    params = weights.make_params(arch, SEED)
    assert {n for n, v in params.items() if v.dtype == jnp.float32} \
        == set(weights.F32_LEAVES)
    assert {str(v.dtype) for v in params.values()} == {"bfloat16", "float32"}
    assert {k: v.shape for k, v in params.items()} == weights.shapes(arch)
    again = weights.make_params(arch, SEED)
    other = weights.make_params(arch, SEED + 1)
    assert all((params[k] == again[k]).all() for k in params)
    assert any((params[k] != other[k]).any() for k in params)
    assert abs(float(params["norm_in"].mean()) - 1) < 0.01
    # what decides whether the state remembers: A = -(1..16) on every
    # channel, steps between 0.001 and 0.1
    np.testing.assert_allclose(np.exp(params["a_log"][0, :, 3]),
                               np.arange(1, 17), rtol=1e-6)
    steps = np.log1p(np.exp(np.asarray(params["dt_b"])))
    assert 0.00099 < steps.min() and steps.max() < 0.1001
    assert flops.n_params(arch) == sum(int(np.prod(s))
                                       for s in weights.shapes(arch).values())


def test_flops_and_bytes_against_a_case_worked_by_hand():
    a = {"vocab_size": 10, "hidden_size": 4, "num_layers": 3,
         "num_heads": 2, "ffn_hidden": 6, "max_seq_len": 8,
         "layer_norm_eps": 1e-6, "num_kv_heads": 1, "head_dim": 2,
         "attn_layer_period": 3, "attn_layer_offset": 1, "mamba_d_state": 2,
         "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 3}
    # d_inner 8; two Mamba layers (0 and 2) and one attention layer (1)
    assert weights.layers_of(a, "mamba") == 2
    # in 4*16 + x 8*(3+4) + dt 3*8 + out 8*4 = 64 + 56 + 24 + 32
    assert flops.mamba_mixer_params(a) == 176
    # q 4*4, o 4*4, k 4*2, v 4*2
    assert flops.attention_mixer_params(a) == 48
    assert flops.mlp_params(a) == 72 and flops.head_params(a) == 40
    # a Mamba layer's small leaves: conv 4*8 + 8, norms 3+2+2, dt_b 8,
    # a_log 16, d 8 = 79; every layer: two norms of 4; final norm 4
    assert flops.n_params(a) == 2 * (176 + 79) + 48 + 3 * (72 + 8) + 40 + 4
    # float32: per Mamba layer 3+2+2+8+16+8 = 39, per layer 8, final 4
    f32 = 2 * 39 + 3 * 8 + 4
    assert flops.weight_bytes(a) == 4 * f32 + 2 * (flops.n_params(a) - f32)
    # a token: Mamba 2*176 + conv 2*4*8 + scan 9*8*2 = 560; attention
    # 2*48; MLP 2*72 a layer
    assert flops.token_flops(a) == 2 * 560 + 96 + 3 * 144
    # one decoded token over 5 positions: + head 80 + 4*2*2*1 layer*5
    assert flops.decode_flops(a, 5) == 1648 + 80 + 80
    # a prompt of 3: 3 tokens, 6 causal pairs, the head once
    assert flops.prefill_flops(a, 3) == 3 * 1648 + 16 * 6 + 80
    # decoding is linear in the context, which is how the runner's loop
    # charges a whole tick at once: tokens 2..4 of a request with a
    # prompt of 7
    base = flops.decode_flops(a, 0)
    per_position = flops.decode_flops(a, 1) - base
    assert sum(flops.decode_flops(a, 7 + k) for k in range(2, 5)) \
        == 3 * base + per_position * (3 * 7 + (2 + 4) * 3 // 2)
    assert flops.kv_bytes_per_position(a) == 2 * 1 * 1 * 2 * 2
    # a slot: 2 layers x (2*8 float32 + 3 rows of 8 bf16)
    assert flops.slot_state_bytes(a) == 2 * (64 + 48)
    assert flops.tick_state_bytes(a, 5) == 2 * 5 * 224


def test_the_published_model_counts_what_the_issue_counts():
    config = harness.load_cell(CELL)["config"]
    a = runner.architecture(config)
    assert flops.n_params(a) == 3_029_337_472
    assert ref.layer_types(a) == tuple(
        "attention" if i in (7, 21) else "mamba" for i in range(28))
    assert a["head_dim"] == 128 and a["num_kv_heads"] == 1
    mamba = 41_241_792 + 62_914_560 + 5_120
    assert flops.mamba_mixer_params(a) + 25_600 + 5_120 + 81_920 + 5_120 \
        + 192 == 41_241_792 and mamba == 104_161_472
    assert flops.attention_mixer_params(a) == 13_762_560
    assert flops.slot_state_bytes(a) == 9_318_400
    assert flops.kv_bytes_per_position(a) == 1_024
    sizing = config["sizing"]
    held = flops.weight_bytes(a) + sizing["num_slots"] * (
        flops.slot_state_bytes(a)
        + sizing["max_len"] * flops.kv_bytes_per_position(a))
    assert 0.5 * 16e9 < held < 0.55 * 16e9
    # every number of the catalog's config is in the file under its key,
    # and nothing is reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == "jamba2-3b-serve")
    assert entry["reduced"] == [] == config["reduced"]
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if "AI21-Jamba2-3B" in l)
        assert entry["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if config.get(k, "absent") != v} == set()
    # the mix: every prompt bucket it reaches is warmed, and a request
    # fits the engine's positions
    from benchmark.generators.requests import draw_lengths, load_lengths
    traffic = harness.load_cell(CELL)["traffic"]
    lengths = load_lengths(traffic["lengths"])
    pairs = draw_lengths(lengths, traffic["pool"], traffic["shape_seed"])
    assert {correct.bucket(int(p)) for p in pairs[:, 0]} == {
        correct.bucket(n) for n in lengths["warm_prompt_lengths"]}
    assert pairs.sum(axis=1).max() <= lengths["max_positions"] \
        <= sizing["max_len"]
