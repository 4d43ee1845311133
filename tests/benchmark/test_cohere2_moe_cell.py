"""The command-a-plus cell's own parts on the CPU: a small cell of the
cohere2_moe family through run.drive() with a window SHORTER than its
prompts and more experts published than held (`correct` true; false when
the reference is told half the window), the faults the limit must refuse,
the benchmark's reference against the program's tests' reference (neither
imports the other; a test may read both), and the runner's arithmetic."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_cohere2_moe as flops
from benchmark import run as bench_run
from benchmark import weights_cohere2_moe as weights
from benchmark.correct import serve_cohere2_moe as correct
from benchmark.readers import program_span_counts
from benchmark.reference import cohere2_moe as ref
from benchmark.runners import serve_cohere2_moe as runner
from benchmark.tiny import tiny_cell

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import cohere2_moe_reference as tests_ref  # noqa: E402

CELL = "command-a-plus-serve.rag-offline"
SEED = 2 ** 31 + 4242
WINDOW = 8


def small_cell():
    cell = tiny_cell(CELL)
    cell["config"]["sliding_window"] = WINDOW
    return cell


def _drive(capsys, cell, **kw):
    rc = bench_run.drive(cell, SEED, 1.0, False, jax.devices()[:1], **kw)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_a_small_cell_with_a_short_window_is_correct(capsys):
    cell = small_cell()
    arch = runner.architecture(cell["config"])
    assert arch["sliding_window"] == WINDOW < 40          # prompts reach 40
    assert arch["experts_held"] == 16 < arch["num_experts"] == 128
    assert arch["num_kv_heads"] == 4 and arch["num_heads"] % 4 == 0
    assert arch["layer_types"] == ("sliding_attention",) * 2
    line, err = _drive(capsys, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["notes"]["compiles_in_window"] == 0
    assert line["notes"]["served_tokens_compared"] > 0
    assert "correct = True" in err


def test_a_reference_told_half_the_window_is_not_correct(capsys):
    line, err = _drive(capsys, small_cell(), told={"window": WINDOW // 2})
    assert line["correct"] is False and line["failed"] == 0
    assert "OVER" in err


def _small_arch(**kw):
    arch = runner.architecture(small_cell()["config"])
    arch.update(num_layers=4, layer_types=(
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention"), vocab_size=512, **kw)
    return arch


def test_the_planted_faults_read_over_the_cells_limit():
    """The reference in the program's place with float8 operands and with
    no window: each serves tokens whose gap against the reference proper
    passes the cell's limit. (The controls are read on the chip at the
    cell's own size — benchmark/limits/, PERF.md; here the model is as
    wide as a test run can hold. The third control, the other rotary
    convention, needs the cell's window of thousands of positions to
    move an argmax: with a window of 8 only the lowest frequencies turn,
    so here it is seen to move the logits, and its gap is a chip reading.)"""
    cell = small_cell()
    limit = cell["limits"]["numbers"]["logit_gap_mean"]["limit"]
    arch = _small_arch(hidden_size=512, ffn_hidden=1024, num_heads=8)
    arch["vocab_size"] = 8192
    params = weights.make_params(arch, SEED)
    rng = np.random.default_rng(7)
    sample = []
    for n in (30, 20):
        prompt = rng.integers(0, 8192, n).astype(np.int32)
        tokens = [0] * 12
        for j in range(12):             # the reference's own greedy tokens
            rows = correct.served_rows(params, arch, prompt, tokens[:j + 1])
            tokens[j] = int(np.asarray(rows[j]).argmax())
        sample.append({"prompt": prompt, "tokens": tokens, "max_new": 12})
    for control in ("fp8", "no_window"):
        out = correct.reference_numbers(arch, SEED, sample, control=control)
        assert out["logit_gap_mean"] == 0.0 == out["logit_gap_max"]
        assert out["served_tokens_compared"] == 24
        assert out["control_logit_gap_mean"] > limit, control
    req = sample[0]
    proper = correct.served_rows(params, arch, req["prompt"], req["tokens"])
    other = correct.served_rows(params, arch, req["prompt"], req["tokens"],
                                **correct.CONTROLS["split_half"])
    assert np.abs(np.asarray(proper - other)).max() > 0.01 * float(
        np.asarray(proper).std())
    # a request cut short, or a token outside the slice, is no answer
    whole = list(req["tokens"])
    req["tokens"] = whole[:-1]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")
    req["tokens"] = whole[:-1] + [8192]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")


def test_the_two_references_agree_and_the_blocks_change_nothing():
    arch = _small_arch(first_expert=8)
    params = weights.make_params(arch, 5, "float32")
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, 300),
                         jnp.int32)                 # past QUERY_ROWS: 3 blocks
    with jax.default_matmul_precision("highest"):
        ours = ref.forward(params, tokens[:256], arch)
    theirs = tests_ref.forward(
        params, tokens[:256], layer_types=arch["layer_types"],
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        window=arch["sliding_window"], theta=arch["rope_theta"],
        eps=arch["layer_norm_eps"], per_token=arch["experts_per_token"],
        first_expert=8)
    np.testing.assert_allclose(ours, theirs, atol=2e-5, rtol=0)
    rows = ref.logits_at(params, tokens[:256], 100, 7, arch)
    np.testing.assert_allclose(rows, ours[100:107], atol=1e-6, rtol=0)
    other = ref.forward(params, tokens[:256], arch, rope="split_half")
    assert np.abs(np.asarray(other - ours)).max() > 0.01 * float(ours.std())


def test_weights_have_the_programs_leaves_in_the_stored_type():
    from paddle_tpu.models import cohere2_moe as program
    arch = _small_arch()
    cfg = runner.program_config(small_cell()["config"], arch)
    assert weights.shapes(arch) == program.param_shapes(cfg)
    params = weights.make_params(arch, SEED)
    assert {str(v.dtype) for v in params.values()} == {"bfloat16"}
    assert {k: v.shape for k, v in params.items()} == weights.shapes(arch)
    again = weights.make_params(arch, SEED)
    other = weights.make_params(arch, SEED + 1)
    assert all((params[k] == again[k]).all() for k in params)
    assert any((params[k] != other[k]).any() for k in params)
    assert abs(float(params["norm"].astype(jnp.float32).mean()) - 1) < 0.01
    assert flops.n_params(arch) == sum(int(np.prod(s))
                                       for s in weights.shapes(arch).values())


def test_the_published_cut_counts_what_the_issue_counts():
    from benchmark import harness
    config = harness.load_cell(CELL)["config"]
    a = runner.architecture(config)
    assert flops.n_params(a) == 4_733_292_544
    assert flops.dense_layer_params(a) + a["hidden_size"] == 344_461_312
    assert flops.routed_experts_per_token(a) == 1.0
    assert a["layer_types"] == ("sliding_attention",) * 3 + (
        "full_attention",)
    # window layers stop at the window, the full layer does not
    assert flops.attended(a, 10_000) == (4096, 10_000)
    assert flops.kv_bytes(a, 10_000) == 4096 * (3 * 4096 + 10_000)
    short, long = flops.prefill_flops(a, 4096), flops.prefill_flops(a, 8192)
    assert 2.0 < long / short < 2.5
    # a tick of 32 tokens touches 87% of the held experts, a prompt all
    few, many = flops.tick_weight_bytes(a, 32), flops.tick_weight_bytes(a, 4096)
    assert 0.85 < (few - flops.tick_weight_bytes(a, 0)) / (
        many - flops.tick_weight_bytes(a, 0)) < 0.89
    assert many <= 2 * flops.n_params(a)
    # every number of the catalog's config is in the file under its key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if "command-a-plus-05-2026" in l)
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        entry = next(c for c in harness.load_benchmark()["configs"]
                     if c["name"] == "command-a-plus-serve")
        assert differs == set(entry["reduced"]) == set(
            config["changed_from_source"]) == set(config["published"])


def test_the_counts_reader_sums_both_sides(monkeypatch):
    class S:
        def __init__(self, name, **counts):
            self.name, self.counts = name, counts

    spans = [S("serving.decode_tick", a=3, b=1, c=8),
             S("serving.decode_tick", a=1, b=1, c=4),
             S("serving.decode_tick", active=2),        # a parent's span
             S("serving.prefill", a=100, b=100, c=1)]
    monkeypatch.setattr(program_span_counts, "traced_spans", lambda: spans)
    read = program_span_counts.read
    assert read({}, "serving.decode_tick", ["a", "b"], ["c"]) == \
        pytest.approx(50.0)
    assert read({"n": 6}, "serving.decode_tick", ["a"], ["c"],
                scale_by="n") == pytest.approx(200.0)
    assert read({}, "serving.decode_tick", ["a"], ["c"], scale_by="n") is None
    assert read({}, "serving.decode_tick", ["active"], ["slots"]) is None
    assert read({}, "serving.upload", ["a"], ["c"]) is None
