"""The gigachat3.5 cell's own parts on the CPU: a small cell of the
gigachat3_5 family through run.drive() with the published linear-attention
head widths, latent ranks, head sizes and expert width over a 64-wide
hidden state (`correct` true; false when the reference is told a planted
fault), the controls the limit must refuse, the configuration file against
the published config, the weights' leaves against the program's, and the
runner's arithmetic."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_gigachat3_5 as flops
from benchmark import harness
from benchmark import run as bench_run
from benchmark import weights_gigachat3_5 as weights
from benchmark.correct import serve_gigachat3_5 as correct
from benchmark.readers import share_of_peak
from benchmark.reference import gigachat3_5 as ref
from benchmark.runners import serve_gigachat3_5 as runner
from benchmark.tiny import tiny_cell

CELL = "gigachat3.5-serve.longgen-offline"
SEED = 2 ** 31 + 4242

# the catalog row's `config` (model-configs/architectures.jsonl,
# GigaChat3.5-432B-A28B), every key of it
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "nextn_is_sparse": False, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 8, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True,
    "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
    "tf_legacy_loss": False,
}
CUT = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}


def _drive(capsys, cell, **kw):
    rc = bench_run.drive(cell, SEED, 1.0, False, jax.devices()[:1], **kw)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_a_small_cell_runs_the_published_widths_and_is_correct(capsys):
    cell = tiny_cell(CELL)
    arch = runner.architecture(cell["config"])
    # benchmark/tiny.py replaces the seven sizes of `model` and nothing else
    assert (arch["hidden_size"], arch["num_layers"], arch["num_heads"],
            arch["ffn_hidden"]) == (64, 2, 4, 256)
    # the linear heads follow the 4 latent heads in the published ratio,
    # each 128 x 128 as published
    assert (arch["linear_num_key_heads"], arch["linear_num_value_heads"],
            arch["linear_key_head_dim"], arch["linear_value_head_dim"],
            arch["linear_conv_kernel_dim"]) == (2, 4, 128, 128, 4)
    assert (arch["q_lora_rank"], arch["kv_lora_rank"],
            arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
            arch["v_head_dim"], arch["moe_ffn_hidden"]) \
        == (1536, 512, 128, 64, 128, 2048)
    # two layers: the leading dense linear layer and the latent one
    assert runner.kept_layers(cell["config"]) == [0, 7]
    assert arch["first_k_dense_replace"] == 1
    assert arch["full_attention_layers"] == (1,)
    assert arch["experts_held"] == 16 < arch["n_routed_experts"] == 256
    line, err = _drive(capsys, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["notes"]["compiles_in_window"] == 0
    assert line["notes"]["served_tokens_compared"] > 0
    assert "correct = True" in err


def test_a_reference_told_another_head_map_is_not_correct(capsys):
    """A planted fault refused end to end: the program's value head j
    reads key head j // 2, the reference is told j % 32."""
    line, err = _drive(capsys, tiny_cell(CELL),
                       told={"head_map": "modulo"})
    assert line["correct"] is False and line["failed"] == 0
    assert "OVER" in err


def _mid_arch(**kw):
    """As wide as a test run can hold, the published heads and widths of
    both kinds of attention kept: two layers (a dense linear one and a
    latent one with experts), 256 wide."""
    arch = runner.architecture(tiny_cell(CELL)["config"])
    arch.update({"hidden_size": 256, "vocab_size": 2048, "max_seq_len": 128,
                 **kw})
    return arch


def test_the_controls_and_the_planted_faults_move_what_correct_reads():
    """The reference in the program's place with float8 operands, with its
    delta-rule state rounded to bf16, and with each planted fault: each but
    the gate's scale (which N2 normalises away) moves the logits at the
    served positions, and the grossest already serve tokens whose gap
    passes the cell's limit at this size. The others need the cell's
    widths and thousands of positions to flip enough near-ties: their
    gaps are chip readings (benchmark/limits/)."""
    limit = harness.load_cell(CELL)["limits"]["numbers"][
        "logit_gap_mean"]["limit"]
    arch = _mid_arch()
    params = weights.make_params(arch, SEED)
    rng = np.random.default_rng(3)
    sample = []
    for n in (40, 90):
        prompt = rng.integers(0, 2048, n).astype(np.int32)
        tokens = [0] * 12
        for j in range(12):             # the reference's own greedy tokens
            rows, _ = correct.served_rows(params, arch, prompt,
                                          tokens[:j + 1])
            tokens[j] = int(np.asarray(rows[j]).argmax())
        sample.append({"prompt": prompt, "tokens": tokens, "max_new": 12})
    names = sorted(correct.CONTROLS)
    assert names == sorted([
        "fp8", "state_bf16", "no_decay", "no_beta", "qk_not_normed",
        "head_modulo", "no_gate_scale", "no_mscale", "latent_shift",
        "padding_advances"])
    out = correct.reference_numbers(arch, SEED, sample,
                                    control="+".join(names))
    assert out["logit_gap_mean"] == 0.0 == out["logit_gap_max"]
    assert out["served_tokens_compared"] == 24
    for name in names:
        move = out[f"control_{name}_logit_move_mean"]
        if name == "no_gate_scale":
            assert move < 1e-4, name
        else:
            assert move > 2e-4, name
    for name in ("head_modulo", "no_decay", "padding_advances"):
        assert out[f"control_{name}_logit_gap_mean"] > limit, name
    # no program states were handed: no state number, read as missing
    assert "state_gap" not in out
    # faults of the linear layers move the first one's state; the output
    # gate's scale, latent attention and padding after the prompt do not
    for name in ("state_bf16", "fp8", "no_decay", "no_beta",
                 "qk_not_normed", "head_modulo"):
        assert out[f"control_{name}_state_gap"] > 1e-3, name
    for name in ("no_gate_scale", "no_mscale", "latent_shift"):
        assert out[f"control_{name}_state_gap"] < 1e-6, name
    assert out["control_fp8_logit_move_mean"] \
        > 5 * out["control_state_bf16_logit_move_mean"]
    # a request cut short, or a token outside the vocabulary, is no answer
    req = sample[0]
    whole = list(req["tokens"])
    req["tokens"] = whole[:-1]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")
    req["tokens"] = whole[:-1] + [2048]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")


def test_the_state_gap_is_the_worst_head_of_the_first_linear_layer():
    """The program's delta-rule state after a prompt against the
    reference's: the reference's own reads 0 and inside the cell's limit,
    the same state rounded to bfloat16 once reads a few thousandths, a
    state rounded at every step (the `state_bf16` control) more, and
    only the FIRST linear layer's heads are read."""
    limit = harness.load_cell(CELL)["limits"]["numbers"]["state_gap"][
        "limit"]
    arch = _mid_arch()
    params = weights.make_params(arch, SEED)
    prompt = np.random.default_rng(4).integers(0, 2048, 100).astype(np.int32)
    tokens = [0, 0]
    for j in range(2):                  # the reference's own greedy tokens
        rows, _ = correct.served_rows(params, arch, prompt, tokens)
        tokens[j] = int(np.asarray(rows[j]).argmax())
    sample = [{"prompt": prompt, "tokens": tokens, "max_new": 2}]
    _, held = correct.served_rows(params, arch, prompt, tokens)
    out = correct.reference_numbers(arch, SEED, sample, states=[held],
                                    control="state_bf16")
    assert out["state_gap"] == 0.0 <= limit
    rounded = np.asarray(held).astype(jnp.bfloat16).astype(np.float32)
    once = correct.reference_numbers(arch, SEED, sample,
                                     states=[rounded])["state_gap"]
    assert 1e-4 < once < 1e-2
    assert out["control_state_bf16_state_gap"] > once
    gaps = correct.head_gaps(rounded, held)
    assert gaps.shape == (1, 4) and once == gaps[0].max()


def test_the_reference_pads_to_one_length_and_padding_changes_nothing():
    """One program a variant: every sequence padded to the model's
    positions, which changes no logit (causal), and the padded-prompt
    fault compared over the tokens that still fit."""
    arch = _mid_arch(max_seq_len=64, hidden_size=64, vocab_size=512)
    params = weights.make_params(arch, SEED, "float32")
    tokens = np.random.default_rng(5).integers(0, 512, 64).astype(np.int32)
    whole = np.asarray(ref.forward(params, jnp.asarray(tokens), arch))
    rows, states = correct.served_rows(params, arch, tokens[:30],
                                       tokens[30:40])
    np.testing.assert_allclose(np.asarray(rows), whole[29:39], atol=2e-5,
                               rtol=0)
    # the states are those after the prompt, whatever follows it
    _, alone = correct.served_rows(params, arch, tokens[:30], tokens[30:31])
    np.testing.assert_array_equal(states, alone)
    assert states.shape == (1, 4, 128, 128) and np.abs(states).max() > 0
    # a prompt of 40 padded to its bucket of 64 leaves room for one token
    fit, _ = correct.served_rows(params, arch, tokens[:40], tokens[40:50],
                                 padded=True)
    assert len(fit) == 1


def test_the_configuration_file_holds_the_published_config():
    """Every key of the catalog row's `config` under the same key, but for
    the three the cut changes; every width as published; the floors of the
    model-configs guide kept."""
    config = harness.load_cell(CELL)["config"]
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    assert config["published"] == {k: PUBLISHED[k] for k in CUT}
    assert set(config["changed_from_source"]) == set(CUT)
    assert config["reduced"] == []
    assert config["precision"]["recurrent_state"] == "float32"
    bench = harness.load_benchmark()
    entry = [c for c in bench["configs"] if c["name"] == "gigachat3.5-serve"]
    assert len(entry) == 1 and sorted(entry[0]["reduced"]) == sorted(CUT)
    assert entry[0]["source"] == config["source"].split(" ")[0]
    cells = [w for w in bench["workloads"] if w["config"] == entry[0]["name"]]
    assert [(w["name"], w["chips"]) for w in cells] == [(CELL, 1)]
    arch = runner.architecture(config)
    assert (arch["linear_num_key_heads"], arch["linear_num_value_heads"]) \
        == (32, 64)
    assert runner.kept_layers(config) == [0, 4, 5, 6, 7]
    assert arch["first_k_dense_replace"] == 1          # published layer 0
    assert arch["full_attention_layers"] == (4,)       # published layer 7
    assert arch["num_layers"] - arch["first_k_dense_replace"] >= 4
    assert arch["experts_held"] >= 8
    assert arch["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert arch["ffn_hidden"] == PUBLISHED["intermediate_size"]
    sizing = config["sizing"]
    assert sizing["max_len"] == config["model"]["max_seq_len"] == 8192
    assert sizing["num_slots"] == 128
    traffic = harness.load_cell(CELL)["traffic"]
    assert traffic["arrival"] == {"process": "backlog", "queued_per_slot": 2}
    assert traffic["pool"] == 1024 and traffic["order"] == "fixed"
    lengths = json.load(open(f"{harness.HERE}/traffic/longgen-lengths.json"))
    assert lengths["prompt"] == {"median": 1024, "sigma": 0.8, "min": 128,
                                 "max": 4096}
    assert lengths["output"] == {"median": 1024, "sigma": 0.6, "min": 128,
                                 "max": 4096}
    assert lengths["max_positions"] == sizing["max_len"]


def test_weights_have_the_programs_leaves_in_the_stored_types():
    """The benchmark's tree against the program's `param_shapes`: the same
    names and shapes, bf16 but for the float32 routing bias, A_log and
    dt_bias — and the engine's pools: the latent and the convolution rows
    in `precision.kv_cache`, the delta-rule state in
    `precision.recurrent_state`."""
    from paddle_tpu.models import gigachat3_5 as m
    cell = tiny_cell(CELL)
    config = cell["config"]
    arch = runner.architecture(config)
    cfg = runner.program_config(config, arch)
    assert weights.shapes(arch) == m.param_shapes(cfg)
    params = weights.make_params(arch, SEED, "bfloat16")
    assert {k: v.shape for k, v in params.items()} == weights.shapes(arch)
    for name, leaf in params.items():
        want = jnp.float32 if name in weights.F32_LEAVES else jnp.bfloat16
        assert leaf.dtype == want, name
        assert float(jnp.abs(leaf.astype(jnp.float32)).max()) > 0, name
    a = np.exp(np.asarray(params["dn_a_log"]))
    assert 0 < a.min() and a.max() <= 16
    dt = np.log1p(np.exp(np.asarray(params["dn_dt_bias"])))
    assert 1e-3 * 0.99 < dt.min() and dt.max() < 0.1 * 1.01
    config["precision"]["compute"] = config["precision"]["kv_cache"]
    pools = m.init_cache(runner.program_config(config, arch), 2, 8)
    assert pools["ckv"].dtype == pools["dn_conv"].dtype == jnp.dtype(
        config["precision"]["kv_cache"])
    assert pools["dn_state"].dtype == jnp.dtype(
        config["precision"]["recurrent_state"]) == jnp.float32
    assert pools["dn_state"].shape == (1, 2, 4, 128, 128)
    assert pools["dn_conv"].shape == (1, 2, 3, 2 * 2 * 128 + 4 * 128)
    assert pools["ckv"].shape == (1, 2, 8, 512)


def test_the_published_cut_counts_what_the_issue_counts():
    """The cut's arithmetic, from the configuration file: the parameters
    this chip stores, the state a slot holds, the latent a position holds,
    and the pools at 128 slots x 8,192."""
    config = harness.load_cell(CELL)["config"]
    arch = runner.architecture(config)
    assert flops.linear_params(arch) + 4 * 16384 + 2 * 64 + 128 \
        == 235_864_320
    assert flops.latent_params(arch) + flops.kv_b_params(arch) + 1536 + 512 \
        == 101_124_096 + 58_720_256
    assert flops.expert_params(arch) == 44_040_192
    assert flops.dense_mlp_params(arch) == 396_361_728
    assert flops.n_params(arch) == 4_731_722_752
    assert sum(int(np.prod(s)) for s in weights.shapes(arch).values()) \
        == flops.n_params(arch)
    assert flops.slot_state_bytes(arch) == 16_777_216 + 393_216
    assert flops.latent_bytes_per_position(arch) == 1_152
    slots, max_len = config["sizing"]["num_slots"], config["sizing"]["max_len"]
    pools = slots * (flops.slot_state_bytes(arch)
                     + max_len * flops.latent_bytes_per_position(arch))
    assert pools == 3_405_774_848
    assert flops.routed_experts_per_token(arch) == 0.5
    # a tick of 128 rows reads and writes 4.40 GB of state
    assert flops.tick_state_bytes(arch, 128) == pytest.approx(4.40e9,
                                                              rel=1e-3)


def test_flops_and_bytes_against_a_case_worked_by_hand():
    a = dict(vocab_size=100, hidden_size=8, num_layers=3,
             full_attention_layers=(2,), num_heads=2, ffn_hidden=16,
             q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4,
             qk_rope_head_dim=2, v_head_dim=4, linear_num_key_heads=1,
             linear_num_value_heads=2, linear_key_head_dim=4,
             linear_value_head_dim=4, linear_conv_kernel_dim=4,
             moe_ffn_hidden=5, first_k_dense_replace=1, n_routed_experts=8,
             experts_held=2, num_experts_per_tok=4, n_shared_experts=1)
    assert flops.kinds(a) == (2, 1, 1, 2)
    linear = 8 * (2 * 4 + 2 * 8 + 2 * 2) + 8 * 8                # 288
    latent = 8 * 6 + 6 * 2 * 6 + 8 * 6 + 2 * 2 * 4 * 8          # 296
    kv_b = 4 * 2 * 8                                            # 64
    assert flops.linear_params(a) == linear
    assert flops.latent_params(a) == latent
    assert flops.kv_b_params(a) == kv_b
    expert, dense = 3 * 8 * 5, 3 * 8 * 16                       # 120, 384
    fixed = 8 * 8 + expert                                      # 184
    assert flops.routed_experts_per_token(a) == 1.0             # 4 x 2 / 8
    rule = 2 * 4 * 16 + 6 * 2 * 4 * 4                           # 320
    assert flops.delta_rule_flops(a) == rule
    token = 2.0 * (2 * linear + latent + kv_b + dense
                   + 2 * (fixed + expert)) + 2 * rule
    assert flops.token_flops(a) == token
    head = 2.0 * 100 * 8
    # a prompt of 5: 15 causal pairs at 2 x 2 x (4 + 2 + 4)
    assert flops.prefill_flops(a, 5) == 5 * token + 40 * 15 + head
    # a tick at context 7: 2 x 2 x (4 + 4 + 2) a live position
    assert flops.decode_flops(a, 7) == token + head + 40 * 7
    assert flops.latent_bytes_per_position(a) == 6 * 2
    assert flops.slot_state_bytes(a) == 2 * (2 * 4 * 4 * 4 + 3 * 16 * 2)
    touched = 1 - (1 - 4 / 8) ** 3
    body = 2 * linear + latent + kv_b + dense + 2 * (fixed
                                                     + touched * 2 * expert)
    assert flops.tick_weight_bytes(a, 3) == (body + 800) * 2
    norms = 3 * 4 * 8 + 8
    per_linear = linear + 4 * 16 + 2 * 2 + 4
    assert flops.n_params(a) == 2 * per_linear + latent + kv_b + 6 + 4 \
        + dense + 2 * (fixed + 8 + 2 * expert) + 2 * 800 + norms


def test_the_loop_charges_a_tick_its_rows_state_and_nothing_else():
    """`state_bytes` (what `ssm_state_hbm_share.serve` reads) from the
    runner's own record: each decoded token's row of state and convolution
    rows, read and written; a prefill, the first token and an idle slot
    add nothing to it. `latent_bytes` (`latent_cache_hbm_share.serve`):
    the live latent positions those tokens attend."""
    arch = runner.architecture(harness.load_cell(CELL)["config"])
    row = 2 * (16_777_216 + 393_216)

    class Req:
        def __init__(self):
            self.tokens, self.done, self.finish_reason = [], False, None

    class Live:
        def __init__(self, prompt_len):
            self.req, self.prompt_len, self.seen = Req(), prompt_len, 0
            self.t_first = self.t_last = self.t_admit = None

    loop = runner.Loop(None, None, 128, arch)
    a, b = Live(1000), Live(3000)
    loop.live = [a, b]
    a.req.tokens = [1]                      # admitted: the first token
    loop._stamp(0.0, 0.1, True)
    assert loop.state_bytes == 0 == loop.latent_bytes
    assert loop.output_tokens == 1
    assert loop.model_flops == flops.prefill_flops(arch, 1000)
    a.req.tokens, b.req.tokens = [1, 2, 3], [4]
    loop._stamp(0.1, 0.2, True)
    assert loop.state_bytes == 2 * row
    # answer tokens 1 and 2 of a 1,000-token prompt attend 1,001 and 1,002
    assert loop.latent_bytes == (1001 + 1002) * 1152
    assert loop.output_tokens == 4
    before = loop.state_bytes, loop.latent_bytes
    loop._stamp(0.2, 0.3, True)                         # nothing moved
    assert (loop.state_bytes, loop.latent_bytes) == before
    record = {"peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 2.0,
              "chips": 1, "state_bytes": loop.state_bytes,
              "latent_bytes": loop.latent_bytes}
    assert share_of_peak.read(record, "state_bytes", "hbm_bytes_per_s") \
        == pytest.approx(100 * 2 * row / (2 * 819e9))
    assert share_of_peak.read(record, "latent_bytes", "hbm_bytes_per_s") \
        == pytest.approx(100 * 2003 * 1152 / (2 * 819e9))
    assert share_of_peak.read({**record, "state_bytes": 0.0},
                              "state_bytes", "hbm_bytes_per_s") is None
