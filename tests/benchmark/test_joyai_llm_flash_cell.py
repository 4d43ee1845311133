"""The joyai-llm-flash cell's own parts on the CPU: a small cell of the
joyai_llm_flash family through run.drive() with the published latent ranks,
head sizes and expert width over a 64-wide hidden state (`correct` true;
false when the reference is told to leave the latent un-normalised), the
controls the limit must refuse, the configuration file against the published
config, the weights' leaves against the program's, and the runner's
arithmetic."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_joyai_llm_flash as flops
from benchmark import harness
from benchmark import run as bench_run
from benchmark import weights_joyai_llm_flash as weights
from benchmark.correct import serve_joyai_llm_flash as correct
from benchmark.readers import share_of_peak
from benchmark.reference import joyai_llm_flash as ref
from benchmark.runners import serve_joyai_llm_flash as runner
from benchmark.tiny import tiny_cell

CELL = "joyai-llm-flash-serve.longdoc-offline"
SEED = 2 ** 31 + 3737

# the catalog row's `config` (model-configs/architectures.jsonl,
# JoyAI-LLM-Flash), every number of it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_size": 2048, "intermediate_size": 7168,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 129280,
    "hidden_act": "silu", "model_type": "joyai_llm_flash",
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
}


def _drive(capsys, cell, **kw):
    rc = bench_run.drive(cell, SEED, 1.0, False, jax.devices()[:1], **kw)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_a_small_cell_runs_the_published_ranks_and_is_correct(capsys):
    cell = tiny_cell(CELL)
    arch = runner.architecture(cell["config"])
    # benchmark/tiny.py replaces the seven sizes of `model` and nothing else
    assert (arch["hidden_size"], arch["num_layers"], arch["num_heads"],
            arch["ffn_hidden"]) == (64, 2, 4, 256)
    assert (arch["q_lora_rank"], arch["kv_lora_rank"],
            arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
            arch["v_head_dim"], arch["moe_ffn_hidden"]) \
        == (1536, 512, 128, 64, 128, 768)
    assert arch["first_k_dense_replace"] == 1       # one dense, one expert
    assert arch["experts_held"] == 16 < arch["n_routed_experts"] == 256
    assert arch["routed_scaling_factor"] == 2.5 and arch["norm_topk_prob"]
    line, err = _drive(capsys, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["notes"]["compiles_in_window"] == 0
    assert line["notes"]["served_tokens_compared"] > 0
    assert "correct = True" in err


def test_a_reference_told_to_skip_the_latents_norm_is_not_correct(capsys):
    """A planted fault refused end to end: the program norms the latent,
    the reference is told not to."""
    line, err = _drive(capsys, tiny_cell(CELL), told={"latent_norm": False})
    assert line["correct"] is False and line["failed"] == 0
    assert "OVER" in err


def _mid_arch(**kw):
    """As wide as a test run can hold, the published ranks and head sizes
    kept: three layers (one dense), 512 wide, 8 heads."""
    arch = runner.architecture(tiny_cell(CELL)["config"])
    arch.update(hidden_size=512, ffn_hidden=1024, num_heads=8, num_layers=3,
                vocab_size=8192, max_seq_len=128, **kw)
    return arch


def test_the_controls_and_the_planted_faults_move_what_correct_reads():
    """The reference in the program's place with float8 operands, with the
    cached latent rounded to float8, and with each planted fault: each
    moves the logits at the served positions, and the grossest (the latent
    left un-normalised) already serves tokens whose gap against the
    reference proper passes the cell's limit at this size. The others need
    the cell's 13 layers and thousands of positions to flip enough
    near-ties: their gaps are chip readings, each over the limit but the
    float8 latent (benchmark/limits/, `not_refused`)."""
    limit = harness.load_cell(CELL)["limits"]["numbers"][
        "logit_gap_mean"]["limit"]
    arch = _mid_arch()
    params = weights.make_params(arch, SEED)
    rng = np.random.default_rng(7)
    sample = []
    for n in (40, 24):
        prompt = rng.integers(0, 8192, n).astype(np.int32)
        tokens = [0] * 10
        for j in range(10):             # the reference's own greedy tokens
            rows = correct.served_rows(params, arch, prompt, tokens[:j + 1])
            tokens[j] = int(np.asarray(rows[j]).argmax())
        sample.append({"prompt": prompt, "tokens": tokens, "max_new": 10})
    names = sorted(correct.CONTROLS)
    assert names == sorted([
        "fp8", "latent_fp8", "no_selection_bias", "no_scaling",
        "split_half", "rope_on_nope", "latent_not_normed"])
    out = correct.reference_numbers(arch, SEED, sample,
                                    control="+".join(names))
    assert out["logit_gap_mean"] == 0.0 == out["logit_gap_max"]
    assert out["served_tokens_compared"] == 20
    for name in names:
        assert out[f"control_{name}_logit_move_mean"] > 2e-3, name
    assert out["control_latent_not_normed_logit_gap_mean"] > limit
    assert out["control_fp8_logit_gap_mean"] \
        > 5 * out["control_latent_fp8_logit_gap_mean"] > 0
    # a request cut short, or a token outside the vocabulary, is no answer
    req = sample[0]
    whole = list(req["tokens"])
    req["tokens"] = whole[:-1]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")
    req["tokens"] = whole[:-1] + [8192]
    assert correct.reference_numbers(arch, SEED, sample)[
        "logit_gap_mean"] == float("inf")


def test_the_references_blocks_and_padding_change_nothing():
    """Query blocks, the scan over layers and the padded length are ways of
    computing the same sums: the logits of a sequence do not move with
    them."""
    arch = runner.architecture(tiny_cell(CELL)["config"])
    arch.update(num_layers=3, vocab_size=512, max_seq_len=256)
    params = weights.make_params(arch, SEED)
    tokens = np.random.default_rng(3).integers(0, 512, 256).astype(np.int32)
    whole = np.asarray(ref.forward(params, jnp.asarray(tokens), arch))
    assert ref.QUERY_ROWS == 128                   # two blocks of rows
    head = np.asarray(ref.forward(params, jnp.asarray(tokens[:128]), arch))
    np.testing.assert_allclose(whole[:128], head, atol=2e-5, rtol=0)
    rows = correct.served_rows(params, arch, tokens[:100], tokens[100:110])
    np.testing.assert_allclose(np.asarray(rows), whole[99:109], atol=2e-5,
                               rtol=0)


def test_the_configuration_file_holds_the_published_config():
    """Every number of the catalog row's `config` under the same key, but
    for the two the cut changes; every width as published; the floors of
    the model-configs guide kept."""
    config = harness.load_cell(CELL)["config"]
    cut = {"num_hidden_layers": 13, "n_routed_experts": 16}
    for key, value in PUBLISHED.items():
        assert config[key] == cut.get(key, value), key
    assert config["published"] == {k: PUBLISHED[k] for k in cut}
    assert set(config["changed_from_source"]) == set(cut)
    bench = harness.load_benchmark()
    entry = [c for c in bench["configs"]
             if c["name"] == "joyai-llm-flash-serve"][0]
    assert sorted(entry["reduced"]) == sorted(cut)
    assert entry["source"] == config["source"].split(" ")[0]
    arch = runner.architecture(config)
    assert arch["num_layers"] - arch["first_k_dense_replace"] >= 4
    assert arch["experts_held"] >= 8
    assert arch["vocab_size"] == PUBLISHED["vocab_size"]
    assert arch["ffn_hidden"] == PUBLISHED["intermediate_size"]
    assert config["model"]["layer_norm_eps"] == PUBLISHED["rms_norm_eps"]
    sizing = config["sizing"]
    assert sizing["max_len"] == config["model"]["max_seq_len"] == 16384
    traffic = harness.load_cell(CELL)["traffic"]
    assert traffic["arrival"] == {"process": "backlog", "queued_per_slot": 2}
    assert traffic["pool"] == 256 and traffic["order"] == "fixed"
    lengths = json.load(open(f"{harness.HERE}/traffic/longdoc-lengths.json"))
    assert lengths["prompt"] == {"median": 6144, "sigma": 0.7, "min": 1024,
                                 "max": 15360}
    assert lengths["output"] == {"median": 256, "sigma": 0.7, "min": 32,
                                 "max": 1024}
    assert lengths["max_positions"] == sizing["max_len"]


def test_weights_have_the_programs_leaves_in_the_stored_types():
    """The benchmark's tree against the program's `param_shapes`: the same
    names and shapes, the multi-token-prediction module's leaves only when
    asked for (the serving cell does not load them), bf16 but for the
    float32 routing bias — and the engine's pools in `precision.kv_cache`."""
    from paddle_tpu.models import joyai_llm_flash as m
    cell = tiny_cell(CELL)
    config = cell["config"]
    arch = runner.architecture(config)
    cfg = runner.program_config(config, arch)
    assert weights.shapes(arch, mtp=True) == m.param_shapes(cfg)
    served = weights.shapes(arch)
    assert served == m.param_shapes(cfg, mtp=False)
    assert not [k for k in served if k.startswith("mtp_")]
    params = weights.make_params(arch, SEED, "bfloat16", mtp=True)
    assert {k: v.shape for k, v in params.items()} \
        == weights.shapes(arch, mtp=True)
    for name, leaf in params.items():
        want = jnp.float32 if name.endswith("router_bias") else jnp.bfloat16
        assert leaf.dtype == want, name
        assert float(jnp.abs(leaf.astype(jnp.float32)).max()) > 0, name
    bias = np.asarray(params["router_bias"])
    assert 0.5 * weights.ROUTER_BIAS_STD < bias.std() \
        < 2 * weights.ROUTER_BIAS_STD
    config["precision"]["compute"] = config["precision"]["kv_cache"]
    pools = m.init_cache(runner.program_config(config, arch), 2, 8)
    assert pools["ckv"].dtype == pools["kpe"].dtype == jnp.bfloat16
    assert pools["ckv"].shape == (2, 2, 8, 512)
    assert pools["kpe"].shape == (2, 2, 8, 64)


def test_the_published_cut_counts_what_the_issue_counts():
    """ISSUE 37's arithmetic, from the configuration file: the parameters
    this chip stores, the latent a position holds, and the pool."""
    config = harness.load_cell(CELL)["config"]
    arch = runner.architecture(config)
    assert flops.attention_params(arch) + flops.kv_b_params(arch) \
        + arch["q_lora_rank"] + arch["kv_lora_rank"] == 26_347_520
    assert flops.dense_mlp_params(arch) == 3 * 2048 * 7168
    assert flops.expert_params(arch) == 4_718_592
    assert flops.n_params(arch) == 1_885_031_424
    assert sum(int(np.prod(s)) for s in weights.shapes(arch).values()) \
        == flops.n_params(arch)
    assert flops.latent_bytes_per_position(arch) == 14_976
    slots, max_len = config["sizing"]["num_slots"], config["sizing"]["max_len"]
    assert slots * max_len * 14_976 == 7_851_737_088
    assert flops.routed_experts_per_token(arch) == 0.5
    assert config["sizing"]["num_slots"] in (32, 24, 16)


def test_flops_and_bytes_against_a_case_worked_by_hand():
    a = dict(vocab_size=100, hidden_size=8, num_layers=3, num_heads=2,
             ffn_hidden=16, q_lora_rank=6, kv_lora_rank=4,
             qk_nope_head_dim=4, qk_rope_head_dim=2, v_head_dim=4,
             moe_ffn_hidden=5, first_k_dense_replace=1, n_routed_experts=8,
             experts_held=2, num_experts_per_tok=4, n_shared_experts=1)
    attention = 8 * 6 + 6 * 2 * 6 + 8 * 6 + 2 * 4 * 8           # 232
    kv_b = 4 * 2 * 8                                            # 64
    assert flops.attention_params(a) == attention
    assert flops.kv_b_params(a) == kv_b
    expert, dense = 3 * 8 * 5, 3 * 8 * 16                       # 120, 384
    fixed = 8 * 8 + expert                                      # 184
    assert flops.expert_layer_fixed_params(a) == fixed
    assert flops.routed_experts_per_token(a) == 1.0             # 4 x 2 / 8
    token = 2.0 * (3 * (attention + kv_b) + dense + 2 * (fixed + expert))
    assert flops.token_matmul_flops(a) == token == 3760.0
    head = 2.0 * 100 * 8
    # a prompt of 5: 15 causal pairs a layer at 2 x 2 x (4 + 2 + 4)
    assert flops.prefill_flops(a, 5) == 5 * token + 3 * 40 * 15 + head
    # a tick at context 7: 2 x 2 x (4 + 4 + 2) a live position a layer
    assert flops.decode_flops(a, 7) == token + head + 3 * 40 * 7
    assert flops.latent_bytes_per_position(a) == 3 * 6 * 2
    touched = 1 - (1 - 4 / 8) ** 3
    body = 3 * (attention + kv_b) + dense + 2 * (fixed + touched * 2 * expert)
    assert flops.tick_weight_bytes(a, 3) == (body + 800) * 2
    norms = 3 * (6 + 4 + 2 * 8) + 8
    assert flops.n_params(a) == 3 * (attention + kv_b) + dense \
        + 2 * (fixed + 8 + 2 * expert) + 2 * 800 + norms


def test_the_loop_charges_a_tick_its_live_latent_and_nothing_else():
    """`latent_bytes` (what `latent_cache_hbm_share.serve` reads) from the
    runner's own record: each decoded token's context x 14,976 B; a
    prefill, the first token and an idle slot add nothing to it."""
    arch = runner.architecture(harness.load_cell(CELL)["config"])

    class Req:
        def __init__(self):
            self.tokens, self.done, self.finish_reason = [], False, None

    class Live:
        def __init__(self, prompt_len):
            self.req, self.prompt_len, self.seen = Req(), prompt_len, 0
            self.t_first = self.t_last = self.t_admit = None

    loop = runner.Loop(None, None, 32, arch)
    a, b = Live(1000), Live(5000)
    loop.live = [a, b]
    a.req.tokens = [1]                      # admitted: the first token
    loop._stamp(0.0, 0.1, True)
    assert loop.latent_bytes == 0 and loop.output_tokens == 1
    assert loop.model_flops == flops.prefill_flops(arch, 1000)
    a.req.tokens, b.req.tokens = [1, 2, 3], [4]
    loop._stamp(0.1, 0.2, True)
    # a decoded tokens 1 and 2 at contexts 1001, 1002; b only admitted
    assert loop.latent_bytes == (1001 + 1002) * 14_976
    assert loop.output_tokens == 4
    before = loop.latent_bytes
    loop._stamp(0.2, 0.3, True)                         # nothing moved
    assert loop.latent_bytes == before
    record = {"peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 2.0,
              "chips": 1, "latent_bytes": loop.latent_bytes}
    assert share_of_peak.read(record, "latent_bytes", "hbm_bytes_per_s") \
        == pytest.approx(100 * 2003 * 14_976 / (2 * 819e9))
    assert share_of_peak.read({**record, "latent_bytes": 0.0},
                              "latent_bytes", "hbm_bytes_per_s") is None
