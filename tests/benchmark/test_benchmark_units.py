"""The benchmark's own arithmetic, generators, clock, reduction and names
(ISSUE 27): everything here is a pure function of its inputs, checked on
the CPU against cases worked by hand. No test describes a TPU topology."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops, harness
from benchmark.generators import requests, token_batches
from benchmark.trace import reduce as trace_reduce

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------------ names
def _all_named():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_all_named()),
                         ids=lambda v: v if isinstance(v, str) else v["name"])
def test_names_and_units_use_allowed_characters(group, entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves", "layer"):
        if key in entry and key != "layer":
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_name_of_a_cell_resolves_to_its_files(cell):
    loaded = harness.load_cell(cell)
    assert loaded["config"]["reduced"] == []
    assert len(loaded["config"]["source"]) <= 200
    harness.load_runner(loaded["config"])
    harness.load_generator(loaded["traffic"])
    assert loaded["limits"]["numbers"]
    names = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"]
    for metric in loaded["end_to_end"] + loaded["per_layer"]:
        read, params = harness.load_reader(metric["name"])
        assert callable(read) and isinstance(params, dict)
    # each per-layer metric moves an end-to-end metric this cell reports
    for metric in loaded["per_layer"]:
        assert metric["moves"] in names


def test_benchmark_json_is_what_the_contract_asks():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    for cfg in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
        assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])


# ------------------------------------------------------------------ peaks
def test_peaks_known_device_has_a_source_and_unknown_raises():
    row = harness.load_peaks("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(harness.BenchmarkError, match="no published peaks"):
        harness.load_peaks("TPU v9 imaginary")


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


# ------------------------------------------------------------- arithmetic
M = {"hidden_size": 4, "ffn_hidden": 16, "num_layers": 2, "vocab_size": 10,
     "max_seq_len": 8, "num_heads": 2}


def test_flops_and_bytes_against_a_hand_worked_case():
    # per layer: qkv 3*4*4=48, out 16, mlp 2*4*16=128 -> 192; two layers 384
    assert flops.body_matmul_params(M) == 384
    assert flops.head_params(M) == 40
    # embeddings 40 + 32, final norm 8, per layer 192 + biases (12+4+16+4)
    # + norms 16 = 244
    assert flops.n_params(M) == 40 + 32 + 8 + 2 * 244
    assert flops.train_flops_per_token(M, 8) == 6 * 568 + 12 * 2 * 4 * 8
    # prefill of 3: body 2*384*3, attention 4*2*4*(1+2+3), head 2*40 once
    assert flops.prefill_flops(M, 3) == 2304 + 192 + 80
    # one decoded token over 5 positions
    assert flops.decode_flops(M, 5) == 2 * (384 + 40) + 4 * 2 * 4 * 5
    assert flops.kv_bytes_per_position(M) == 2 * 2 * 4 * 2
    assert flops.tick_weight_bytes(M) == (384 + 40) * 2
    real = harness.load_cell("gpt3-1.3b-serve.offline")["config"]["model"]
    assert flops.kv_bytes_per_position(real) == 196608
    assert flops.n_params(real) == 1313722368


def test_mfu_and_hbm_readers_against_a_hand_worked_case():
    from benchmark.readers import share_of_peak, train_step_mfu
    peaks = {"flops_bf16": 1000.0, "hbm_bytes_per_s": 100.0}
    rec = {"peaks": peaks, "model": M, "seq_len": 8, "tokens": 16,
           "window_s": 64.0, "chips": 2}
    # 6 * 568 + 12 * 2 * 4 * 8 = 4176 flops a token; 16 tokens / 64 s of
    # them = 1044 flop/s of 2 chips x 1000
    assert train_step_mfu.read(rec) == pytest.approx(52.2)
    rec = {"peaks": peaks, "window_s": 4.0, "chips": 1,
           "model_flops": 1000.0, "model_bytes": 100.0}
    assert share_of_peak.read(rec, "model_flops", "flops_bf16") == \
        pytest.approx(25.0)
    assert share_of_peak.read(rec, "model_bytes", "hbm_bytes_per_s") == \
        pytest.approx(25.0)
    # nothing to read is nothing, never 0
    assert share_of_peak.read({"window_s": 1.0, "chips": 1}, "model_flops",
                              "flops_bf16") is None
    assert train_step_mfu.read({"peaks": None, "tokens": 5}) is None


def test_percentile_interpolates_between_order_statistics():
    assert harness.percentile([], 95) is None
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert harness.percentile([0, 10], 95) == pytest.approx(9.5)


# ------------------------------------------------------------- generators
LENGTHS = requests.load_lengths("chat-lengths")
SERVE = harness.load_cell("gpt3-1.3b-serve.chat")


@pytest.mark.parametrize("mix", ["offline", "chat"])
def test_request_stream_is_a_pure_function_of_the_seed(mix):
    cell = harness.load_cell("gpt3-1.3b-serve." + mix)

    def draw(seed, n=40):
        s = requests.make(cell["traffic"], cell["config"], seed, 20.0)
        n = n if s.backlog else min(n, s.n_open)
        return [s.next() for _ in range(n)]

    a, b, c = draw(2 ** 31 + 5), draw(2 ** 31 + 5), draw(7)
    for (d1, p1, n1), (d2, p2, n2) in zip(a, b):
        assert d1 == d2 and n1 == n2 and np.array_equal(p1, p2)
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, c))
    lo, hi = LENGTHS["prompt"]["min"], LENGTHS["prompt"]["max"]
    for _due, prompt, max_new in a + c:
        assert lo <= len(prompt) <= hi and prompt.dtype == np.int32
        assert LENGTHS["output"]["min"] <= max_new <= LENGTHS["output"]["max"]
        assert len(prompt) + max_new <= 1024
        assert 0 <= prompt.min() and prompt.max() < 50304


def _all_of(traffic, seed, seconds=30.0):
    s = requests.make(traffic, SERVE["config"], seed, seconds)
    rows = [s.next() for _ in range(s.n_open)]
    assert s.exhausted()
    return rows


def test_a_seeded_order_offers_the_same_work_in_another_order():
    traffic = dict(SERVE["traffic"], order="seeded")
    a, b = _all_of(traffic, 11), _all_of(traffic, 2 ** 31 + 99)
    assert len(a) == len(b) == round(
        traffic["arrival"]["rate_per_s"] * 30.0)
    assert sorted((len(p), n) for _, p, n in a) == \
        sorted((len(p), n) for _, p, n in b)
    assert [(len(p), n) for _, p, n in a] != [(len(p), n) for _, p, n in b]
    # the arrivals are one multiset of gaps (from the file's shape_seed,
    # scaled to fill the window) in another order: all but the first gap
    # of each order show as differences of the due times
    full = requests.draw_gaps(traffic["arrival"], len(a),
                              traffic["shape_seed"])
    full = np.sort(full * 30.0 / full.sum())
    for rows in (a, b):
        seen = np.sort(np.diff([d for d, _, _ in rows]))
        at = np.searchsorted(full, seen)
        nearest = full[np.clip(at, 0, len(full) - 1)]
        before = full[np.clip(at - 1, 0, len(full) - 1)]
        assert np.minimum(abs(nearest - seen), abs(before - seen)).max() < 1e-9
    assert a[0][0] == 0.0 and all(0 <= d < 30.0 for d, _, _ in a)


def test_the_chat_mix_keeps_one_schedule_for_every_seed():
    assert SERVE["traffic"]["order"] == "fixed"
    a = _all_of(SERVE["traffic"], 11)
    b = _all_of(SERVE["traffic"], 2 ** 31 + 99)
    assert [(d, len(p), n) for d, p, n in a] == \
        [(d, len(p), n) for d, p, n in b]
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, b))


def test_lengths_clip_the_output_to_the_models_positions():
    tight = dict(LENGTHS, max_positions=720)
    pairs = requests.draw_lengths(tight, 4000, 1)
    assert (pairs.sum(axis=1) <= 720).all() and (pairs[:, 1] >= 1).all()
    assert (pairs[:, 0] >= 16).all() and (pairs[:, 0] <= 704).all()
    # medians about three to one, heavy-tailed
    assert 150 <= np.median(pairs[:, 0]) <= 240


def test_training_batches_are_seeded_fresh_and_in_range():
    cell = harness.load_cell("gpt3-350m-train.steady")
    a = token_batches.make(cell["traffic"], cell["config"], 2 ** 31 + 3, 1.0)
    b = token_batches.make(cell["traffic"], cell["config"], 2 ** 31 + 3, 1.0)
    first, second = a.next_batch(), a.next_batch()
    assert first.shape == (8, 1025) and first.dtype == np.int32
    assert np.array_equal(first, b.next_batch())
    assert not np.array_equal(first, second)
    assert 0 <= first.min() and first.max() < 50304
    assert len({row.tobytes() for row in first}) == 8


# ------------------------------------------------------------------ trace
def _planes():
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("while", 100.0, 400.0), ("fusion.1", 120.0, 100.0),
            ("fusion.2", 300.0, 150.0), ("copy", 700.0, 100.0)]},
        {"name": "Steps", "events": [("1", 0.0, 1000.0)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("bench.traced", 0.0, 1000.0), ("submit", 0.0, 90.0),
        ("router.step", 90.0, 500.0), ("stamp", 590.0, 120.0)]}]}
    return [device, host]


def test_trace_reduction_on_a_synthetic_trace_worked_by_hand():
    out = trace_reduce.reduce(_planes(), ("submit", "router.step", "stamp"))
    # busy = [100, 500] U [700, 800] = 500 ns of a 1000 ns window
    assert out["busy_s"] == pytest.approx(500e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["chips"] == 1
    # own time: the while covers 400 less its children's 250
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"while": 150e-9, "fusion.2": 150e-9, "fusion.1": 100e-9,
         "copy": 100e-9})
    # gaps: [0,100] under submit, [500,700] mostly under stamp, [800,1000]
    # under no span
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"submit": 100e-9, "stamp": 200e-9, "(no span)": 200e-9})


def test_device_operations_are_named_by_result_and_type():
    long = ("%fusion.430 = bf16[8,16,1024,512]{3,2,1,0:T(8,128)(2,1)} "
            "fusion(f32[8,16,1024,512]{3,2,1,0} %fusion.429), kind=kOutput")
    assert trace_reduce.short_name(long) == "fusion.430 bf16[8,16,1024,512]"
    assert trace_reduce.short_name(
        "%f.1 = (f32[8]{0}, f32[4]{0}) fusion(f32[8] %a)") == "f.1 f32[8]"
    assert trace_reduce.short_name("router.step") == "router.step"


def test_trace_reduction_without_device_work_reads_nothing():
    host_only = [p for p in _planes() if p["name"].startswith("/host")]
    assert trace_reduce.reduce(host_only, ("submit",)) is None
    from benchmark.readers import device_idle_share
    assert device_idle_share.read({"trace": None}) is None
    assert device_idle_share.read(
        {"trace": {"busy_s": 0.5, "window_s": 2.0}}) == pytest.approx(75.0)


def test_trace_window_falls_back_to_the_device_events_and_averages_chips():
    planes = _planes()[:1] + [{"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("fusion.1", 100.0, 700.0)]}]}]
    out = trace_reduce.reduce(planes, ())
    assert out["window_s"] == pytest.approx(700e-9)      # 100 .. 800
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((500e-9 + 700e-9) / 2)
