"""The rehearsal of chip_smoke.py, and the guards that keep a run off the
chip from looking like one.

chip_smoke.py itself needs a TPU and prints its success line only there.
Here the SAME phase functions run once at tiny widths on the CPU (Pallas
kernels in the TPU interpreter), the four-chip phase runs on four of the
virtual devices, and the entry points that measure (chip_smoke.py,
bench.py) are shown to refuse a CPU-only backend without printing a
result. Nothing in this file is a speed statement.
"""
import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _phases(lines):
    recs = [json.loads(ln) for ln in lines]
    # every line a rehearsal prints is a phase record; the contract's
    # success line ({"ok": true, "device": ...}) has no "phase"
    assert all("phase" in r and "device" not in r for r in recs), recs
    return {r["phase"]: r for r in recs}


def test_rehearsal_runs_every_one_chip_phase():
    lines = []
    run = chip_smoke.Run(seed=0, emit=lines.append)
    assert not run.on_chip
    chip_smoke.run_one_chip(run, chip_smoke.TINY)
    phases = _phases(lines)
    assert list(phases) == ["surface", "kernels", "train", "serve",
                            "serve_recurrent"]
    assert all(r["ok"] for r in phases.values())
    assert phases["surface"]["loader_workers"] == "processes"
    assert set(phases["kernels"]["kernels"]) >= {
        "flash_fwd_hd64", "flash_bwd_hd128", "flash_tiled_hd64",
        "flash_tiled_hd128", "jax_flash", "splash", "ce",
        "ce_fused", "fused_adamw", "decode_live_blocks", "mla_live_blocks",
        "gated_delta_tick"}
    losses = phases["train"]["losses"]
    assert losses[1] < losses[0]
    variants = phases["serve"]["variants"]
    assert set(variants) == {"dense", "paged", "paged_spec", "paged_int8",
                             "reference_decode"}
    # TINY computes in float32: every stream identical, no tie to judge
    assert variants["paged"]["vs_dense"]["exact"] == 6
    assert variants["paged_spec"]["vs_paged"]["exact"] == 6
    assert variants["reference_decode"]["exact"] == 1
    recurrent = phases["serve_recurrent"]
    assert recurrent["readmitted_equals_fresh"] is True
    assert recurrent["model"] == "4Lx128d m/a/m/a"
    assert set(recurrent["pool_bytes"]) == {"k", "v", "ssm", "conv"}


def test_rehearsal_runs_the_four_chip_phase_on_virtual_devices():
    lines = []
    run = chip_smoke.Run(seed=0, emit=lines.append)
    assert len(run.devices) >= 4        # tests/conftest.py pins eight
    chip_smoke.run_four_chips(run, chip_smoke.TINY)
    phases = _phases(lines)
    assert list(phases) == ["four_chips.train", "four_chips.serve"]
    train, serve = phases["four_chips.train"], phases["four_chips.serve"]
    assert train["tp2_pp2"]["plan"] == "dp1_fsdp1_tp2_pp2"
    for plan in ("planned", "tp2_pp2"):
        assert train[plan]["max_rel_dev"] <= train["rtol"]
        assert all(b > 0 for b in train[plan]["param_bytes_per_device"])
    assert serve["tp4"]["vs_one_chip"]["exact"] == 6
    assert len(set(serve["router4"]["replica_devices"])) == 4
    assert len(serve["router4"]["replicas_used"]) > 1


def test_a_failed_check_raises_and_prints_no_record():
    lines = []
    run = chip_smoke.Run(seed=0, emit=lines.append)

    def broken(run):
        chip_smoke.check(False, "nope")
    with pytest.raises(chip_smoke.SmokeFailure, match="nope"):
        run.phase("broken", broken)
    assert lines == [] and run.records == []


def _cpu_only(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *cmd], cwd=REPO, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=300)


@pytest.mark.parametrize("cmd", [["chip_smoke.py"],
                                 ["chip_smoke.py", "--chips", "4"],
                                 ["bench.py"]],
                         ids=["chip_smoke", "chip_smoke_chips4", "bench"])
def test_measuring_entry_points_refuse_a_cpu_only_backend(cmd):
    """No TPU: a non-zero exit and NO result on stdout — never a CPU
    number under a device metric's name, never the success line."""
    res = _cpu_only(cmd)
    assert res.returncode != 0, res.stdout
    assert res.stdout.strip() == b"", res.stdout
    assert b"no TPU" in res.stderr


def test_bench_cpu_check_says_cpu_and_carries_no_mfu():
    res = _cpu_only(["bench.py", "--cpu"])
    assert res.returncode == 0, res.stderr[-2000:]
    rec = json.loads(res.stdout.decode().strip().splitlines()[-1])
    assert rec["backend"] == "cpu" and rec["config"] == "cpu-check"
    assert "mfu" not in rec and "vs_baseline" not in rec
    assert rec["metric"] != "gpt_train_tokens_per_sec_per_chip"


def test_peaks_table_knows_the_v5e_and_nothing_it_was_not_told():
    from paddle_tpu.device import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    for kind in ("TPU v9", "cpu", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            chip_peaks(kind)


def test_live_mfu_gauge_prices_the_live_device_or_raises(tmp_path):
    """train.mfu without an explicit peak_flops= is priced from the peaks
    table by the live device_kind; a CPU is not in it."""
    import numpy as np
    from paddle_tpu.profiler import telemetry
    tele = telemetry.TelemetryPipeline(
        str(tmp_path / "t.jsonl"), every=1, fields=telemetry.MFU_FIELDS,
        flops_per_token=1.0)
    tok = telemetry.MFU_FIELDS.index("tokens")
    row = np.zeros((1, len(telemetry.MFU_FIELDS)), np.float32)
    row[0, tok] = 8.0
    try:
        tele._prev_flush_t = 1.0      # past the compile window
        with pytest.raises(ValueError, match="no published peaks"):
            tele._enqueue({"buf": row, "n": 1})
    finally:
        tele.close()


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_a_preset_cache_dir_survives_both_helpers(platform, tmp_path,
                                                  monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the program uses that
    directory: it sets no other and never turns it off."""
    from paddle_tpu.utils import compile_cache as cc
    outside = str(tmp_path / "outside_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    prior = jax.config.jax_compilation_cache_dir
    try:
        # what jax itself does at start-up when the variable is set
        jax.config.update("jax_compilation_cache_dir", outside)
        cc.seed_cache_env()
        cc.sync_compile_cache_for(platform)
        assert jax.config.jax_compilation_cache_dir == outside
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_unset_cache_dir_falls_to_the_checkout_on_the_tpu_only(monkeypatch):
    from paddle_tpu.utils import compile_cache as cc
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        cc.seed_cache_env()
        checkout = os.path.join(REPO, "perf", "xla_cache")
        assert jax.config.jax_compilation_cache_dir == checkout
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == checkout
        cc.sync_compile_cache_for("tpu")
        assert jax.config.jax_compilation_cache_dir == checkout
        cc.sync_compile_cache_for("cpu")
        assert jax.config.jax_compilation_cache_dir is None
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
