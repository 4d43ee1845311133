"""Sweep-winner auto-adoption: kernels.registry.adopt_sweep_winner writes
perf/sweep_winner.json when tools/sweep_gpt_step.py lands; the attention
impl default (TPU only) follows it. Pins the env->impl translation and
the CPU guard (the suite must keep exercising the pallas path)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

from paddle_tpu.kernels import flash_attention as fa


class TestImplFromWinnerEnv:
    def test_selector_key_direct(self):
        assert fa.impl_from_winner_env(
            {"PADDLE_TPU_ATTN_IMPL": "splash"}) == "splash"

    def test_kill_switch_spelling_means_xla(self):
        assert fa.impl_from_winner_env(
            {"PADDLE_TPU_DISABLE_PALLAS_ATTN": "1",
             "PADDLE_TPU_DISABLE_PALLAS_BWD": "1"}) == "xla"

    def test_unknown_or_empty(self):
        assert fa.impl_from_winner_env({}) == ""
        assert fa.impl_from_winner_env(
            {"PADDLE_TPU_ATTN_IMPL": "cuda"}) == ""


class TestAdoption:
    def _write_winner(self, tmp_path, records):
        from sweep_gpt_step import _specs
        from paddle_tpu.kernels import registry
        registry.adopt_sweep_winner(records, "WTEST", _specs(),
                                    perf_dir=str(tmp_path))
        return os.path.join(str(tmp_path), "sweep_winner.json")

    def test_best_tpu_record_wins_cpu_noise_ignored(self, tmp_path):
        path = self._write_winner(tmp_path, [
            {"name": "allbutmlp-splash-b8", "ms_per_step": 400.0,
             "tokens_per_sec": 20480.0, "batch": 8, "platform": "tpu"},
            {"name": "noremat-xlaattn-b4", "ms_per_step": 160.0,
             "tokens_per_sec": 25600.0, "batch": 4, "platform": "tpu"},
            {"name": "cpu-noise", "tokens_per_sec": 9e9,
             "platform": "cpu"},
        ])
        doc = json.load(open(path))
        assert doc["name"] == "noremat-xlaattn-b4"
        assert doc["remat"] is False and doc["window"] == "WTEST"
        assert fa.impl_from_winner_env(doc["env"]) == "xla"

    def test_no_tpu_records_writes_nothing(self, tmp_path):
        path = self._write_winner(tmp_path, [
            {"name": "x", "tokens_per_sec": 1.0, "platform": "cpu"}])
        assert not os.path.exists(path)

    def test_attn_default_follows_winner_on_tpu_only(self, monkeypatch):
        # memoized file read is stubbed; the guard under test is the
        # backend check + env precedence
        monkeypatch.setattr(fa, "_sweep_winner_impl", "xla")
        monkeypatch.delenv("PADDLE_TPU_ATTN_IMPL", raising=False)
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "cpu")
        assert fa._attn_impl() == "pallas"     # CPU ignores the winner
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._attn_impl() == "xla"        # TPU adopts it
        monkeypatch.setenv("PADDLE_TPU_ATTN_IMPL", "splash")
        assert fa._attn_impl() == "splash"     # env always outranks
