"""Evidence-gated kernel selection registry (kernels/registry.py,
perf/kernel_registry.json) — the round-6 tentpole.

Pins: (1) the shipped registry file is clean under validate() — this IS
the tier-1 CI guard against an ungated/implausible entry landing in the
repo; (2) selection precedence (env > sweep winner > registry > coded
default) and the seeded per-backend-class defaults: the TPU resolves
attention to 'xla' (the only hardware ablation's winner), CPU keeps
'pallas' so interpret-mode parity coverage keeps running; (3) adoption
— both registry.adopt and the sweep adoption — REJECTS rows the
roofline plausibility gate fails, so a host-bound or broken-clock
timing can never ship as the default."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

from paddle_tpu.kernels import registry
from paddle_tpu.kernels import flash_attention as fa


@pytest.fixture(autouse=True)
def _fresh_registry_memo():
    registry._reset()
    yield
    registry._reset()


class TestShippedRegistryFile:
    """The repo-committed table must stay trustworthy — CI fails here if
    an ungated or implausible entry is ever committed."""

    def test_file_exists_and_validates_clean(self):
        assert os.path.exists(registry.REGISTRY_PATH)
        problems = registry.validate()
        assert problems == [], problems

    def test_seeded_backend_class_defaults(self):
        assert registry.winner("attention", backend="tpu") == "xla"
        assert registry.winner("attention", backend="cpu") == "pallas"

    def test_seed_evidence_passes_the_gate_it_claims(self):
        ent = registry.entry("attention", "tpu")
        assert ent["kind"] == "measured"
        assert registry.gate_ms(ent["ms"], flops=ent["flops"],
                                bytes_moved=ent["bytes_moved"]) is None


class TestLookup:
    def _write(self, tmp_path, entries):
        path = str(tmp_path / "kernel_registry.json")
        with open(path, "w") as f:
            json.dump({"entries": entries}, f)
        return path

    def test_bucket_falls_back_to_wildcard(self, tmp_path):
        path = self._write(tmp_path, {
            "attention::tpu::S2048": {"impl": "splash", "kind": "policy",
                                      "reason": "test"},
            "attention::tpu::*": {"impl": "xla", "kind": "policy",
                                  "reason": "test"},
        })
        assert registry.winner("attention", backend="tpu",
                               bucket="S2048", path=path) == "splash"
        assert registry.winner("attention", backend="tpu",
                               bucket="S1024", path=path) == "xla"

    def test_invalid_entries_are_never_served(self, tmp_path):
        # an implausibly-fast 'measured' row and an unknown impl: both
        # must degrade to None (hardcoded default), not ship
        path = self._write(tmp_path, {
            "attention::tpu::*": {"impl": "xla", "kind": "measured",
                                  "ms": 0.001, "flops": 1.9e13},
            "ce::tpu::*": {"impl": "cudnn", "kind": "policy",
                           "reason": "typo'd impl"},
        })
        assert registry.winner("attention", backend="tpu",
                               path=path) is None
        assert registry.winner("ce", backend="tpu", path=path) is None
        assert len(registry.validate(path=path)) == 2

    def test_missing_file_is_empty_not_fatal(self, tmp_path):
        path = str(tmp_path / "nope.json")
        assert registry.winner("attention", backend="tpu",
                               path=path) is None
        assert registry.validate(path=path) == []

    def test_seq_bucket_rounds_up_to_pow2(self):
        assert registry.seq_bucket(1024) == "S1024"
        assert registry.seq_bucket(1000) == "S1024"
        assert registry.seq_bucket(1) == "S1"


class TestAdopt:
    def test_rejects_implausibly_fast_row(self, tmp_path):
        path = str(tmp_path / "kr.json")
        err = registry.adopt("attention", "xla", ms=0.01, flops=1.9e13,
                            backend="tpu", path=path)
        assert err and "implausibly fast" in err
        assert not os.path.exists(path)      # nothing was written

    def test_rejects_sub_floor_slow_row(self, tmp_path):
        path = str(tmp_path / "kr.json")
        err = registry.adopt("attention", "xla", ms=9e6, flops=1.9e13,
                            backend="tpu", path=path)
        assert err and "implausibly slow" in err
        assert not os.path.exists(path)

    def test_rejects_row_with_no_evidence_volume(self, tmp_path):
        path = str(tmp_path / "kr.json")
        err = registry.adopt("attention", "xla", ms=400.0, backend="tpu",
                            path=path)
        assert err and "volume" in err

    def test_plausible_row_persists_and_serves(self, tmp_path):
        path = str(tmp_path / "kr.json")
        assert registry.adopt(
            "attention", "splash", ms=380.0, flops=1.9e13, backend="tpu",
            bucket="S1024", source="unit test", window="WTEST",
            path=path) is None
        registry._reset()                    # force a disk re-read
        assert registry.winner("attention", backend="tpu",
                               bucket="S1024", path=path) == "splash"
        assert registry.validate(path=path) == []


class TestAttentionSelection:
    """Acceptance pin: env overrides unset + no sweep file present ->
    _attn_impl() is 'xla' on TPU-class backends (seeded registry) and
    'pallas' on CPU (parity coverage)."""

    def _no_sweep(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ATTN_IMPL", raising=False)
        # memoized sweep-winner read pinned to "file absent/invalid"
        monkeypatch.setattr(fa, "_sweep_winner_impl", "")

    def test_cpu_default_is_pallas(self, monkeypatch):
        self._no_sweep(monkeypatch)
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "cpu")
        assert fa._attn_impl() == "pallas"

    def test_tpu_default_is_xla(self, monkeypatch):
        self._no_sweep(monkeypatch)
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._attn_impl() == "xla"

    def test_env_override_outranks_registry(self, monkeypatch):
        self._no_sweep(monkeypatch)
        monkeypatch.setenv("PADDLE_TPU_ATTN_IMPL", "splash")
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._attn_impl() == "splash"

    def test_sweep_winner_outranks_registry(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ATTN_IMPL", raising=False)
        monkeypatch.setattr(fa, "_sweep_winner_impl", "jax_flash")
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._attn_impl() == "jax_flash"


class TestVarlenSelection:
    def test_env_override(self, monkeypatch):
        from paddle_tpu.nn.functional.attention import _varlen_impl
        monkeypatch.setenv("PADDLE_TPU_VARLEN_IMPL", "dense")
        assert _varlen_impl(10**9) == "dense"
        monkeypatch.setenv("PADDLE_TPU_VARLEN_IMPL", "blockwise")
        assert _varlen_impl(1) == "blockwise"

    def test_heuristic_default(self, monkeypatch):
        from paddle_tpu.nn.functional import attention as A
        monkeypatch.delenv("PADDLE_TPU_VARLEN_IMPL", raising=False)
        assert A._varlen_impl(A._VARLEN_DENSE_MAX + 1) == "blockwise"
        assert A._varlen_impl(64) == "dense"

    def test_registry_dense_winner_cannot_override_memory_guard(
            self, monkeypatch):
        """A wildcard 'dense' registry row measured on a small packing
        is a preference, not a license to materialize an O(n) probs
        buffer at every size: above _VARLEN_DENSE_MAX it degrades to
        blockwise. The env override (operator escape hatch) stays
        absolute."""
        from paddle_tpu.nn.functional import attention as A
        monkeypatch.delenv("PADDLE_TPU_VARLEN_IMPL", raising=False)
        monkeypatch.setattr(registry, "winner",
                            lambda *a, **k: "dense")
        assert A._varlen_impl(64) == "dense"
        assert A._varlen_impl(A._VARLEN_DENSE_MAX + 1) == "blockwise"


class TestSweepAdoptionGate:
    """registry.adopt_sweep_winner must refuse to ship a row the
    physical-plausibility gate rejects."""

    def _adopt(self, tmp_path, rows):
        from sweep_gpt_step import _specs
        registry.adopt_sweep_winner(rows, "WGATE", _specs(),
                                    perf_dir=str(tmp_path))
        return (os.path.join(str(tmp_path), "sweep_winner.json"),
                os.path.join(str(tmp_path), "kernel_registry.json"))

    def test_implausibly_fast_winner_not_adopted(self, tmp_path):
        # 1 ms for a GPT-350M B=4 step: ~50x faster than the roofline —
        # the classic broken-clock artifact. Nothing may ship.
        sweep, kr = self._adopt(tmp_path, [
            {"name": "noremat-xlaattn-b4", "ms_per_step": 1.0,
             "tokens_per_sec": 4096000.0, "batch": 4,
             "platform": "tpu"}])
        assert not os.path.exists(sweep)
        assert not os.path.exists(kr)

    def test_plausible_winner_lands_in_both_stores(self, tmp_path):
        sweep, kr = self._adopt(tmp_path, [
            {"name": "noremat-xlaattn-b4", "ms_per_step": 160.0,
             "tokens_per_sec": 25600.0, "batch": 4, "platform": "tpu"}])
        doc = json.load(open(sweep))
        assert doc["name"] == "noremat-xlaattn-b4"
        assert doc["gate"]["passed"] is True
        # the registry row is written through the gated adopt() and
        # validates clean
        registry._reset()
        assert registry.winner("attention", backend="tpu",
                               bucket="S1024", path=kr) == "xla"
        assert registry.validate(path=kr) == []
