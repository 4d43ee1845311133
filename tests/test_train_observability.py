"""Training MFU observatory (ISSUE 12 tentpole): the train-step ledger's
arithmetic properties, the GSPMD collective audit over the executable
3D plans, the achieved-MFU telemetry gauge, and the train_attrib join —
on the 8-virtual-device CPU mesh.

The contract pinned here:
- `cost_model.train_step_ledger`: bwd prices exactly 2x the forward;
  remat adds recompute FLOPs and ZERO bytes; collective bytes scale
  with the right axis degrees and cross-check against parallel/planner
  _estimate's breakdown (same _ring_factor formulas);
- `roofline_attribution` prices `channel: "ici"` phases against the
  interconnect, reports the plan's peak MFU;
- `profiler/hlo_audit` finds the expected collectives for
  dp2×fsdp2×tp2 / dp4×tp2 / fsdp8 and names every surprise (the
  resharding collective-permutes around the vocab-parallel embedding
  are KNOWN findings — BASELINE.md "Training observability");
- the telemetry `tokens` field extension leaves sharded loss
  trajectories BIT-IDENTICAL to telemetry-off, and the flush computes
  the `train.mfu` gauge;
- `tools/train_attrib.attrib_row` joins a recorded JSONL with the
  ledger;
- `tools/diff_failures` flags only NEW failures.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.cost_model import (roofline_attribution,
                                   train_flops_per_token,
                                   train_step_ledger)
from paddle_tpu.models.facade import make_train_step
from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                   init_opt_state, train_step)
from paddle_tpu.parallel.planner import ChipSpec, plan_train

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

B, S = 8, 32


def _cfg():
    return GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=4, max_seq_len=64, dtype=jnp.float32,
                     remat=False, sequence_parallel=False)


def _tokens(seed=0):
    return np.random.RandomState(seed).randint(
        0, 512, (B, S + 1)).astype(np.int32)


# --------------------------------------------------------------------------
# the ledger's arithmetic properties
# --------------------------------------------------------------------------
class TestTrainStepLedger:
    def test_bwd_is_twice_fwd(self):
        led = train_step_ledger(_cfg(), plan={"dp": 2, "fsdp": 2,
                                              "tp": 2},
                                global_batch=B, seq=S)
        p = led["phases"]
        assert p["bwd"]["flops"] == 2 * (p["fwd_matmul"]["flops"]
                                         + p["fwd_attention"]["flops"])
        assert p["bwd"]["bytes"] == 2 * p["fwd_matmul"]["bytes"]

    def test_remat_adds_recompute_flops_not_bytes(self):
        base = train_step_ledger(_cfg(), global_batch=B, seq=S,
                                 remat="none")
        full = train_step_ledger(_cfg(), global_batch=B, seq=S,
                                 remat="full")
        dots = train_step_ledger(_cfg(), global_batch=B, seq=S,
                                 remat="dots")
        assert base["phases"]["remat"]["flops"] == 0
        assert full["phases"]["remat"]["flops"] > \
            dots["phases"]["remat"]["flops"] > 0
        assert full["phases"]["remat"]["bytes"] == 0
        # recompute is the ONLY difference
        assert full["total"]["bytes"] == base["total"]["bytes"]
        with pytest.raises(ValueError, match="remat policy"):
            train_step_ledger(_cfg(), global_batch=B, remat="bogus")

    def test_collective_bytes_scale_with_the_right_axis(self):
        cfg = _cfg()
        led1 = train_step_ledger(cfg, plan={"dp": 1}, global_batch=B,
                                 seq=S)
        # degree-1 axes price to zero
        assert all(led1["phases"][f"coll_{a}"]["bytes"] == 0
                   for a in ("tp", "dp", "fsdp"))
        # tp volume scales with the ring factor (2(n-1)/n), per chip
        # (same dp => same tok_local; the ledger prices any degree
        # combination, not only 8-device factorizations)
        tp2 = train_step_ledger(cfg, plan={"tp": 2, "dp": 2},
                                global_batch=B, seq=S)
        tp4 = train_step_ledger(cfg, plan={"tp": 4, "dp": 2},
                                global_batch=B, seq=S)
        # ring(4)/ring(2) = 1.5
        assert tp4["phases"]["coll_tp"]["bytes"] == pytest.approx(
            1.5 * tp2["phases"]["coll_tp"]["bytes"])
        # fsdp volume scales with 3(n-1)/n of the per-tp params
        f2 = train_step_ledger(cfg, plan={"fsdp": 2, "dp": 4},
                               global_batch=B, seq=S)
        f8 = train_step_ledger(cfg, plan={"fsdp": 8},
                               global_batch=B, seq=S)
        assert f8["phases"]["coll_fsdp"]["bytes"] == pytest.approx(
            (3 * 7 / 8) / (3 * 1 / 2)
            * f2["phases"]["coll_fsdp"]["bytes"])
        # dp gradient reduction shrinks as fsdp/tp shard the params
        d_wide = train_step_ledger(cfg, plan={"dp": 2, "fsdp": 4},
                                   global_batch=B, seq=S)
        d_flat = train_step_ledger(cfg, plan={"dp": 2, "fsdp": 1,
                                              "tp": 4},
                                   global_batch=B, seq=S)
        assert d_wide["phases"]["coll_dp"]["bytes"] == pytest.approx(
            d_flat["phases"]["coll_dp"]["bytes"])

    def test_cross_checks_planner_pricing(self):
        """The ledger's collective phases ARE the planner's comm model:
        bound seconds match _estimate's breakdown exactly (breakdown
        applies its overlap discounts of 1.0/0.3/0.6 on top)."""
        cfg = _cfg()
        plan = plan_train(cfg, 8, B, dp=2, fsdp=2, tp=2)
        # planner prices the spec's full seq and bf16-ish activations
        led = train_step_ledger(cfg, plan=plan, global_batch=B,
                                seq=cfg.max_seq_len, dtype_bytes=2)
        chip = ChipSpec()
        bd = plan.plan.breakdown
        assert led["phases"]["coll_tp"]["bytes"] / chip.ici_bw == \
            pytest.approx(bd["tp_s"])
        assert 0.3 * led["phases"]["coll_dp"]["bytes"] / chip.ici_bw \
            == pytest.approx(bd["dp_s"])
        assert 0.6 * led["phases"]["coll_fsdp"]["bytes"] / chip.ici_bw \
            == pytest.approx(bd["fsdp_s"])

    def test_roofline_prices_ici_channel_and_peak_mfu(self):
        led = train_step_ledger(_cfg(), plan={"dp": 2, "fsdp": 2,
                                              "tp": 2},
                                global_batch=B, seq=S)
        roof = roofline_attribution(led)
        assert roof["per_phase"]["coll_fsdp"]["bound"] == "ici"
        assert 0 < roof["peak_mfu"] <= 1
        assert roof["predicted_step_ms"] > 0
        # halving the interconnect moves ONLY the ici phases
        slow = roofline_attribution(led, ici_bw=ChipSpec().ici_bw / 2)
        assert slow["per_phase"]["coll_fsdp"]["bound_s"] == \
            pytest.approx(2 * roof["per_phase"]["coll_fsdp"]["bound_s"])
        assert slow["per_phase"]["fwd_matmul"]["bound_s"] == \
            pytest.approx(roof["per_phase"]["fwd_matmul"]["bound_s"])
        # the MFU numerator is the ONE-home formula
        n_params = led["config"]["n_params"]
        assert led["model_flops"] == pytest.approx(
            train_flops_per_token(n_params, 2, 128, S) * B * S)


# --------------------------------------------------------------------------
# the HLO collective audit
# --------------------------------------------------------------------------
AUDIT_PLANS = [
    {"dp": 2, "fsdp": 2, "tp": 2},
    {"dp": 4, "fsdp": 1, "tp": 2},
    {"dp": 1, "fsdp": 8, "tp": 1},
]


class TestHloAudit:
    def test_parse_both_replica_group_spellings(self):
        from paddle_tpu.profiler.hlo_audit import _parse_groups
        assert _parse_groups("{{0,1},{4,5},{2,3},{6,7}}") == [
            (0, 1), (4, 5), (2, 3), (6, 7)]
        # iota: arange(8).reshape(4,2).T.reshape(2,4)
        assert _parse_groups("[2,4]<=[4,2]T(1,0)") == [
            (0, 2, 4, 6), (1, 3, 5, 7)]
        assert _parse_groups("[4,2]<=[8]") == [
            (0, 1), (2, 3), (4, 5), (6, 7)]

    @pytest.mark.parametrize("axes", AUDIT_PLANS,
                             ids=lambda a: "_".join(
                                 f"{k}{v}" for k, v in a.items()))
    def test_audit_finds_expected_collectives(self, axes):
        from paddle_tpu.profiler import hlo_audit
        from paddle_tpu.profiler import monitor
        cfg = _cfg()
        plan = plan_train(cfg, 8, B, **axes)
        doc = hlo_audit.audit_train_step(cfg, plan, B, seq=S)
        assert doc["n_devices"] == 8
        assert doc["compile_ms"] > 0
        by_axes = {(tuple(r["axes"]) if r["axes"] else None, r["op"])
                   for r in doc["collectives"]}
        if axes["fsdp"] > 1:
            # ZeRO-3: parameter all-gathers on the fsdp axis
            assert (("fsdp",), "all-gather") in by_axes
        if axes["tp"] > 1:
            # per-layer activation reductions on the tp axis
            assert any(op == "all-reduce" and ax and "tp" in ax
                       for ax, op in by_axes)
        if axes["dp"] > 1:
            # gradient/loss reductions touch dp (alone or with fsdp)
            assert any(op == "all-reduce" and ax and "dp" in ax
                       for ax, op in by_axes)
        # every surprise is NAMED ...
        for f in doc["findings"]:
            assert f["kind"] in ("resharding_groups",
                                 "resharding_permute",
                                 "unplanned_collective")
        # ... and since PR 16 killed the embedding-resharding
        # collective-permutes (batch-axis-aligned embedding specs),
        # the canonical 3D plans audit CLEAN — tools/audit_gate.py
        # pins this per plan against perf/audit_baseline.json
        assert doc["findings"] == []
        # compile observability published
        assert monitor.counter("train.compile.audits").value >= 1
        assert monitor.gauge("train.compile.audit_ms").value > 0


# --------------------------------------------------------------------------
# achieved-MFU telemetry + bit-identical trajectories
# --------------------------------------------------------------------------
class TestMfuTelemetry:
    def _run_instrumented(self, tmp_path, every=2, steps=6):
        from paddle_tpu.profiler.telemetry import (MFU_FIELDS,
                                                   TelemetryPipeline,
                                                   instrument_train_step)
        cfg = _cfg()
        plan = plan_train(cfg, 8, B, dp=2, fsdp=2, tp=2)
        mesh = plan.build_mesh()
        led = train_step_ledger(cfg, plan=plan, global_batch=B, seq=S)
        path = str(tmp_path / "mfu.jsonl")
        tele = TelemetryPipeline(
            path, every=every, fields=MFU_FIELDS,
            flops_per_token=led["model_flops"] / led["tokens"],
            peak_flops=8 * ChipSpec().peak_flops)
        step = instrument_train_step(train_step, tele, cfg=cfg,
                                     lr=1e-3, mesh=mesh, plan=plan)
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        toks = _tokens()
        tstate = tele.device_init()
        losses = []
        for i in range(steps):
            loss, params, opt, tstate = step(params, opt, toks, tstate)
            losses.append(float(loss))
            tstate = tele.tick(i, tstate)
        tele.close()
        assert step.trace_count == 1
        return path, losses, tele

    def test_mfu_gauge_appears_after_flush(self, tmp_path):
        from paddle_tpu.profiler import monitor
        path, _losses, tele = self._run_instrumented(tmp_path)
        assert tele.pulls == 3
        assert monitor.gauge("train.mfu").value > 0
        assert monitor.gauge("train.tokens_per_s").value > 0
        # the SAME flush's monitor record carries the gauge into the
        # stream, and every step recorded the static token count
        recs = [json.loads(ln) for ln in open(path)]
        mons = [r for r in recs if r.get("kind") == "monitor"]
        assert mons[-1]["stats"]["train.mfu"] == \
            monitor.gauge("train.mfu").value
        steps = [r for r in recs if r.get("kind") == "step"]
        assert all(r["tokens"] == B * S for r in steps)
        # facade compile stats rode along
        assert mons[-1]["stats"]["train.compile.executables"] >= 1
        assert mons[-1]["stats"]["train.compile.wall_ms"] > 0

    def test_flops_per_token_requires_tokens_field(self, tmp_path):
        from paddle_tpu.profiler.telemetry import TelemetryPipeline
        with pytest.raises(ValueError, match="tokens"):
            TelemetryPipeline(str(tmp_path / "x.jsonl"),
                              flops_per_token=1.0)

    def test_sharded_loss_bit_identical_to_telemetry_off(self,
                                                         tmp_path):
        """Extending the accumulator with tokens/step must not move the
        loss by one ulp (the acceptance bar: telemetry is observation,
        not perturbation)."""
        _path, losses_on, _tele = self._run_instrumented(tmp_path,
                                                         steps=4)
        cfg = _cfg()
        plan = plan_train(cfg, 8, B, dp=2, fsdp=2, tp=2)
        mesh = plan.build_mesh()
        step = make_train_step(train_step, cfg=cfg, lr=1e-3,
                               mesh=mesh, plan=plan)
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        toks = _tokens()
        losses_off = []
        for _ in range(4):
            loss, params, opt = step(params, opt, toks)
            losses_off.append(float(loss))
        assert losses_on[:4] == losses_off       # BIT-identical

    def test_report_grows_mfu_block(self, tmp_path):
        path, _losses, _tele = self._run_instrumented(tmp_path)
        from telemetry_report import summarize
        doc = summarize(path)
        assert doc["mfu"]["mfu"] > 0
        assert doc["mfu"]["tokens_per_s"] > 0
        assert doc["mfu"]["compile"]["executables"] >= 1


# --------------------------------------------------------------------------
# the train_attrib join on a recorded JSONL
# --------------------------------------------------------------------------
class TestTrainAttribJoin:
    def test_join_recorded_jsonl(self, tmp_path):
        t = __import__("train_attrib")
        cfg = _cfg()

        class A:
            batch, seq = B, S

        path, _losses, _tele = TestMfuTelemetry()._run_instrumented(
            tmp_path, every=2, steps=6)
        led = train_step_ledger(cfg, plan=t.parse_plan_name(
            "dp2_fsdp2_tp2"), global_batch=B, seq=S)
        roof = roofline_attribution(led)
        from telemetry_report import summarize
        row = t.attrib_row(summarize(path), led, roof,
                           plan_name="dp2_fsdp2_tp2")
        assert row["plan"] == "dp2_fsdp2_tp2"
        assert row["measured_ms_per_step_p50"] > 0
        assert row["roofline_ms_per_step"] > 0
        assert 0 < row["achieved_vs_roofline"] < 1   # CPU vs TPU roof
        assert row["achieved_mfu"] > 0
        assert abs(sum(p["share"]
                       for p in row["phases"].values()) - 1.0) < 0.01

    def test_parse_plan_name(self):
        t = __import__("train_attrib")
        assert t.parse_plan_name("dp2_fsdp2_tp2") == {
            "dp": 2, "fsdp": 2, "tp": 2}
        assert t.parse_plan_name("fsdp8") == {"dp": 1, "fsdp": 8,
                                              "tp": 1}
        assert t.parse_plan_name("dp4_tp2") == {"dp": 4, "fsdp": 1,
                                                "tp": 2}
        assert t.parse_plan_name("dp2_tp2_pp2_mb4") == {
            "dp": 2, "fsdp": 1, "tp": 2, "pp": 2, "microbatches": 4}
        assert t.parse_plan_name("fsdp8_overlap") == {
            "dp": 1, "fsdp": 8, "tp": 1, "overlap": True}


# --------------------------------------------------------------------------
# train_attrib --compare: the before/after delta table on recorded
# fixtures (the overlap campaign's evidence format)
# --------------------------------------------------------------------------
class TestTrainAttribCompare:
    @staticmethod
    def _row(plan, ms, mfu, shares, findings=0):
        return {
            "plan": plan, "steps": 8,
            "measured_ms_per_step_p50": ms,
            "roofline_ms_per_step": 1.0,
            "achieved_mfu": mfu,
            "phases": {p: {"share": s, "bound": "ici",
                           "flops": 0, "bytes": 100}
                       for p, s in shares.items()},
            "audit": {"counts": {}, "compile_ms": 1.0,
                      "findings": [
                          {"kind": "resharding-all-gather", "op": "ag",
                           "axes": ["fsdp"], "count": 1, "bytes": 8}
                      ] * findings},
        }

    def _fixtures(self, tmp_path):
        import json
        before = [
            self._row("dp2_fsdp2_tp2", 40.0, 0.20,
                      {"fwd_matmul": 0.4, "coll_fsdp": 0.35,
                       "coll_tp": 0.25}, findings=2),
            self._row("fsdp8", 30.0, 0.22,
                      {"fwd_matmul": 0.5, "coll_fsdp": 0.5}),
        ]
        after = [
            self._row("dp2_fsdp2_tp2", 31.0, 0.31,
                      {"fwd_matmul": 0.55, "coll_fsdp": 0.15,
                       "coll_tp": 0.30}),
            self._row("fsdp8", 24.0, 0.29,
                      {"fwd_matmul": 0.7, "coll_fsdp": 0.3}),
        ]
        # before: a main() stdout doc; after: a telemetry stream with
        # embedded rows — load_rows must read both shapes
        bpath, apath = tmp_path / "before.jsonl", tmp_path / "after.jsonl"
        bpath.write_text(json.dumps(
            {"metric": "train_roofline_attribution",
             "backend": "cpu", "plans": before}) + "\n")
        with open(apath, "w") as f:
            f.write(json.dumps({"kind": "telemetry",
                                "step_ms": 24.0}) + "\n")
            for r in after:
                f.write(json.dumps({"kind": "train_attrib", **r}) + "\n")
            f.write("not json\n")
        return str(bpath), str(apath)

    def test_load_rows_reads_both_formats(self, tmp_path):
        t = __import__("train_attrib")
        bpath, apath = self._fixtures(tmp_path)
        assert [r["plan"] for r in t.load_rows(bpath)] == [
            "dp2_fsdp2_tp2", "fsdp8"]
        assert [r["plan"] for r in t.load_rows(apath)] == [
            "dp2_fsdp2_tp2", "fsdp8"]

    def test_compare_rows_deltas(self, tmp_path):
        t = __import__("train_attrib")
        bpath, apath = self._fixtures(tmp_path)
        cmp_rows = t.compare_rows(t.load_rows(bpath),
                                  t.load_rows(apath))
        assert [r["plan"] for r in cmp_rows] == ["dp2_fsdp2_tp2",
                                                 "fsdp8"]
        r = cmp_rows[0]
        assert r["measured_ms_delta"] == pytest.approx(-9.0)
        assert r["achieved_mfu_delta"] == pytest.approx(0.11)
        assert r["findings_before"] == 2 and r["findings_after"] == 0
        # the ISSUE acceptance check: coll_fsdp share strictly down on
        # both canonical plans with overlap on
        for row in cmp_rows:
            assert row["phase_share_delta"]["coll_fsdp"] < 0, row

    def test_compare_skips_unmatched_plans(self, tmp_path):
        t = __import__("train_attrib")
        bpath, apath = self._fixtures(tmp_path)
        after = t.load_rows(apath)
        after.append(self._row("dp8", 9.0, 0.5, {"fwd_matmul": 1.0}))
        cmp_rows = t.compare_rows(t.load_rows(bpath), after)
        assert [r["plan"] for r in cmp_rows] == ["dp2_fsdp2_tp2",
                                                 "fsdp8"]

    def test_render_compare_table(self, tmp_path):
        t = __import__("train_attrib")
        bpath, apath = self._fixtures(tmp_path)
        out = t.render_compare(t.compare_rows(t.load_rows(bpath),
                                              t.load_rows(apath)))
        assert "dp2_fsdp2_tp2" in out and "fsdp8" in out
        assert "coll_fsdp-20%" in out       # the hidden collective leg
        assert "+11.00%" in out             # the MFU delta

    def test_cli_compare_prints_doc_and_table(self, tmp_path, capsys,
                                              monkeypatch):
        import json
        t = __import__("train_attrib")
        bpath, apath = self._fixtures(tmp_path)
        monkeypatch.setattr(sys, "argv",
                            ["train_attrib.py", "--compare", bpath,
                             apath])
        assert t.main() == 0
        lines = capsys.readouterr().out.splitlines()
        doc = json.loads(lines[0])
        assert doc["metric"] == "train_attrib_compare"
        assert [r["plan"] for r in doc["plans"]] == [
            "dp2_fsdp2_tp2", "fsdp8"]
        assert any("coll_fsdp" in ln for ln in lines[1:])


# --------------------------------------------------------------------------
# tools/audit_gate.py: the no-new-resharding regression gate
# --------------------------------------------------------------------------
class TestAuditGate:
    def test_finding_counts_aggregates_by_kind(self):
        g = __import__("audit_gate")
        audit = {"findings": [
            {"kind": "resharding_permute", "count": 2},
            {"kind": "resharding_permute", "count": 1},
            {"kind": "resharding_groups", "count": 4},
        ]}
        assert g.finding_counts(audit) == {"resharding_permute": 3,
                                           "resharding_groups": 4}
        assert g.finding_counts({"findings": []}) == {}

    def test_diff_counts_flags_new_and_grown_only(self):
        g = __import__("audit_gate")
        base = {"resharding_permute": 2}
        assert g.diff_counts(base, {"resharding_permute": 2}) == []
        assert g.diff_counts(base, {"resharding_permute": 1}) == []
        assert g.diff_counts(base, {"resharding_permute": 3}) == [
            ("resharding_permute", 2, 3)]
        assert g.diff_counts(base, {"unplanned_collective": 1}) == [
            ("unplanned_collective", 0, 1)]

    def test_gate_round_trip_on_stub_audits(self, tmp_path,
                                            monkeypatch, capsys):
        import json
        g = __import__("audit_gate")
        audits = {"fsdp8": {"findings": []},
                  "dp2_fsdp2_tp2": {"findings": [
                      {"kind": "resharding_permute", "count": 1}]}}
        monkeypatch.setattr(g, "audit_plan", lambda n: audits[n])
        path = str(tmp_path / "audit_baseline.json")
        plans = ["fsdp8", "dp2_fsdp2_tp2"]
        assert g.gate(plans, path, write=True) == 0
        doc = json.load(open(path))
        assert doc["plans"]["fsdp8"]["findings"] == 0
        assert doc["plans"]["dp2_fsdp2_tp2"]["kinds"] == {
            "resharding_permute": 1}
        # unchanged state: green
        assert g.gate(plans, path) == 0
        # a NEW kind on a clean plan: red, and the regression is named
        audits["fsdp8"] = {"findings": [
            {"kind": "resharding_groups", "count": 2}]}
        assert g.gate(plans, path) == 1
        assert "REGRESSION fsdp8: resharding_groups 0 -> 2" in \
            capsys.readouterr().out
        # a FIXED plan: green with the --write-baseline nudge
        audits["fsdp8"] = {"findings": []}
        audits["dp2_fsdp2_tp2"] = {"findings": []}
        assert g.gate(plans, path) == 0
        assert "--write-baseline" in capsys.readouterr().out

    def test_repo_baseline_is_all_zero(self):
        """PR 16's contract: the canonical plans audit CLEAN, and the
        checked-in baseline says so (a nonzero entry means someone
        banked a regression instead of fixing it)."""
        import json
        g = __import__("audit_gate")
        doc = json.load(open(g.BASELINE_PATH))
        assert set(doc["plans"]) == set(g.CANONICAL_PLANS)
        for name, entry in doc["plans"].items():
            assert entry["findings"] == 0, name
            assert entry["kinds"] == {}, name


# --------------------------------------------------------------------------
# tools/diff_failures.py (the tier-1 ritual, automated)
# --------------------------------------------------------------------------
class TestDiffFailures:
    def _write(self, tmp_path, name, text):
        p = tmp_path / name
        p.write_text(text)
        return str(p)

    def test_new_failure_exits_nonzero(self, tmp_path, capsys):
        d = __import__("diff_failures")
        new = self._write(tmp_path, "new.log",
                          "FAILED tests/a.py::t1 - boom\n"
                          "ERROR tests/b.py::t2\n.... 2 failed\n")
        old = self._write(tmp_path, "base.txt",
                          "# comment\ntests/a.py::t1\n"
                          "tests/c.py::t3\n")
        assert d.main([new, old]) == 1
        out = capsys.readouterr().out
        assert "NEW     tests/b.py::t2" in out
        assert "FIXED   tests/c.py::t3" in out

    def test_same_or_fewer_failures_pass(self, tmp_path):
        d = __import__("diff_failures")
        new = self._write(tmp_path, "new.log",
                          "FAILED tests/a.py::t1 - boom\n")
        old = self._write(tmp_path, "base.txt",
                          "tests/a.py::t1\ntests/c.py::t3\n")
        assert d.main([new, old]) == 0

    def test_write_baseline_round_trips(self, tmp_path):
        d = __import__("diff_failures")
        log = self._write(tmp_path, "run.log",
                          "FAILED tests/a.py::t1 - x\n"
                          "FAILED tests/b.py::t[2-3]\n")
        base = str(tmp_path / "base.txt")
        assert d.main([log, "--write-baseline", base]) == 0
        assert d.parse_baseline(base) == {"tests/a.py::t1",
                                          "tests/b.py::t[2-3]"}
        assert d.main([log, base]) == 0

    def test_repo_baseline_file_parses(self):
        d = __import__("diff_failures")
        ids = d.parse_baseline(d.DEFAULT_BASELINE)
        assert len(ids) <= 13         # the 2026-08-07 set; only shrinks
        assert all(id_.startswith("tests/") for id_ in ids)
