"""The harness driven end to end on tiny float32 cells on the CPU, through
run.drive() — the test-only entry that skips the look for a chip (the
command has no CPU mode) — and, with the timed path broken underneath,
`correct` seen to come out false (ISSUE 27, "How `correct` is decided")."""
import json

import jax
import numpy as np
import pytest

from benchmark import harness, run as bench_run
from benchmark.correct import serve as correct_serve
from benchmark.correct import train as correct_train
from benchmark.correct.judge import judge
from benchmark.runners import serve as serve_runner
from benchmark.tiny import tiny_cell

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
SEED = 2 ** 31 + 12345


def _drive(capsys, cell, trace=False, seconds=1.0, **kw):
    rc = bench_run.drive(cell, SEED, seconds, trace, jax.devices()[:1], **kw)
    assert rc == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    return line, captured.err


@pytest.mark.parametrize("name", CELLS)
def test_a_tiny_cell_prints_the_contracts_last_line(name, capsys):
    cell = tiny_cell(name)
    line, err = _drive(capsys, cell)
    assert LINE_KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for m in cell["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["notes"]["compiles_in_window"] == 0
    # each number compared, beside its limit, ends standard error
    tail = err.strip().splitlines()[-len(line["compared"]) - 1:]
    assert tail[-1].startswith("correct = True")
    for name_, (value, limit) in line["compared"].items():
        assert value <= limit
        assert any(t.startswith(f"compared {name_} = ") for t in tail)


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_tiny_cell_reports_per_layer_metrics_only(name, capsys):
    cell = tiny_cell(name)
    line, _ = _drive(capsys, cell, trace=True)
    per_layer = {m["name"] for m in cell["per_layer"]}
    # the CPU has no device plane and no peaks: shares of a trace or of a
    # peak are left out, never reported as 0
    assert set(line["metrics"]) <= per_layer
    assert set(line["metrics"]) == {
        n for n in per_layer
        if "idle" not in n and "mfu" not in n and "hbm" not in n}
    assert "busy_s" not in line["device"]


def test_the_train_runner_takes_its_chips_from_the_cell(capsys):
    """A four-chip training cell is data: the runner plans for the cell's
    chips (plan_train(cfg, 4, batch) -> dp4 here) on four of the suite's
    virtual devices, and the reference still agrees."""
    cell = tiny_cell("gpt3-350m-train.steady")
    cell["chips"] = 4
    rc = bench_run.drive(cell, SEED, 0.5, False, jax.devices()[:4])
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["device"]["count"] == 4
    assert "plan dp4" in captured.err


# ---------------------------------------------------- faults: training
def _state_unchanged(step):
    def broken(params, opt, batch):
        copy = jax.tree_util.tree_map(lambda x: x + 0, (params, opt))
        loss, _, _ = step(*copy, batch)
        return loss, params, opt
    return broken


def _half_batch(step):
    def broken(params, opt, batch):
        batch = np.array(batch)
        half = len(batch) // 2
        batch[half:] = batch[:half]        # the mean is over the first half
        return step(params, opt, batch)
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(fault, capsys):
    cell = tiny_cell("gpt3-350m-train.steady")
    line, err = _drive(capsys, cell, tamper=fault)
    assert line["correct"] is False
    assert "OVER" in err
    if fault is _state_unchanged:
        # nothing moved and Adam's m is still nought: both read 1
        assert line["compared"]["update_norm_gap"][0] == pytest.approx(1.0)
        assert line["compared"]["grad_direction_gap"][0] == \
            pytest.approx(1.0)


# ----------------------------------------------------- faults: serving
class _AlteredTokens:
    """A router whose every served token is altered where it is produced."""

    def __init__(self, router, vocab):
        self._router, self._vocab, self._seen = router, vocab, {}

    def __getattr__(self, name):
        return getattr(self._router, name)

    def submit(self, *a, **kw):
        req = self._router.submit(*a, **kw)
        self._seen[id(req)] = (req, 0)
        return req

    def step(self):
        out = self._router.step()
        for key, (req, n) in list(self._seen.items()):
            for i in range(n, len(req.tokens)):
                req.tokens[i] = (req.tokens[i] + 1 + i) % self._vocab
            self._seen[key] = (req, len(req.tokens))
        return out


@pytest.mark.parametrize("name", [c for c in CELLS if "serve" in c])
def test_an_altered_token_is_not_correct(name, capsys):
    cell = tiny_cell(name)
    vocab = cell["config"]["model"]["vocab_size"]
    line, err = _drive(capsys, cell,
                       tamper=lambda r: _AlteredTokens(r, vocab))
    assert line["correct"] is False and "OVER" in err
    assert line["compared"]["logit_gap_mean"][0] > \
        line["compared"]["logit_gap_mean"][1]


# ------------------------------------------------------------ controls
# The controls are read on the chip at the cells' own sizes (PERF.md); here
# they are kept at a size a test run can hold: wide enough that float8
# operands leave the reference by more than the cells' limits allow.
def test_the_fp8_control_fails_the_training_cells_limits():
    cell = tiny_cell("gpt3-350m-train.steady")
    config = cell["config"]
    config["model"].update(num_layers=4, hidden_size=256, ffn_hidden=1024,
                           vocab_size=8192, max_seq_len=128)
    config["sizing"]["seq_len"] = 128
    gen = harness.load_generator(cell["traffic"]).make(
        cell["traffic"], config, SEED, 1.0)
    batches = [gen.next_batch() for _ in range(correct_train.STEPS)]
    reference = correct_train.reference_readings(config, SEED, batches)
    control = correct_train.reference_readings(config, SEED, batches,
                                               precision="fp8")
    verdict = judge(correct_train.compare(control, reference),
                    cell["limits"])
    assert not verdict["grad_direction_gap"]["ok"]
    same = judge(correct_train.compare(reference, reference), cell["limits"])
    assert all(v["ok"] and v["value"] == 0 for v in same.values())


def test_the_fp8_control_fails_the_serving_cells_limit():
    cell = tiny_cell("gpt3-1.3b-serve.offline")
    config = cell["config"]
    config["model"].update(num_layers=12, hidden_size=512, ffn_hidden=2048,
                           vocab_size=8192)
    rng = np.random.default_rng(5)
    sample = []
    for n in (40, 24, 12):
        prompt = rng.integers(0, 8192, n).astype(np.int32)
        sample.append({"prompt": prompt, "max_new": 16,
                       "tokens": [0] * 16})
    # serve the reference's own greedy tokens: gap 0 by construction
    from benchmark.weights import make_gpt_params
    params = make_gpt_params(config["model"], SEED)
    for req in sample:
        for j in range(16):
            rows = correct_serve.served_rows(
                params, config["model"], req["prompt"],
                req["tokens"][:j + 1])
            req["tokens"][j] = int(np.asarray(rows[j]).argmax())
    out = correct_serve.reference_numbers(config, SEED, sample,
                                          control="fp8")
    assert out["logit_gap_mean"] == 0.0 == out["logit_gap_max"]
    assert out["served_tokens_compared"] == 48
    limit = cell["limits"]["numbers"]["logit_gap_mean"]["limit"]
    assert out["control_logit_gap_mean"] > limit
    # a request cut short, or a token outside the vocabulary, is no answer
    sample[0]["tokens"] = sample[0]["tokens"][:-1]
    assert correct_serve.reference_numbers(config, SEED, sample)[
        "logit_gap_mean"] == float("inf")


# ------------------------------------------------------------ the clock
class _FakeRequest:
    def __init__(self):
        self.tokens, self.done, self.finish_reason = [], False, None


class _FakeRouter:
    """Every step takes `tick` seconds of the fake clock and gives each
    request one token; a request ends after 3."""

    def __init__(self, clock, tick=0.01):
        self.clock, self.tick, self.reqs = clock, tick, []

    def submit(self, prompt, max_new):
        self.reqs.append(_FakeRequest())
        return self.reqs[-1]

    def has_work(self):
        return any(not r.done for r in self.reqs)

    def step(self):
        self.clock.t += self.tick
        for r in self.reqs:
            if not r.done:
                r.tokens.append(1)
                if len(r.tokens) == 3:
                    r.done, r.finish_reason = True, "length"


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Stream:
    backlog, queued_per_slot = False, 0

    def __init__(self, dues):
        self.dues, self.i, self.n_open = dues, 0, len(dues)

    def exhausted(self):
        return self.i >= len(self.dues)

    def peek_due(self):
        return self.dues[self.i]

    def next(self):
        self.i += 1
        return self.dues[self.i - 1], np.zeros(4, np.int32), 3


def _ttfts(stall_before_second: float):
    clock = _Clock()
    loop = serve_runner.Loop(_FakeRouter(clock), _Stream([0.0, 0.05]), 4,
                             tiny_cell(CELLS[1])["config"]["model"],
                             clock=clock)
    loop.t0 = 0.0
    while loop.live or not loop.stream.exhausted():
        if clock.t >= 0.04 and stall_before_second:
            clock.t += stall_before_second       # the host stalls here
            stall_before_second = 0.0
        if not loop.router.has_work() and loop.stream.peek_due() > clock.t:
            clock.t = loop.stream.peek_due()
            continue
        loop.tick()
    return [r["ttft_ms"] for r in serve_runner.request_rows(loop, loop.all)]


def test_the_open_loop_clock_measures_from_the_due_time():
    plain, stalled = _ttfts(0.0), _ttfts(1.0)
    assert plain[0] == pytest.approx(10.0) == stalled[0]
    # the second request was DUE at 0.05 s; a 1-s stall before its submit
    # makes its first token 1 s later, and its TTFT says so
    assert stalled[1] - plain[1] == pytest.approx(1000.0, abs=50.0)
