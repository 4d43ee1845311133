"""Offline TPU sweep for the bench train step: attention impl x remat
policy x batch (x flash blocks via env).

Variants run IN-PROCESS inside one child (one interpreter + jax import +
backend init for the whole list; the orchestrator itself stays off jax,
so the child is the one process that holds the chip). The orchestrator
watches the child's stdout and respawns it with the remaining variants
if it crashes (e.g. a Mosaic abort) or stalls past the per-variant
budget, dropping only the variant that was in flight. Every
variant's env (kill switches, impl selector, flash blocks) is applied
around its own run from a whole-env snapshot, and every gate re-reads
env per trace, so in-process racing is sound.

Each variant prints one JSON line; the parent prints a ranked summary
(by tokens/sec — batches differ) at the end, and hands the TPU rows to
kernels.registry.adopt_sweep_winner, which persists the best one to
perf/sweep_winner.json and the kernel registry if the plausibility gate
admits it.

Usage:  python tools/sweep_gpt_step.py                 # orchestrate
        python tools/sweep_gpt_step.py --run-list '<json>'   # internal
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

JAXBWD = {"PADDLE_TPU_DISABLE_PALLAS_BWD": "1"}
XLA_ATTN = {"PADDLE_TPU_DISABLE_PALLAS_ATTN": "1", **JAXBWD}

VARIANTS = [
    # name, remat, policy, (bq, bk, bwd_q, bwd_k), extra env[, batch]
    # Ordered by the round-4 ablation matrix (perf/window_*/ablate.out):
    # no-remat at reduced batch beat every remat variant per-token
    # (42.5 ms/sample at B=4 vs 53.4 best remat at B=8), and attention
    # is ~66% of the step. Default blocks are the round-4 autotune
    # winners (fwd 512/256; bwd 128/128). Explicit FLASH_BLOCK env
    # settings outrank the autotune cache, so these tuples really do
    # control every variant.
    # HIGHEST-VALUE HYPOTHESES FIRST: a run cut short still has them.
    # all_but_mlp: nested checkpoint around just the dense FFN (block
    # otherwise unremat'd) — near-no-remat memory at full batch (true
    # no-remat OOMs at B=8); splash = upstream block-sparse kernel (the
    # homegrown kernel measured ~6 TF/s effective in the ablation)
    ("allbutmlp-splash-b8", True, "all_but_mlp", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "splash"}),
    # cheapest remat x the attention impl the window-1 ablation crowned
    # (xla 399.7 ms vs 427+ for every pallas fwd) — the most likely
    # winner cross, so it races near the front
    ("allbutmlp-xlaattn-b8", True, "all_but_mlp", (512, 256, 128, 128),
     XLA_ATTN),
    ("allbutmlp-b8", True, "all_but_mlp", (512, 256, 128, 128), JAXBWD),
    ("splash-dotsflash-b8", True, "dots_flash", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "splash"}),
    ("noremat-b4", False, "dots", (512, 256, 128, 128), JAXBWD, 4),
    ("splash-noremat-b4", False, "dots", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "splash"}, 4),
    # same-window baseline for honest deltas vs r02/r03 numbers
    ("dots-jaxbwd", True, "dots", (512, 256, 128, 128), JAXBWD),
    ("jaxflash-dotsflash-b8", True, "dots_flash", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "jax_flash"}),
    # opportunistic: larger batch if the memory shape allows (OOM is
    # caught and the variant skipped)
    ("allbutmlp-splash-b12", True, "all_but_mlp", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "splash"}, 12),
    ("jaxflash-noremat-b4", False, "dots", (512, 256, 128, 128),
     {"PADDLE_TPU_ATTN_IMPL": "jax_flash"}, 4),
    ("noremat-xlaattn-b4", False, "dots", (512, 256, 128, 128),
     XLA_ATTN, 4),
    ("noremat-b6", False, "dots", (512, 256, 128, 128), JAXBWD, 6),
    ("noremat-pallasbwd-b4", False, "dots", (512, 256, 128, 128), {}, 4),
    # autotune's bwd microbench flipped the round-3 result (Pallas bwd
    # 116 ms vs jax-level 170.6): re-litigate at step level, tuned blocks
    ("dots-pallasbwd-tuned", True, "dots", (512, 256, 128, 128), {}),
    ("dotsflash-jaxbwd", True, "dots_flash", (512, 256, 128, 128), JAXBWD),
    ("xlaattn-dots-b8", True, "dots", (512, 256, 128, 128), XLA_ATTN, 8),
    ("noremat-b5", False, "dots", (512, 256, 128, 128), JAXBWD, 5),
    # host-offloaded dot saves: HBM headroom without recompute
    ("offload-jaxbwd", True, "offload_dots", (512, 256, 128, 128), JAXBWD),
    ("dotsflash-jaxbwd-unroll2", True, "dots_flash", (512, 256, 128, 128),
     {**JAXBWD, "SWEEP_SCAN_UNROLL": "2"}),
    ("noremat-xlaattn-b6", False, "dots", (512, 256, 128, 128),
     XLA_ATTN, 6),
    ("dots-jaxbwd-noCE", True, "dots", (512, 256, 128, 128),
     {**JAXBWD, "PADDLE_TPU_DISABLE_PALLAS_CE": "1"}),
]

MODEL = dict(vocab_size=32768, hidden_size=1024, num_layers=24,
             num_heads=16, max_seq_len=1024)
BATCH, SEQ, ITERS = 8, 1024, 8
VARIANT_BUDGET_S = 900      # stall bound: no output for this long → kill


def _specs() -> list:
    """VARIANTS table → self-contained spec dicts (env folded in)."""
    specs = []
    for name, remat, policy, (bq, bk, bwq, bwk), extra, *rest in VARIANTS:
        env = {
            "PADDLE_TPU_FLASH_BLOCK_Q": str(bq),
            "PADDLE_TPU_FLASH_BLOCK_K": str(bk),
            "PADDLE_TPU_FLASH_BLOCK_BWD_Q": str(bwq),
            "PADDLE_TPU_FLASH_BLOCK_BWD_K": str(bwk),
            **extra,
        }
        specs.append({"name": name, "remat": remat, "policy": policy,
                      "env": env, "batch": rest[0] if rest else BATCH,
                      "model": MODEL, "seq": SEQ})
    return specs


def run_one(spec: dict) -> None:
    """One variant, in the current process; env applied from a snapshot
    (all kernel gates re-read env per trace)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       init_opt_state, train_step)
    snapshot = dict(os.environ)
    try:
        os.environ.update(spec.get("env", {}))
        devs = jax.devices()
        cfg = GPTConfig(sequence_parallel=False, remat=spec["remat"],
                        remat_policy=spec["policy"], dtype=jnp.bfloat16,
                        scan_unroll=int(os.environ.get(
                            "SWEEP_SCAN_UNROLL", "1")),
                        **spec.get("model", MODEL))
        batch = int(spec.get("batch", BATCH))
        seq = int(spec.get("seq", SEQ))
        params = init_gpt_params(cfg, jax.random.PRNGKey(0))
        opt_state = init_opt_state(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, seq + 1), 0, cfg.vocab_size)
        from paddle_tpu.models.facade import make_train_step
        step = make_train_step(train_step, cfg=cfg, lr=1e-4)
        t0 = time.perf_counter()
        loss, params, opt_state = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss, params, opt_state = step(params, opt_state, tokens)
        jax.block_until_ready((loss, params, opt_state))
        dt = (time.perf_counter() - t0) / ITERS
        print(json.dumps({"name": spec["name"],
                          "ms_per_step": round(dt * 1e3, 2),
                          "tokens_per_sec": round(batch * seq / dt, 1),
                          "batch": batch, "compile_s": round(compile_s, 1),
                          "platform": devs[0].platform}), flush=True)
    finally:
        os.environ.clear()
        os.environ.update(snapshot)


def run_list(specs: list) -> None:
    """Child entry: race every spec in this one process. A failed
    variant (OOM, Mosaic error) is reported and skipped; a hard crash
    ends the process and the orchestrator respawns with the rest."""
    if os.environ.get("SWEEP_PIN_CPU") == "1":
        # dev/smoke hook: run the list on the CPU
        from paddle_tpu.device import pin_cpu
        pin_cpu(1)
    for spec in specs:
        print(f"[sweep-child] === {spec['name']} ===", file=sys.stderr,
              flush=True)
        if spec.get("_crash"):      # orchestrator-respawn test hook
            os._exit(9)
        try:
            run_one(spec)
        except Exception as e:
            print(json.dumps({"name": spec["name"],
                              "error": repr(e)[:200]}), flush=True)


def main() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pending = _specs()
    results, failed = [], []

    while pending:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--run-list",
             json.dumps(pending)],
            cwd=here, stdout=subprocess.PIPE)
        done_this_child = 0
        import select
        fd = proc.stdout.fileno()
        buf = b""
        # the stall deadline is measured from the last ACCEPTED record —
        # stray stdout noise (jax/libtpu retry chatter) must not keep a
        # hung variant alive, and raw os.read avoids the buffered-
        # readline-vs-select trap where a completed record sits unread
        last_rec = time.time()

        def handle(raw: bytes) -> None:
            nonlocal done_this_child, last_rec
            # a record is only the next pending variant's line — noise
            # must neither crash the sweep nor desync the pending slice
            try:
                rec = json.loads(raw.decode(errors="replace").strip())
            except ValueError:
                return
            if (done_this_child >= len(pending)
                    or not isinstance(rec, dict)
                    or rec.get("name") !=
                    pending[done_this_child]["name"]):
                return
            done_this_child += 1
            last_rec = time.time()
            if "error" in rec:
                failed.append(rec)
                print(f"[sweep] {rec['name']}: FAILED "
                      f"{rec['error'][:80]}", file=sys.stderr, flush=True)
            else:
                results.append(rec)
                print(f"[sweep] {rec['name']}: {rec['ms_per_step']} "
                      f"ms/step ({rec['tokens_per_sec']} tok/s)",
                      file=sys.stderr, flush=True)

        while True:
            r, _, _ = select.select([fd], [], [], 10.0)
            if r:
                chunk = os.read(fd, 65536)
                if not chunk:
                    if buf:
                        handle(buf)            # unterminated final line
                    break                      # EOF: child exited
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    handle(line)
            elif proc.poll() is not None:
                if buf:
                    handle(buf)
                break
            # checked EVERY iteration — stdout noise must not postpone
            # the deadline (only accepted records reset last_rec)
            if time.time() - last_rec > VARIANT_BUDGET_S:
                # in-flight variant hung: kill, drop it, respawn
                proc.kill()
                proc.wait()
                break
        if proc.poll() is None:
            proc.wait()
        survived = pending[done_this_child:]
        if proc.returncode == 0 and done_this_child >= len(pending):
            pending = []
        elif survived:
            dropped = survived[0]
            print(f"[sweep] child died/stalled on {dropped['name']}; "
                  f"dropping it, {len(survived) - 1} remain",
                  file=sys.stderr, flush=True)
            failed.append({"name": dropped["name"],
                           "error": "child crashed or stalled"})
            pending = survived[1:]
        else:
            pending = []

    # batches differ across variants: rank by throughput, not step time
    results.sort(key=lambda r: -r["tokens_per_sec"])
    print(json.dumps({"ranked": results, "failed": failed}, indent=1),
          flush=True)
    # every child has exited, so importing the package here starts no
    # second process on the chip; rows that did not run on a TPU are
    # ignored by the adoption itself
    sys.path.insert(0, here)
    from paddle_tpu.kernels import registry
    note = registry.adopt_sweep_winner(
        results, time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()), _specs())
    print(f"[sweep] adoption: {note}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--run-list":
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        run_list(json.loads(sys.argv[2]))
    else:
        main()
