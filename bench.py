"""GPT-350M causal-LM training throughput on the device jax finds.

Prints ONE JSON line: the fused train step (fwd+bwd+AdamW) in tokens/s
per chip, with the platform, `device_kind` and device count it ran on.
One configuration, one process, one line. It is not the benchmark (that
is ROADMAP S0); it is the quickest number that says the training path
still runs on the chip.

There is no fallback. On anything but a TPU it exits non-zero, unless
`--cpu` is given: then it runs a cut-down check configuration on the
CPU, says `"backend": "cpu"` under a metric name of its own, and carries
no MFU. A failure of the step is a failure of the command.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

# (model kwargs, batch, seq, timed iterations)
CHIP_CONFIG = (dict(vocab_size=32768, hidden_size=1024, num_layers=24,
                    num_heads=16, max_seq_len=1024, remat=True,
                    remat_policy="dots", dtype="bfloat16"), 8, 1024, 10)
# --cpu only: small enough for a CPU to finish; its numbers say that the
# path runs, nothing about speed
CPU_CHECK_CONFIG = (dict(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=4, max_seq_len=128, remat=False,
                         dtype="float32"), 2, 64, 3)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU and run the cut-down check "
                         "configuration (no MFU)")
    args = ap.parse_args(argv)

    if args.cpu:
        from paddle_tpu.device import pin_cpu
        if not pin_cpu(1):
            _log("could not pin the CPU platform")
            return 1
    from paddle_tpu.utils.compile_cache import (seed_cache_env,
                                                sync_compile_cache_for)
    seed_cache_env()

    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    _log(f"backend: {platform} x{len(devs)} ({kind})")
    if platform != "tpu" and not args.cpu:
        _log(f"no TPU (found {platform!r}); pass --cpu for the CPU check")
        return 1
    sync_compile_cache_for(platform)

    from paddle_tpu.cost_model import train_flops_per_token
    from paddle_tpu.device import chip_peaks
    from paddle_tpu.models.facade import make_train_step
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       init_opt_state, train_step)

    on_chip = platform == "tpu"
    kw, batch, seq, iters = CHIP_CONFIG if on_chip else CPU_CHECK_CONFIG
    kw = dict(kw, dtype=jnp.bfloat16 if kw["dtype"] == "bfloat16"
              else jnp.float32)
    # an unknown chip fails here, before any time is spent
    peak = chip_peaks(kind).flops if on_chip else None
    cfg = GPTConfig(sequence_parallel=False, **kw)
    _log(f"{cfg.num_layers}L x {cfg.hidden_size}d, batch={batch}, "
         f"seq={seq}")

    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                0, cfg.vocab_size)
    n_params = sum(int(v.size) for v in params.values())
    step = make_train_step(train_step, cfg=cfg, lr=1e-4)

    t0 = time.perf_counter()
    loss, params, opt_state = step(params, opt_state, tokens)
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0
    _log(f"compile+first {compile_s:.1f}s (loss={float(loss):.4f})")
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt_state = step(params, opt_state, tokens)
    jax.block_until_ready((loss, params, opt_state))
    dt = (time.perf_counter() - t0) / iters

    tps = batch * seq / dt
    rec = {
        "metric": ("gpt_train_tokens_per_sec_per_chip" if on_chip
                   else "gpt_train_cpu_check_tokens_per_sec"),
        "value": round(tps, 1),
        "unit": "tokens/s",
        "backend": platform,
        "device_kind": kind,
        "device_count": len(devs),
        "config": "gpt-350m" if on_chip else "cpu-check",
        "batch": batch,
        "seq": seq,
        "ms_per_step": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 1),
    }
    if on_chip:
        mfu = train_flops_per_token(n_params, cfg.num_layers,
                                    cfg.hidden_size, seq) * tps / peak
        rec["mfu"] = round(mfu, 4)
        # the source's >=45% MFU yardstick (BASELINE.json)
        rec["vs_baseline"] = round(mfu / 0.45, 4)
        stats = devs[0].memory_stats() or {}
        rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
