"""Measure the BASELINE.md ladder rows beyond the headline GPT bench:
MNIST-MLP steps/sec, BERT-base-ish jit tokens/sec, ResNet-50 images/sec.

Each row runs in a subprocess of its own under a timeout, one after
another (the parent stays off jax, so the row is the one process that
holds the chip), and prints one JSON line that names its platform; a
row that ran on a CPU says so and is not a speed number:
    python tools/bench_ladder.py            # all rows
    python tools/bench_ladder.py --run mnist
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROWS = ["mnist", "bert", "resnet50", "ernie_vil"]


def _bench_loop(step, iters=10):
    t0 = time.perf_counter()
    out = step()
    _force(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step()
    _force(out)
    return compile_s, (time.perf_counter() - t0) / iters


def _force(out):
    import jax
    jax.block_until_ready(out)


def run_row(row: str) -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.utils.compile_cache import (seed_cache_env,
                                                sync_compile_cache_for)
    seed_cache_env()
    import jax
    import jax.numpy as jnp
    import functools
    import numpy as np
    devs = jax.devices()
    platform = devs[0].platform
    sync_compile_cache_for(platform)
    on_chip = platform == "tpu"

    if row == "mnist":
        # BASELINE config 1: MNIST MLP train step (784-512-512-10)
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(784, 512), nn.ReLU(),
                            nn.Linear(512, 512), nn.ReLU(),
                            nn.Linear(512, 10))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        loss_fn = nn.CrossEntropyLoss()
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(256, 784).astype(np.float32))
        y = paddle.to_tensor(np.random.RandomState(1)
                             .randint(0, 10, 256).astype(np.int64))

        def step():
            loss = loss_fn(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss._value
        compile_s, dt = _bench_loop(step, iters=20)
        print(json.dumps({"row": "mnist_mlp", "metric": "steps_per_sec",
                          "value": round(1.0 / dt, 2),
                          "batch": 256, "compile_s": round(compile_s, 1),
                          "platform": platform}), flush=True)

    elif row == "bert":
        # BASELINE config 2: BERT-base MLM train step (the real encoder,
        # models/bert.py) via one jitted graph
        import optax
        from paddle_tpu.models.bert import (BertConfig, init_bert_params,
                                            bert_mlm_loss)
        cfg = BertConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=512, dtype=jnp.bfloat16)
        params = init_bert_params(cfg, jax.random.PRNGKey(0))
        opt = optax.adamw(1e-4)
        opt_state = opt.init(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 512), 0,
                                    cfg.vocab_size)
        # 15% MLM masking
        labels = jnp.where(
            jax.random.uniform(jax.random.PRNGKey(2), (16, 512)) < 0.15,
            tokens, -100)
        batch = {"tokens": tokens, "labels": labels}

        from paddle_tpu.models.facade import make_train_step

        @make_train_step
        def step(params, opt_state, batch):
            loss, g = jax.value_and_grad(
                functools.partial(bert_mlm_loss, cfg=cfg))(params, batch)
            upd, opt_state = opt.update(g, opt_state, params)
            return loss, optax.apply_updates(params, upd), opt_state

        def run():
            nonlocal params, opt_state
            loss, params, opt_state = step(params, opt_state, batch)
            return loss
        compile_s, dt = _bench_loop(run, iters=10)
        tps = 16 * 512 / dt
        n_params = sum(int(v.size)
                       for v in jax.tree_util.tree_leaves(params))
        flops_per_tok = 6.0 * n_params + 12.0 * 12 * 768 * 512
        rec = {"row": "bert_base_jit",
               "metric": "tokens_per_sec_per_chip",
               "value": round(tps, 1),
               "compile_s": round(compile_s, 1),
               "platform": platform}
        if on_chip:
            from paddle_tpu.device import chip_peaks
            rec["mfu"] = round(flops_per_tok * tps / chip_peaks(
                devs[0].device_kind).flops, 4)
        print(json.dumps(rec), flush=True)

    elif row == "resnet50":
        # BASELINE config 4: ResNet-50 fwd+bwd images/sec (functional core
        # jitted in one graph)
        import paddle_tpu as paddle
        from paddle_tpu.vision.models import resnet50
        paddle.seed(0)
        net = resnet50(num_classes=1000)
        import paddle_tpu.nn as nn
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=net.parameters())
        loss_fn = nn.CrossEntropyLoss()
        B = 64 if on_chip else 4
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(B, 3, 224, 224).astype(np.float32))
        y = paddle.to_tensor(np.random.RandomState(1)
                             .randint(0, 1000, B).astype(np.int64))

        # fwd+loss as ONE traced op (to_static): eager per-op dispatch
        # would mean 100+ separate compiles; the reference's analog row
        # also runs the conv stack as one graph
        net.train()
        fwd_loss = paddle.jit.to_static(lambda xx, yy: loss_fn(net(xx), yy))

        def step():
            loss = fwd_loss(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss._value
        compile_s, dt = _bench_loop(step, iters=5)
        print(json.dumps({"row": "resnet50", "metric": "images_per_sec",
                          "value": round(B / dt, 1), "batch": B,
                          "compile_s": round(compile_s, 1),
                          "platform": platform}), flush=True)

    elif row == "ernie_vil":
        # BASELINE config 5: ERNIE-ViL dual-encoder contrastive step,
        # samples/sec/chip (ViT-base image tower + BERT-base text tower)
        import optax
        from paddle_tpu.models.ernie_vil import (ErnieViLConfig,
                                                 init_ernie_vil_params,
                                                 contrastive_loss)
        cfg = ErnieViLConfig()
        B = 32 if on_chip else 2
        params = init_ernie_vil_params(cfg, jax.random.PRNGKey(0))
        opt = optax.adamw(1e-4)
        opt_state = opt.init(params)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, 64),
                                         0, cfg.text.vocab_size),
            "images": jax.random.normal(jax.random.PRNGKey(2),
                                        (B, 3, 224, 224), jnp.float32),
        }

        from paddle_tpu.models.facade import make_train_step

        @make_train_step
        def step(params, opt_state, batch):
            loss, g = jax.value_and_grad(functools.partial(
                contrastive_loss, cfg=cfg))(params, batch)
            upd, opt_state = opt.update(g, opt_state, params)
            return loss, optax.apply_updates(params, upd), opt_state

        def run():
            nonlocal params, opt_state
            loss, params, opt_state = step(params, opt_state, batch)
            return loss
        compile_s, dt = _bench_loop(run, iters=5)
        print(json.dumps({"row": "ernie_vil_dual_encoder",
                          "metric": "samples_per_sec_per_chip",
                          "value": round(B / dt, 1), "batch": B,
                          "compile_s": round(compile_s, 1),
                          "platform": platform}), flush=True)


def main():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for row in ROWS:
        print(f"[ladder] === {row} ===", file=sys.stderr, flush=True)
        try:
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--run", row],
                cwd=here, stdout=subprocess.PIPE, timeout=1500)
        except subprocess.TimeoutExpired:
            print(f"[ladder] {row}: TIMEOUT", file=sys.stderr, flush=True)
            continue
        out = res.stdout.decode().strip()
        line = next((ln for ln in reversed(out.splitlines())
                     if ln.startswith("{")), None)
        if res.returncode == 0 and line:
            print(line, flush=True)
        else:
            print(f"[ladder] {row}: FAILED rc={res.returncode}",
                  file=sys.stderr, flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--run":
        run_row(sys.argv[2])
    else:
        main()
