"""Fine-tune the BERT encoder for sequence classification, from raw
strings: FasterTokenizer (native WordPiece) → bert_encode → pooled
classifier — the text stack end-to-end.

    python examples/finetune_bert_classifier.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# examples demo on CPU devices by default, at toy sizes;
# run with PADDLE_TPU_EXAMPLE_BACKEND=native for real chips
if os.environ.get("PADDLE_TPU_EXAMPLE_BACKEND", "cpu") == "cpu":
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(1), "could not pin the CPU backend"

import functools
import numpy as np
import jax
import jax.numpy as jnp
import optax

from paddle_tpu.text import FasterTokenizer
from paddle_tpu.models.bert import (BertConfig, init_bert_params,
                                    init_cls_head, bert_cls_loss)

SENTENCES = [
    ("the movie was great fun", 1), ("a lazy boring film", 0),
    ("great acting and fun plot", 1), ("boring and lazy writing", 0),
    ("fun from start to finish", 1), ("a great watch", 1),
    ("lazy plot , boring cast", 0), ("boring , skip it", 0),
]
VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "the", "movie", "was",
         "great", "fun", "a", "lazy", "boring", "film", "acting", "and",
         "plot", "from", "start", "to", "finish", "watch", "cast",
         "writing", "skip", "it", ","]


def main():
    tok = FasterTokenizer({t: i for i, t in enumerate(VOCAB)})
    enc = tok([s for s, _ in SENTENCES], max_seq_len=12)
    labels = jnp.asarray([y for _, y in SENTENCES])
    batch = {"tokens": jnp.asarray(enc["input_ids"]),
             "attention_mask": jnp.asarray(enc["attention_mask"]),
             "labels": labels}

    cfg = BertConfig(vocab_size=len(VOCAB), hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=32, dtype=jnp.float32)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    head = init_cls_head(cfg, 2, jax.random.PRNGKey(1))

    def loss_fn(both, batch):
        return bert_cls_loss(both[0], both[1], batch, cfg)

    opt = optax.adam(5e-3)
    both = (params, head)
    state = opt.init(both)
    lf = jax.jit(loss_fn)
    gf = jax.jit(jax.grad(loss_fn))
    for it in range(30):
        g = gf(both, batch)
        upd, state = opt.update(g, state)
        both = jax.tree_util.tree_map(lambda p, u: p + u, both, upd)
        if it % 10 == 0:
            print(f"step {it}: loss={float(lf(both, batch)):.4f}")
    print(f"final loss={float(lf(both, batch)):.4f}")


if __name__ == "__main__":
    main()
