"""BERT fine-tuning for text classification, end to end.

↔ the reference's BERT workflow (import → fine-tune with a task head):
WordPiece-tokenize raw text (nlp/wordpiece.py, HF-oracle-pinned), encode
to the model's [CLS]/[SEP] feature dict, put a classifier head on the
pooled [CLS] state, train with the standard Trainer, evaluate with the
standard Evaluation stack. The task is synthetic sentiment (word
patterns), so it runs offline and converges in seconds.

Also shows the model-protocol extension point: any object with
init/loss_fn/apply drives Trainer — here a small adapter that reuses the
Bert encoder + pooler and swaps the pretraining heads for a task head.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.evaluation import Evaluation
from deeplearning4j_tpu.models.bert import Bert, BertConfig
from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.ops import nn as opsnn
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

GOOD = ["good", "great", "excellent", "wonderful", "superb"]
BAD = ["bad", "awful", "terrible", "poor", "dreadful"]
FILLER = ["the", "movie", "was", "plot", "acting", "and", "a", "bit",
          "really", "quite", "film", "story"]
VOCAB = (["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
         + GOOD + BAD + FILLER + ["##s", "##ly"])


class BertClassifier:
    """Task head over the Bert encoder: pooled [CLS] → num_classes."""

    def __init__(self, bert: Bert, num_classes: int):
        self.bert = bert
        self.net = bert.net
        self.num_classes = num_classes

    def init(self, seed=None):
        seed = self.net.seed if seed is None else seed
        v = self.bert.init(seed=seed)
        k = jax.random.key(seed + 1)
        h = self.bert.config.hidden
        v["params"]["classifier"] = {
            "W": 0.02 * jax.random.normal(k, (h, self.num_classes)),
            "b": jnp.zeros((self.num_classes,)),
        }
        return v

    def _logits(self, params, features, *, train, rng):
        hidden = self.bert.encode(params, features, train=train, rng=rng)
        pooled = jnp.tanh(opsnn.linear(
            hidden[:, 0, :], params["pooler"]["W"], params["pooler"]["b"]))
        return opsnn.linear(pooled, params["classifier"]["W"],
                            params["classifier"]["b"])

    def loss_fn(self, params, state, batch, rng=None):
        lg = self._logits(params, batch["features"], train=True, rng=rng)
        loss = losses.sparse_softmax_cross_entropy(lg, batch["labels"])
        return loss, (state, {"loss": loss})

    def apply(self, variables, features, *, train=False, rng=None):
        return self._logits(variables["params"], features, train=train,
                            rng=rng), variables.get("state", {})


def make_dataset(tok, n, max_len, seed):
    r = np.random.default_rng(seed)
    rows, ys = [], []
    for _ in range(n):
        y = int(r.integers(0, 2))
        words = list(r.choice(FILLER, 5)) + [r.choice(GOOD if y else BAD)]
        r.shuffle(words)
        rows.append(tok.encode(" ".join(words), max_len=max_len))
        ys.append(y)
    feats = {k: np.stack([row[k] for row in rows]) for k in rows[0]}
    return feats, np.asarray(ys, np.int32)


def main(quick: bool = False):
    tok = BertWordPieceTokenizerFactory({t: i for i, t in enumerate(VOCAB)})
    max_len = 16
    bert = Bert(BertConfig(
        vocab_size=len(VOCAB), hidden=64, num_layers=2, num_heads=2,
        intermediate=128, max_position=max_len, dropout=0.1,
        net=NeuralNetConfiguration(updater=Adam(1e-3), seed=0)))
    model = BertClassifier(bert, num_classes=2)
    trainer = Trainer(model)
    ts = trainer.init_state()

    xtr, ytr = make_dataset(tok, 96 if quick else 256, max_len, seed=0)
    xte, yte = make_dataset(tok, 64, max_len, seed=1)
    steps = 40 if quick else 150
    for i in range(steps):
        ts, m = trainer.train_step(ts, {"features": xtr, "labels": ytr})
        if i % 20 == 0:
            print(f"step {i}: loss {float(jax.device_get(m['loss'])):.3f}")

    logits, _ = model.apply(trainer.variables(ts), xte)
    ev = Evaluation(num_classes=2)
    ev.eval(jax.nn.one_hot(yte, 2), jax.nn.softmax(logits))
    print(ev.stats())
    acc = ev.accuracy()
    print(f"test accuracy: {acc:.3f}")
    assert acc > 0.9, "fine-tune failed to learn the synthetic task"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
