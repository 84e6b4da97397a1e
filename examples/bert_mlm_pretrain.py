"""BERT MLM+NSP pretraining steps (north-star workload #4 shape).

↔ the reference's SameDiff BERT training path. Here the whole train step
(attention backend picked by auto-dispatch, bf16-mixed matmuls, Adam,
donated state) is one compiled XLA program. Uses the tiny config off-TPU.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax

from deeplearning4j_tpu.models.bert import bert_base, bert_tiny, make_mlm_batch
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def main(quick: bool = False):
    on_tpu = jax.devices()[0].platform == "tpu"
    net = NeuralNetConfiguration(updater=Adam(1e-4), mixed_precision=on_tpu)
    model = bert_base(net=net) if (on_tpu and not quick) else bert_tiny(net=net)
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = make_mlm_batch(0, batch_size=8, seq_len=32,
                           vocab_size=model.config.vocab_size)
    losses = []
    for i in range(10 if quick else 40):
        ts, m = trainer.train_step(ts, batch)
        losses.append(float(m["total_loss"]))
    print(f"params: {model.num_params(trainer.variables(ts)):,}")
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    losses = main(ap.parse_args().quick)
    assert losses[-1] < losses[0], (losses[0], losses[-1])
