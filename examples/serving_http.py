"""Production model serving with the serving/ subsystem.

↔ the reference's serving story (ParallelInference behind a REST
endpoint), grown up: one ``ModelRegistry`` holds TWO models — a LeNet
digit classifier (array features) and a BERT sentiment classifier (dict
features {token_ids, segment_ids, mask}) — behind one ``ModelServer``
with warmup (all power-of-two batch buckets pre-compiled before /readyz
flips), admission control with per-request deadlines, Prometheus
/metrics, warmed hot-swap + rollback, and graceful drain.

Run, then:
  curl -s localhost:PORT/models
  curl -s localhost:PORT/v1/models/lenet:predict \
       -d '{"inputs": [[...784 floats...]]}'
  curl -s localhost:PORT/metrics

--quick serves concurrent requests against both models, hot-swaps the
LeNet entry mid-traffic, rolls it back, and exits (the examples-suite
smoke path).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse
import threading

import jax
import numpy as np

from deeplearning4j_tpu.models.bert import Bert, BertConfig
from deeplearning4j_tpu.models.lenet import lenet
from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.serving import ModelRegistry, ModelServer, ServingClient, spec
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

# Reuse the fine-tune example's task head + synthetic sentiment corpus.
from bert_finetune_classifier import VOCAB, BertClassifier, make_dataset

MAX_LEN = 12


def build_sentiment_model(quick: bool):
    """Fine-tune a tiny BERT classifier on the synthetic sentiment task."""
    tok = BertWordPieceTokenizerFactory({t: i for i, t in enumerate(VOCAB)})
    bert = Bert(BertConfig(
        vocab_size=len(VOCAB), hidden=32, num_layers=1, num_heads=2,
        intermediate=64, max_position=MAX_LEN, dropout=0.0,
        net=NeuralNetConfiguration(updater=Adam(2e-3), seed=0)))
    model = BertClassifier(bert, num_classes=2)
    trainer = Trainer(model)
    ts = trainer.init_state()
    x, y = make_dataset(tok, 64 if quick else 192, MAX_LEN, seed=0)
    for _ in range(25 if quick else 120):
        ts, _ = trainer.train_step(ts, {"features": x, "labels": y})
    return tok, model, trainer.variables(ts)


def build_server(port: int = 0, quick: bool = False):
    registry = ModelRegistry()

    lenet_model = lenet()
    registry.register(
        "lenet", lambda v, x: lenet_model.output(v, x),
        lenet_model.init(seed=0), input_spec=spec((28, 28, 1)),
        version="v1", mode="batched", max_batch_size=16)

    tok, sent_model, sent_vars = build_sentiment_model(quick)
    registry.register(
        "sentiment",
        lambda v, x: jax.nn.softmax(sent_model.apply(v, x)[0]),
        sent_vars,
        input_spec={"token_ids": spec((MAX_LEN,), np.int32),
                    "segment_ids": spec((MAX_LEN,), np.int32),
                    "mask": spec((MAX_LEN,), np.float32)},
        version="v1", mode="batched", max_batch_size=4)

    # cache=True arms the exact-match response cache: identical
    # repeats are answered before a batch slot is taken, invalidated
    # automatically on hot-swap/rollback
    server = ModelServer(registry, port=port, cache=True)
    return server, registry, tok, lenet_model


def main(quick: bool = False):
    server, registry, tok, lenet_model = build_server(quick=quick)
    server.start(warm=True)  # pre-compiles every batch bucket, then ready
    print(f"serving on {server.url}  "
          f"(models: {', '.join(registry.names())})")

    if not quick:
        try:
            threading.Event().wait()
        except KeyboardInterrupt:
            server.stop()
        return

    client = ServingClient(server.url)
    assert client.ready()["ready"], "warmup must flip /readyz before traffic"
    rng = np.random.default_rng(0)

    # -- concurrent clients against BOTH models, mixed batch sizes --------
    results, errors = [], []

    def call_lenet(i):
        # per-thread Generator: np Generators are not thread-safe
        x = np.random.default_rng(i).normal(
            size=(1 + i % 3, 784)).astype(np.float32)
        try:
            results.append(("lenet", client.predict("lenet", x)))
        except Exception as e:  # noqa: BLE001 - smoke collects, then asserts
            errors.append(e)

    def call_sentiment(text):
        feats = {k: v[None] for k, v in
                 tok.encode(text, max_len=MAX_LEN).items()}
        try:
            results.append(("sentiment", client.predict("sentiment", feats)))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=call_lenet, args=(i,))
               for i in range(6)]
    threads += [threading.Thread(target=call_sentiment, args=(t,))
                for t in ("the movie was really great",
                          "awful plot and terrible acting")]
    for th in threads:
        th.start()

    # -- warmed hot-swap while those clients are in flight -----------------
    v2 = registry.deploy("lenet", lenet_model.init(seed=1), version="v2")
    for th in threads:
        th.join()
    assert not errors, f"smoke requests failed: {errors[:3]}"
    assert len(results) == 8
    print(f"8 concurrent requests served across 2 models "
          f"(lenet now {v2})")
    for name, r in results:
        if name == "sentiment":
            probs = np.asarray(r["outputs"])[0]
            print(f"  sentiment p(positive)={probs[1]:.3f}")

    # served by v2 after the swap, by v1 again after rollback
    x1 = rng.normal(size=(1, 784)).astype(np.float32)
    assert client.predict("lenet", x1)["version"] == "v2"
    assert registry.rollback("lenet") == "v1"
    assert client.predict("lenet", x1)["version"] == "v1"
    print("hot-swap v1 -> v2 -> rollback v1: versions observed correctly")

    # -- exact-match response cache: a repeat costs no batch slot ----------
    xc = rng.normal(size=(1, 784)).astype(np.float32)
    first = client.predict("lenet", xc)
    again = client.predict("lenet", xc)
    assert again.get("cached") is True
    assert again["outputs"] == first["outputs"]
    print("repeat request served from the response cache "
          f"(hits={server.response_cache.describe()['hits']})")

    metrics = client.metrics_text()
    for series in ("serving_requests_total", "serving_request_latency_seconds",
                   "serving_batch_occupancy_bucket"):
        assert series in metrics, f"missing metric {series}"
    print("metrics:", len(metrics.splitlines()), "exposition lines")

    drained = server.stop()  # graceful drain
    assert drained and not server.readiness()["ready"]
    print("drained and stopped cleanly")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
