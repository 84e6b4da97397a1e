"""A benchmark of tiny cells in a temporary directory, made of new files
alone: a manifest, two configurations, their traffic, cells and metrics.
It is what a later PR would add, and what the CPU tests drive."""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402

ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
TRAINING = {"adam": ADAM, "mixed_precision": True, "rng_impl": "rbg"}

TINY_BERT = {
    "vocab_size": 211, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 2, "intermediate_size": 128,
    "max_position_embeddings": 32, "type_vocab_size": 2,
    "layer_norm_eps": 1e-12, "initializer_range": 0.02,
    "reference": "benchmark.configs.bert_reference",
    "program": {
        "factory": "deeplearning4j_tpu.models.bert:bert_base",
        "kwargs": {"dropout": 0.0, "attention_dropout": 0.0,
                   "vocab_size": 211, "hidden": 64, "num_layers": 2,
                   "num_heads": 2, "intermediate": 128, "max_position": 32}},
    "training": TRAINING,
}
TINY_GPT = {
    "vocab_size": 211, "n_positions": 32, "n_embd": 64, "n_layer": 2,
    "n_head": 2, "n_inner": 128, "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.02,
    "reference": "benchmark.configs.gpt2_reference",
    "program": {
        "factory": "deeplearning4j_tpu.models.gpt:gpt2_small",
        "kwargs": {"dropout": 0.0, "attention_dropout": 0.0,
                   "vocab_size": 211, "hidden": 64, "num_layers": 2,
                   "num_heads": 2, "intermediate": 128, "max_position": 32}},
    "training": TRAINING,
    "serving": {"dtype": "float32", "temperature": 0.0},
}
# the serving cell's model: the same, with logits of the real model's
# scale (0.02 x sqrt(768) = 0.07 x sqrt(64)), so that a lower precision
# moves the first token as far as it does there
TINY_GPT_CHAT = dict(TINY_GPT, initializer_range=0.07)
BATCHES = "benchmark.harness.traffic:fixed_batches"
# 25 requests, ten a second: 5 lead in over half a second, 20 are due in a
# window of two seconds; prompts of 3 to 12 tokens, outputs of 2 to 8
CHAT_REQUESTS = [[round(-0.5 + 0.1 * i + 0.03 * (i % 3), 3),
                  3 + (7 * i) % 10, 2 + (5 * i) % 7] for i in range(25)]
TRAFFIC = {
    "tiny_mlm": {"family": BATCHES, "rows": 8, "seq_len": 16,
                 "distinct_batches": 4, "max_predictions": 3,
                 "mask_frac": 0.15, "mask_id": 3, "max_in_flight": 2},
    "tiny_clm": {"family": BATCHES, "rows": 8, "seq_len": 16,
                 "distinct_batches": 4, "max_in_flight": 2},
    "tiny_chat": {"family": "benchmark.harness.traffic:replayed",
                  "requests": CHAT_REQUESTS},
}
CELLS = {"tiny_bert.tiny_mlm": ("tiny_bert", "tiny_mlm"),
         "tiny_gpt.tiny_clm": ("tiny_gpt", "tiny_clm"),
         "tiny_gpt_chat.tiny_chat": ("tiny_gpt_chat", "tiny_chat")}
CHAT = "tiny_gpt_chat.tiny_chat"
# the real cells that the tiny training cells stand for; the manifest has
# no serving cell yet, so the tiny one brings its own metrics, as a later
# PR's would
REAL = {"tiny_bert.tiny_mlm": "bert_base.train_s128",
        "tiny_gpt.tiny_clm": "gpt2_small.train_s1024"}
SERVE_END_TO_END = [
    {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.02,
     "source": "host_clock", "workloads": [CHAT]},
    {"name": "serve_tok_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.01, "source": "host_clock", "workloads": [CHAT]},
]
SERVE_PER_LAYER = {
    "send_lag_p95_ms": ("ms", "entry", "serve_tok_s", "percentile",
                        {"counter": "send_lag_s", "q": 95, "scale": 1000.0}),
    "ttft_p95_ms": ("ms", "scheduler", "itl_p95_ms", "percentile",
                    {"counter": "ttft_s", "q": 95, "scale": 1000.0}),
    "rows_per_step": ("rows", "scheduler", "itl_p95_ms", "ratio",
                      {"num": "output_tokens", "den": "decode_steps"}),
    "compiles_in_window": ("count", "scheduler", "itl_p95_ms", "counter",
                           {"name": "compiles_in_window"}),
}


# Limits of the tiny cells, set as the real cells' are (PERF.md section 2)
# from readings at this size on the CPU, eight seeds of the program, four
# of the control and of the fault. grad_share_gap: the program's largest
# and the fp8 control's smallest were 0.00136 and 0.0053 (tiny_bert) and
# 0.00052 and 0.0012 (tiny_gpt); half of the batch left out read 0.065 and
# 0.012 or more. A state left unchanged reads 1 for change_norm_gap
# against the program's 0.07 and for change_median_gap against the
# program's 0.0012 (eight seeds), where a learning rate a fifth off
# reads 0.2. The tiny serving cell's logits have the real
# model's scale, so it takes the limit that the real model's readings on
# the chip gave (PERF.md section 7): the program 0.011 at most, the fp8
# control 0.216 at least.
LIMITS = {
    "tiny_bert.tiny_mlm": {"grad_share_gap": 0.0027, "change_norm_gap": 0.25,
                           "change_median_gap": 0.1},
    "tiny_gpt.tiny_clm": {"grad_share_gap": 0.0008, "change_norm_gap": 0.25,
                          "change_median_gap": 0.1},
    CHAT: {"served_logit_gap": 0.05, "served_tokens_missing": 0.5},
}


def tiny_cells(metric):
    """A metric's ``workloads`` key with the tiny cells in place of the
    real ones they stand for."""
    if "workloads" not in metric:
        return {}
    return {"workloads": [t for t, r in REAL.items()
                          if r in metric["workloads"]]}


def write(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)


def build(tmp: str) -> str:
    """Write the tiny benchmark under ``tmp``; returns the manifest's path."""
    bench = os.path.join(tmp, "bench")
    write(os.path.join(bench, "configs", "tiny_bert.json"), TINY_BERT)
    write(os.path.join(bench, "configs", "tiny_gpt.json"), TINY_GPT)
    write(os.path.join(bench, "configs", "tiny_gpt_chat.json"), TINY_GPT_CHAT)
    for name, doc in TRAFFIC.items():
        write(os.path.join(bench, "traffic", name + ".json"), doc)
    for name, (_, traffic) in CELLS.items():
        doc = {"kind": "train", "step_module": "jit_train_step",
               "trace_slice_s": 0.2,
               "check": {"steps": 3, "reference_row_block": 4}}
        if traffic == "tiny_chat":
            doc = {"kind": "serve", "drain_s": 30.0, "trace_slice_s": 0.5,
                   "engine": {"num_slots": 4, "max_len": 32,
                              "max_new_tokens": 8, "max_waiting": 256,
                              "min_kv_bucket": 16, "min_prompt_bucket": 8},
                   "check": {"requests": 4, "pad_to": 24}}
        write(os.path.join(bench, "workloads", name + ".json"),
              dict(doc, limits=LIMITS[name]))
    shutil.copytree(os.path.join(manifest.BENCH_DIR, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    for name, (_, _, _, reader, args) in SERVE_PER_LAYER.items():
        write(os.path.join(bench, "metrics", name + ".json"),
              {"reader": "benchmark.harness.readers:" + reader, "args": args})
    real = manifest.load_json(manifest.MANIFEST)
    doc = dict(
        real, paths=["bench"],
        configs=[{"name": c, "source": "test", "reduced": [], "why": "test",
                  "file": f"bench/configs/{c}.json"}
                 for c in ("tiny_bert", "tiny_gpt", "tiny_gpt_chat")],
        workloads=[{"name": n, "config": c, "traffic": t, "chips": 1,
                    "why": "test"} for n, (c, t) in CELLS.items()],
        end_to_end=[dict(m, **tiny_cells(m)) for m in real["end_to_end"]]
        + SERVE_END_TO_END,
        per_layer=[dict(m, **tiny_cells(m)) for m in real["per_layer"]]
        + [{"name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": layer, "moves": moves}
           for name, (unit, layer, moves, _, _) in SERVE_PER_LAYER.items()])
    path = os.path.join(tmp, "BENCHMARK.json")
    write(path, doc)
    return path


def load(tmp: str, cell: str) -> manifest.Cell:
    return manifest.load_cell(cell, manifest_path=build(tmp),
                              bench_dir=os.path.join(tmp, "bench"))
