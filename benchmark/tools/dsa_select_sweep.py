"""The selection of indexed sparse attention, timed on the chip at one
layer of a cell's own size: the program's search for each query's
threshold by counting passes over the score's bit pattern, against
``jax.lax.top_k`` on the masked rows and against a sort, and the indexer's
score alone.

``python benchmark/tools/dsa_select_sweep.py --workload <cell>`` prints one
JSON row a method (ms a layer, the median of ``--runs`` runs after one to
compile) and whether its mask equals the program's; the rows also go to
``chiprun_out/dsa_select_sweep_<cell>.jsonl``. Run by hand, never by a run
of the benchmark. The variants live here; the program holds one method.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="keye_vl2_30b_a3b.train_s8192")
    parser.add_argument("--runs", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--cpu", action="store_true",
                        help="rehearsal off the chip at a tiny size; the "
                             "times mean nothing")
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.device import check_devices
    from deeplearning4j_tpu.nn.layers import attention as attn

    cell = manifest.load_cell(args.workload)
    check_devices(cell.chips, not args.cpu)
    sa = cell.config["sa_config"]
    n, t = cell.traffic["rows"], cell.traffic["seq_len"]
    heads, width, top_k = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                           sa["topk"])
    if args.cpu:
        t, top_k = 64, 16
    kq, kk, kw = jax.random.split(jax.random.key(args.seed), 3)
    q = jax.random.normal(kq, (n, t, heads, width), jnp.float32)
    k = jax.random.normal(kk, (n, t, width), jnp.float32)
    w = jax.random.normal(kw, (n, t, heads), jnp.float32) / (
        heads * width) ** 0.5

    program = attn._kth_largest

    def by_top_k(scores, k_):
        return scores, jax.lax.top_k(scores, k_)[0][..., -1:]

    def by_sort(scores, k_):
        return scores, jnp.sort(scores, axis=-1)[..., scores.shape[-1] - k_,
                                                 None]

    def no_selection(scores, k_):  # the indexer's score alone
        return scores, jnp.max(scores, axis=-1, keepdims=True)

    methods = {"threshold_by_bit_search_xla": program,
               "jax.lax.top_k": by_top_k, "jnp.sort": by_sort,
               "indexer_score_alone": no_selection}
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(f"chiprun_out/dsa_select_sweep_{cell.name}.jsonl", "a")
    want = None
    for name, method in methods.items():
        attn._kth_largest = method
        attn._selected_pairs.clear_cache()
        try:
            t0 = time.perf_counter()
            mask = jax.block_until_ready(attn._selected_pairs(q, k, w, top_k))
            first = time.perf_counter() - t0
            times = []
            for _ in range(args.runs):
                t0 = time.perf_counter()
                jax.block_until_ready(attn._selected_pairs(q, k, w, top_k))
                times.append(1e3 * (time.perf_counter() - t0))
            if want is None:
                want = np.asarray(mask)
            row = {"method": name, "ms_a_layer": statistics.median(times),
                   "runs_ms": [round(x, 3) for x in times],
                   "first_call_s": round(first, 2),
                   "same_mask_as_the_program": bool(
                       np.array_equal(np.asarray(mask), want)),
                   "keys_selected_mean": float(np.asarray(mask).sum())
                   / (n * t)}
        except Exception as e:  # noqa: BLE001 - a method the chip refuses
            row = {"method": name, "error": f"{type(e).__name__}: {e}"[:400]}
        finally:
            attn._kth_largest = program
            attn._selected_pairs.clear_cache()
        row.update(rows=n, seq_len=t, top_k=top_k,
                   device=jax.devices()[0].device_kind)
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
