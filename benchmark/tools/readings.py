"""Readings for the limits of a cell's comparison, in one process on the
chip at the cell's own size.

``python benchmark/tools/readings.py --workload <cell> --seeds 12
--control-seeds 3`` prints, for every seed, the numbers a run compares
(the program against the plain reference: the lower readings) and, for the
first ``--control-seeds`` of them, the same numbers for the control (the
reference computed in fp8, put in the program's place), for the reference
in bfloat16 (the program's own precision: a witness that sides with the
program or with the reference where a seed reads far off) and, in a
training cell, for the fault "half
of the batch left out, the mean taken over the rest", planted in the
reference. A serving cell is read through whole short runs of
``--seconds`` each. The rows also go to
``chiprun_out/readings_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def half_batches(batches):
    import jax

    return [jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], b)
            for b in batches]


def serve_readings(cell, args) -> int:
    """Whole short runs of a serving cell, the controls read beside the
    program on the first seeds."""
    import time as time_mod

    from benchmark.kinds import serve

    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/readings_{cell.name}.jsonl", "a") as out:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            controls = ("fp8", "bfloat16") if i < args.control_seeds else ()
            result = serve.run(
                cell, seed=seed, seconds=args.seconds, trace=False,
                t_start=time_mod.perf_counter(), require_tpu=not args.cpu,
                controls=controls)
            row = {"seed": seed, "failed": result["failed"],
                   "attempted": result["attempted"],
                   "metrics": {k: v["value"]
                               for k, v in result["metrics"].items()},
                   "compared": result["compared"]}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_147_483_659)
    parser.add_argument("--seconds", type=float, default=10.0,
                        help="a serving cell's short window")
    parser.add_argument("--cpu", action="store_true",
                        help="rehearsal off the chip; readings mean nothing")
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchmark.configs import reference_common as rc
    from benchmark.harness import manifest, verdict
    from benchmark.harness import traffic as traffic_mod
    from benchmark.harness.device import arm_compile_cache, check_devices
    from benchmark.kinds import train

    cell = manifest.load_cell(args.workload)
    if cell.workload["kind"] == "serve":
        return serve_readings(cell, args)
    check_devices(cell.chips, not args.cpu)
    if not args.cpu:
        arm_compile_cache()
    n_check = int(cell.workload["check"]["steps"])
    trainer = train.build_trainer(cell)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(f"chiprun_out/readings_{cell.name}.jsonl", "a")

    sizes = rc.leaf_sizes(cell.reference.param_shapes(cell.config))

    def emit(seed, who, raw, reference, seconds):
        """The numbers a run compares, on standard output; the norms and
        losses they were worked out from go to the file as well, so that
        another number can be tried on the same readings."""
        numbers = verdict.training_numbers(raw, reference, sizes)
        row = {"seed": seed, "who": who, "seconds": round(seconds, 1),
               **{k: v["value"] for k, v in numbers.items()},
               "leaves": {k: v.get("leaf") for k, v in numbers.items()
                          if "leaf" in v}}
        print(json.dumps(row), flush=True)
        out.write(json.dumps(dict(row, raw=raw)) + "\n")
        out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        batches = traffic_mod.generate(cell, seed, 0.0)[:n_check]
        t = time.perf_counter()
        ts, probe, feed, program = train.first_steps(
            cell, trainer, seed, batches)
        del ts, probe, feed
        gc.collect()
        t_program = time.perf_counter() - t
        t = time.perf_counter()
        reference = train.follow_reference(cell, seed, batches)
        t_reference = time.perf_counter() - t
        emit(seed, "reference", reference, reference, t_reference)
        emit(seed, "program", program, reference, t_program)
        print(f"# reference took {t_reference:.1f} s, losses "
              f"{reference['losses']} program {program['losses']}",
              flush=True)
        if i < args.control_seeds:
            for who, precision, data in (
                    ("control_fp8", "fp8", batches),
                    ("witness_bfloat16", "bfloat16", batches),
                    ("fault_half_batch", "float32", half_batches(batches))):
                t = time.perf_counter()
                other = train.follow_reference(cell, seed, data, precision)
                emit(seed, who, other, reference, time.perf_counter() - t)
    out.close()
    stats = (jax.local_devices()[0].memory_stats() or {})
    print("# peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
