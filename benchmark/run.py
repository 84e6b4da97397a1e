"""The benchmark's command.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell in this process on the chips of this
machine and prints one JSON object as the last line of standard output.
The cell's kind (a key of its file) names the module of
``benchmark/kinds/`` that runs it. Progress, and as its last lines each
number compared beside its limit, go to standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness import manifest

    cell = manifest.load_cell(args.workload)
    kind = importlib.import_module(f"benchmark.kinds.{cell.workload['kind']}")
    result = kind.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START)
    for row in result["compared"]:
        print("compared", json.dumps(row), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
