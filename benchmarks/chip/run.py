#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload qwen2-0.5b.chat --seed 7 \
        --seconds 51 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout, runs
it on the chips JAX finds, and prints one JSON object as the last line of
standard output (``--trace 0``: the cell's end-to-end metrics; ``--trace
1``: its per-layer metrics, with a profiler trace of the window). Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for, or where the program cannot be imported.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
