"""Readings of the control and of planted faults for the cells of the
`train_loop` driver, for setting their limits.

  python3 bench/control_loop.py --workload granite-4.0-h-micro-10l.train-stream-8k \
      --seeds 11 12 13

The control is the plain reference put in the program's place, computed one
step below the configuration's bfloat16: every product's operands float8
(e4m3, one scale a tensor). The faults (`train_loop.faults`) are planted in
the reference put in the program's place. Each is compared with the float32
reference exactly as a run compares the program, and printed as one JSON
line a seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from bench.harness import Benchmark

    bench = Benchmark(ROOT)
    for seed in args.seeds:
        cell = bench.cell(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **bench.driver(cell).readings(cell)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
