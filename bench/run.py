"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload olmo-1b-8l.train-stream --seed 7 \
      --seconds 20 --trace 0

Set-up (JAX start-up, weights on the device from the seed, objects written
into the emulated store, warm-up) is timed from process start. The window
then runs for `--seconds`. Afterwards the run checks what the timed path
produced against the plain reference and prints each number compared beside
its limit on standard error, then one JSON line on standard output:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), and `checks` last. Without a TPU, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    # Cache every program, however quick to compile: after a checkout's first
    # run nothing compiles, not even what the program compiles again per call.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.harness import NoChip, run_cell

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
