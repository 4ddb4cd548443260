"""Readings of the control and of planted faults, for setting a cell's limits.

  python3 bench/control.py --workload olmo-1b-8l.train-stream --seeds 11 12 13

The control is the plain reference put in the program's place, computed one
step below the configuration's bfloat16: float8 (e4m3, one scale a tensor).
A fault is planted in the reference put in the program's place. Each is
compared with the float32 reference exactly as a run compares the program,
and printed as one JSON line a seed. The benchmark's own runs never run
this: it exists to show that each limit fails what it must.

  train-stream:  control; "half_batch" (the mean over half of each batch)
  coldstart:     control; "token_altered" (each served token one id off)
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(driver, cell, waves: int) -> dict:
    """The control's and the faults' compared numbers for one cell and seed;
    `driver` is the cell's driver module."""
    import jax.numpy as jnp
    import numpy as np

    from bench import reference, weights

    if cell.workload["driver"] == "train":
        stream = driver.token_shards(cell)
        ref = driver.reference_numbers(cell, stream)
        control = driver.reference_numbers(cell, stream, precision="fp8")
        half = driver.reference_numbers(cell, stream, rows=cell.workload["batch"] // 2)
        return {"reference": ref, "control": driver.gaps(control, ref),
                "half_batch": driver.gaps(half, ref)}
    pool = driver.prompt_pool(cell)[:waves]
    shape = pool.shape[:2]
    control = driver.logit_gaps(cell, pool, np.zeros(shape, np.int64), precision="fp8")
    w = weights.make_jitted(cell.config, cell.seed)
    top = np.stack([np.asarray(jnp.argmax(reference.prompt_logits(
        w, reference.static(cell.config), jnp.asarray(p), "float32"), axis=-1)) for p in pool])
    altered = driver.logit_gaps(cell, pool, (top + 1) % cell.config["vocab_size"])
    return {"control": {"logit_gap": float(control.max())},
            "token_altered": {"logit_gap": float(altered.max())}}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--waves", type=int, default=8,
                    help="coldstart: waves compared, as many as a run serves")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from bench.harness import Benchmark

    bench = Benchmark(ROOT)
    for seed in args.seeds:
        cell = bench.cell(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(bench.driver(cell), cell, args.waves)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
