"""A benchmark root at CPU-test size: tiny configurations and cells beside
the real drivers and metric readers."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = {"head_dim": 16, "d_ff": 128, "num_layers": 2, "d_model": 64, "norm_eps": 1e-5,
              "rope_theta": 10000.0, "tie_embeddings": True, "act": "silu", "glu": True,
              "param_dtype": "float32", "compute_dtype": "bfloat16", "reduced": []}
CONFIGS = {
    "tiny-smollm": {**TINY_MODEL, "name": "tiny-smollm", "program_arch": "smollm-135m",
                    "num_heads": 4, "num_kv_heads": 2, "vocab_size": 512,
                    "norm_type": "rmsnorm", "parametric_norm": True},
    "tiny-olmo": {**TINY_MODEL, "name": "tiny-olmo", "program_arch": "olmo-1b",
                  "num_heads": 4, "num_kv_heads": 4, "vocab_size": 500,
                  "norm_type": "layernorm", "parametric_norm": False},
}
FAST_LINK = {"latency_s": 0.001, "bandwidth_Bps": 1e9}
WORKLOADS = {
    "tiny-olmo.train-stream": {
        "config": "tiny-olmo", "traffic": "train-stream", "driver": "train", "link": FAST_LINK,
        "batch": 2, "seq_len": 64, "shards": 2, "tokens_per_shard": 4096,
        "loader": {"engine": "rolling", "blocksize": 4096, "depth": 2, "autotune": True,
                   "eviction_interval_s": 0.2, "cache_bytes": 65536},
        "feeder_depth": 2,
        "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                      "grad_clip_norm": 1.0, "warmup_steps": 5000, "total_steps": 100000,
                      "min_lr_ratio": 0.1},
        "step": {"microbatches": 1, "q_chunk": 64, "loss_chunk": 64, "remat": True},
        "checked_steps": 3,
        # From CPU readings at this size: the program over 8 seeds read at most
        # 6.9e-5 / 1.5e-3 / 1.2e-3, the float8 control at least 3.1e-4 / 6.6e-3
        # / 3.3e-3 over 5.
        "limits": {"loss_gap": 1.5e-4, "grad_gap": 4e-3, "change_gap": 2.5e-3,
                   "data_rows_wrong": 0},
    },
    "tiny-smollm.coldstart": {
        "config": "tiny-smollm", "traffic": "coldstart", "driver": "coldstart", "link": FAST_LINK,
        "requests": 4, "prompt_len": 16, "max_new_tokens": 1, "waves": 16,
        # The program over 8 seeds read at most 0.004, the control at least 0.028.
        "limits": {"logit_gap": 0.015, "requests_missing": 0, "restored_leaves_differing": 0},
    },
}


def make_root(tmp: Path, configs=CONFIGS, workloads=WORKLOADS) -> Path:
    """A checkout-shaped directory: BENCHMARK.json naming the given cells,
    their files, and the real drivers, metrics and peaks."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = tmp / "bench"
    for sub in ("drivers", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, wl in workloads.items():
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    spec = dict(real)
    spec["configs"] = [{"name": n, "source": "test", "file": f"bench/configs/{n}.json",
                        "reduced": [], "why": "test"} for n in configs]
    spec["workloads"] = [{"name": n, "config": wl["config"], "traffic": wl["traffic"],
                          "chips": 1, "why": "test"} for n, wl in workloads.items()]
    by_driver = {wl["driver"]: n for n, wl in workloads.items()}
    real_driver = {w["name"]: json.loads(
        (BENCH / "workloads" / f"{w['name']}.json").read_text())["driver"]
        for w in real["workloads"]}

    def cells(metric):
        if "workloads" not in metric:
            return metric
        drivers = {real_driver[c] for c in metric["workloads"]}
        return {**metric, "workloads": [by_driver[d] for d in drivers if d in by_driver]}

    spec["end_to_end"] = [cells(m) for m in real["end_to_end"]]
    spec["per_layer"] = [cells(m) for m in real["per_layer"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
