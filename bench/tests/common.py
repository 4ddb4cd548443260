"""Helpers for tests that drive a whole run in-process on the CPU."""

from __future__ import annotations

import math
import time

from bench import trace as tr
from bench.harness import Benchmark, Harness, run_cell

SEED = 2**31 + 11


def fake_trace(monkeypatch):
    """The CPU has no device plane: traced runs read this summary instead,
    and the CPU gets a row of peaks."""
    summary = tr.Summary(busy_s=0.5, window_s=1.0, devices=1,
                         device_ops=[["fusion.1", 0.4], ["dot.2", 0.1]],
                         idle_gaps=[["loader_wait", 0.3], [tr.NO_SPAN, 0.2]])
    monkeypatch.setattr(Harness, "summary", lambda self: summary)
    monkeypatch.setattr(Benchmark, "peak", lambda self, kind: {"bf16_flops_per_s": 1e12})


def run(root, cell, trace=False, seconds=0.3, seed=SEED):
    return run_cell(root, cell, seed, seconds, trace, time.perf_counter(), require_chip=False)


def assert_schema(result: dict, trace: bool, metrics: set[str]) -> None:
    keys = list(result)
    assert keys[-1] == "checks"
    assert set(keys) == {"correct", "attempted", "failed", "metrics", "device", "checks"} | (
        {"breakdown"} if trace else set())
    assert isinstance(result["correct"], bool)
    assert isinstance(result["attempted"], int) and result["attempted"] > 0
    assert isinstance(result["failed"], int)
    assert set(result["metrics"]) == metrics
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
    dev = result["device"]
    want = {"platform", "kind", "count", "memory_peak_bytes"} | (
        {"busy_s", "window_s"} if trace else set())
    assert set(dev) == want
    if trace:
        assert dev["busy_s"] > 0
        for lst in result["breakdown"].values():
            assert len(lst) <= 10 and all(isinstance(v, float) for _, v in lst)
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
