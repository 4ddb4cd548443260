"""The data-driven harness: finds a cell's files by name, drives one run and
assembles its result line.

Everything that belongs to one configuration, cell or per-layer metric is a
file of its own, found by the name `BENCHMARK.json` gives it:

  <paths[0]>/configs/<config>.json    sizes, source, cuts, deployment
  <paths[0]>/workloads/<cell>.json    driver, traffic parameters, limits
  <paths[0]>/drivers/<driver>.py      one per kind of traffic: `run(...)`
  <paths[0]>/metrics/<metric>.py      one per per-layer metric: `read(...)`

A new cell is therefore new files and new entries, never an edit.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

from bench import trace as tr


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file
    workload: dict      # the cell's workload file
    seed: int


@dataclass
class Outcome:
    """What a driver measured and checked in one run."""

    end_to_end: dict[str, float]                  # metric name -> value
    attempted: int
    failed: int
    checks: dict[str, tuple[float, float]]        # name -> (value, limit)
    memory_peak_bytes: int
    facts: dict = field(default_factory=dict)     # inputs of per-layer readers


@dataclass
class Readings:
    """What a per-layer metric's reader may read."""

    spans: dict[str, list[float]]   # harness span name -> durations (s)
    facts: dict
    trace: tr.Summary | None
    peak: dict                      # the device's row of peaks.json


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """`BENCHMARK.json` and the files it names, under one checkout root."""

    def __init__(self, root: Path) -> None:
        self.root = Path(root)
        self.spec = load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / self.spec["paths"][0]

    def entry(self, cell: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == cell:
                return w
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")

    def cell(self, name: str, seed: int) -> Cell:
        entry = self.entry(name)
        (conf,) = [c for c in self.spec["configs"] if c["name"] == entry["config"]]
        workload = load_json(self.dir / "workloads" / f"{name}.json")
        if workload["config"] != entry["config"] or workload["traffic"] != entry["traffic"]:
            raise ValueError(f"{name}: workload file disagrees with BENCHMARK.json")
        return Cell(name=name, chips=int(entry["chips"]),
                    config=load_json(self.root / conf["file"]),
                    workload=workload, seed=seed)

    def driver(self, cell: Cell) -> ModuleType:
        return load_module(self.dir / "drivers" / f"{cell.workload['driver']}.py")

    def _applies(self, metric: dict, cell: str) -> bool:
        return cell in metric.get("workloads", [w["name"] for w in self.spec["workloads"]])

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.spec["per_layer"] if self._applies(m, cell)]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.dir / "metrics" / f"{metric}.py")

    def peak(self, device_kind: str) -> dict:
        peaks = load_json(self.dir / "peaks.json")
        if device_kind not in peaks:
            raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
        return peaks[device_kind]


class Harness:
    """Host spans around each call into a layer, the set-up clock and the
    traced window. With tracing on, every span is also a profiler
    `TraceAnnotation`, so the trace can name what the host did in a gap."""

    def __init__(self, t_start: float, trace: bool) -> None:
        self.t_start = t_start
        self.trace = trace
        self.setup_s: float | None = None
        self.spans: dict[str, list[float]] = {}
        self.trace_dir: str | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        ann = jax.profiler.TraceAnnotation(name) if self.trace else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def setup_done(self) -> None:
        """Set-up ends where the first timed call starts."""
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced to a temporary directory when on.
        Spans recorded before it are dropped: per-layer readers see the
        window's alone."""
        import jax

        self.spans = {}
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with self.span(tr.WINDOW):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    def summary(self) -> tr.Summary | None:
        if self.trace_dir is None:
            return None
        try:
            return tr.reduce(tr.load_events(self.trace_dir), set(self.spans))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def passed(value: float, limit: float) -> bool:
    return math.isfinite(value) and value <= limit


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    bench = Benchmark(root)
    cell = bench.cell(name, seed)
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"{name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} x {dev.platform} ({dev.device_kind})")
    h = Harness(t_start, trace)
    out: Outcome = bench.driver(cell).run(cell, seconds, h)
    for span, times in sorted(h.spans.items()):
        print(f"span {span}: n={len(times)} total={sum(times)!r} max={max(times)!r}"
              + (f" each={times!r}" if len(times) <= 10 else ""), file=sys.stderr)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result: dict = {"correct": all(passed(v, lim) for v, lim in out.checks.values()),
                    "attempted": out.attempted, "failed": out.failed}
    e2e = {**out.end_to_end, "setup_s": h.setup_s}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in bench.end_to_end(name)}
    else:
        summary = h.summary()
        if summary is None:
            raise RuntimeError(f"{name}: the trace holds no device operation in the window")
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        readings = Readings(spans=h.spans, facts=out.facts, trace=summary,
                            peak=bench.peak(dev.device_kind))
        metrics = {}
        for m in bench.per_layer(name):
            value = bench.reader(m["name"]).read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    return result
