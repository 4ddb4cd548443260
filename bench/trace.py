"""Reduce a profiler trace to device busy time, idle gaps and the breakdown.

Device time is the union of the intervals of the operations on each device's
"XLA Ops" line, averaged over the devices. The window is the harness's own
`WINDOW` span, read from the same trace, so both share one clock. Each idle
gap of the first device is named after the innermost harness span that covers
its middle, which says what the host was doing while the chip waited.
"""

from __future__ import annotations

import glob
from dataclasses import dataclass, field
from typing import Iterable

WINDOW = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"
NO_SPAN = "host_loop"
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    busy_s: float                  # union of op intervals, mean over devices
    window_s: float                # the harness's window span
    devices: int
    device_ops: list = field(default_factory=list)   # [[name, seconds]] by time
    idle_gaps: list = field(default_factory=list)    # [[host span, seconds]]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the one `.xplane.pb` the profiler wrote under
    `trace_dir`."""
    import jax

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
            for plane in data.planes for line in plane.lines for ev in line.events]


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(name: str) -> str:
    """An op's name without its HLO text: `%fusion.12 = bf16[...] ...` reads
    `%fusion.12`."""
    return name.split(" = ", 1)[0]


def _self_times(intervals: list[tuple[float, float, str]]) -> list[tuple[str, float]]:
    """(name, seconds) of each op less the ops nested inside it: a `while`
    op's events enclose its body's."""
    out: list[list] = []
    stack: list[list] = []
    for s, t, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, t, name, t - s]
        if stack:
            stack[-1][3] -= t - s
        stack.append(rec)
        out.append(rec)
    return [(name, max(self_ns, 0.0) / 1e9) for _, _, name, self_ns in out]


def reduce(events: list[Event], span_names: set[str]) -> Summary | None:
    """Busy time, idle gaps and top operations inside the window; None when
    the trace holds no window span or no device operation."""
    windows = [e for e in events if e.name == WINDOW and not e.plane.startswith("/device:")]
    if len(windows) != 1:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    ops: dict[str, list[tuple[float, float, str]]] = {}
    for e in events:
        if not (e.plane.startswith("/device:") and e.line == DEVICE_OPS_LINE):
            continue
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            ops.setdefault(e.plane, []).append((s, t, op_name(e.name)))
    if not ops:
        return None
    totals: dict[str, float] = {}
    for iv in ops.values():
        for name, self_s in _self_times(iv):
            totals[name] = totals.get(name, 0.0) + self_s
    unions = {plane: _union((s, t) for s, t, _ in iv) for plane, iv in ops.items()}
    busy = [sum(t - s for s, t in u) for u in unions.values()]

    spans = [e for e in events if e.name in span_names and e.name != WINDOW
             and not e.plane.startswith("/device:")]
    first = unions[sorted(unions)[0]]
    gaps: dict[str, float] = {}
    edge = w0
    for s, t in [*first, (w1, w1)]:
        if s > edge:
            mid = (edge + s) / 2
            covering = [sp for sp in spans if sp.start_ns <= mid <= sp.end_ns]
            name = min(covering, key=lambda sp: sp.dur_ns).name if covering else NO_SPAN
            gaps[name] = gaps.get(name, 0.0) + (s - edge) / 1e9
        edge = max(edge, t)

    def top(d: dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return Summary(busy_s=sum(busy) / len(busy) / 1e9, window_s=(w1 - w0) / 1e9,
                   devices=len(unions), device_ops=top(totals), idle_gaps=top(gaps))
