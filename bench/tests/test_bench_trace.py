"""The trace reduction on a hand-built event set."""

from bench import trace as tr

MS = 1e6  # ns


def ev(plane, line, name, start_ms, dur_ms):
    return tr.Event(plane, line, name, start_ms * MS, dur_ms * MS)


def events(second_device=False):
    host = "/host:CPU"
    dev = "/device:TPU:0"
    out = [
        ev(host, "python", tr.WINDOW, 10, 100),          # window 10..110 ms
        ev(host, "python", "loader_wait", 10, 20),       # 10..30
        ev(host, "python", "step_wait", 50, 40),         # 50..90
        ev(host, "python", "prefill", 95, 15),           # 95..110
        ev(dev, "XLA Ops", "%fusion.1 = bf16[2] fusion(x)", 0, 15),  # clipped to 10..15
        ev(dev, "XLA Ops", "%while.3 = (s32[]) while(y)", 30, 40),   # 30..70, holds:
        ev(dev, "XLA Ops", "%dot.2 = f32[4] dot(a, b)", 35, 10),     #   35..45
        ev(dev, "XLA Ops", "%fusion.1 = bf16[2] fusion(x)", 50, 10),  #   50..60
        ev(dev, "XLA Modules", "jit_step", 30, 40),                  # not an op line
        ev(dev, "XLA Ops", "%dot.2 = f32[4] dot(a, b)", 120, 5),     # outside the window
    ]
    if second_device:
        out.append(ev("/device:TPU:1", "XLA Ops", "fusion.9", 10, 100))
    return out


def test_busy_union_idle_gaps_and_breakdown():
    s = tr.reduce(events(), {tr.WINDOW, "loader_wait", "step_wait", "prefill"})
    assert s.devices == 1
    assert abs(s.window_s - 0.100) < 1e-12
    # Busy: 10..15 and 30..70 -> 45 ms; nested ops count once.
    assert abs(s.busy_s - 0.045) < 1e-12
    assert abs(s.idle_pct - 55.0) < 1e-9
    # Gaps: 15..30 (loader_wait), 70..110 (mid 90: step_wait ends at 90,
    # prefill starts at 95; the innermost covering span at 90 is step_wait).
    gaps = dict(s.idle_gaps)
    assert set(gaps) == {"loader_wait", "step_wait"}
    assert abs(gaps["loader_wait"] - 0.015) < 1e-12
    assert abs(gaps["step_wait"] - 0.040) < 1e-12
    # Each op's own time, the while less its body: 20, 5 + 10, 10 ms.
    assert [name for name, _ in s.device_ops] == ["%while.3", "%fusion.1", "%dot.2"]
    ops = dict(s.device_ops)
    assert abs(ops["%while.3"] - 0.020) < 1e-12
    assert abs(ops["%fusion.1"] - 0.015) < 1e-12
    assert abs(ops["%dot.2"] - 0.010) < 1e-12


def test_gap_outside_every_span_is_named_host_loop():
    evs = [ev("/host:CPU", "python", tr.WINDOW, 0, 10),
           ev("/device:TPU:0", "XLA Ops", "op", 0, 2)]
    s = tr.reduce(evs, {tr.WINDOW})
    assert len(s.idle_gaps) == 1 and s.idle_gaps[0][0] == tr.NO_SPAN
    assert abs(s.idle_gaps[0][1] - 0.008) < 1e-12


def test_busy_is_averaged_over_devices():
    s = tr.reduce(events(second_device=True), {tr.WINDOW})
    assert s.devices == 2
    assert abs(s.busy_s - (0.045 + 0.100) / 2) < 1e-12


def test_no_window_or_no_device_op_gives_nothing():
    assert tr.reduce([e for e in events() if e.name != tr.WINDOW], set()) is None
    assert tr.reduce([e for e in events() if not e.plane.startswith("/device")],
                     {tr.WINDOW}) is None
