"""The cold-start driver end to end at a tiny size on the CPU: its result
line, the faults it must catch, and the control it must fail."""

import jax
import numpy as np
import pytest

from bench import control
from bench.harness import Benchmark
from bench.tests import common
from bench.tests.tiny import make_root

CELL = "tiny-smollm.coldstart"
PER_LAYER = {"restore_s", "restore_link_pct", "prefill_s", "device_idle_pct.coldstart"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_result_line(root, monkeypatch, trace):
    common.fake_trace(monkeypatch)
    result = common.run(root, CELL, trace=trace)
    common.assert_schema(result, trace, PER_LAYER if trace else {"cold_start_s", "setup_s"})
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    if trace:
        assert 0 < result["metrics"]["restore_link_pct"]["value"] <= 100


@pytest.mark.parametrize("fault", ["token_altered", "half_the_wave", "restore_corrupted"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    from repro.serve.engine import ServeEngine

    run, from_store = ServeEngine.run, ServeEngine.from_store
    if fault == "token_altered":
        def broken(self, *a, **k):
            out = run(self, *a, **k)
            for r in out:
                r.tokens = (r.tokens + 1) % 512
            return out
        monkeypatch.setattr(ServeEngine, "run", broken)
    elif fault == "half_the_wave":
        monkeypatch.setattr(ServeEngine, "run", lambda self, *a, **k: run(self, *a, **k)[::2])
    else:
        def corrupted(cls, *a, **k):
            engine = from_store(*a, **k)
            leaves, tree = jax.tree.flatten(engine.params)
            leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1.0)
            engine.params = jax.tree.unflatten(tree, leaves)
            return engine
        monkeypatch.setattr(ServeEngine, "from_store", classmethod(corrupted))
    result = common.run(root, CELL)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_the_limits(root, seed):
    bench = Benchmark(root)
    cell = bench.cell(CELL, seed)
    found = control.readings(bench.driver(cell), cell, waves=4)
    limit = cell.workload["limits"]["logit_gap"]
    assert found["control"]["logit_gap"] > limit
    assert found["token_altered"]["logit_gap"] > limit
    assert np.isfinite(found["control"]["logit_gap"])
