"""The train driver end to end at a tiny size on the CPU: its result line, the
faults it must catch, and the control it must fail."""

import jax
import pytest

from bench import control
from bench.harness import Benchmark
from bench.tests import common
from bench.tests.tiny import make_root

CELL = "tiny-olmo.train-stream"
PER_LAYER = {"loader_wait_ms", "step_mfu", "device_idle_pct.train"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_result_line(root, monkeypatch, trace):
    common.fake_trace(monkeypatch)
    result = common.run(root, CELL, trace=trace)
    common.assert_schema(result, trace, PER_LAYER if trace else {"train_tokens_per_s", "setup_s"})
    assert result["correct"], result["checks"]
    assert result["failed"] == 0


def unchanged_state(build):
    def wrapped(*args):
        step = build(*args)
        return lambda state, batch: (state, step(state, batch)[1])
    return wrapped


def half_batch(build):
    def wrapped(*args):
        step = build(*args)
        return lambda state, batch: step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return wrapped


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "token_altered"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    import repro.train
    from repro.data import PrefetchingDataLoader

    if fault == "token_altered":
        batches = PrefetchingDataLoader.batches

        def altered(self, *a, **k):
            for inputs, labels in batches(self, *a, **k):
                inputs = inputs.copy()
                inputs[0, 0] = (inputs[0, 0] + 1) % 500
                yield inputs, labels
        monkeypatch.setattr(PrefetchingDataLoader, "batches", altered)
    else:
        wrap = {"unchanged_state": unchanged_state, "half_batch": half_batch}[fault]
        monkeypatch.setattr(repro.train, "build_train_step", wrap(repro.train.build_train_step))
    result = common.run(root, CELL)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("seed", [3, 4])
def test_control_fails_the_limits(root, seed):
    bench = Benchmark(root)
    cell = bench.cell(CELL, seed)
    found = control.readings(bench.driver(cell), cell, waves=0)
    limits = cell.workload["limits"]
    assert any(v > limits[k] for k, v in found["control"].items()), found["control"]
    assert any(v > limits[k] for k, v in found["half_batch"].items()), found["half_batch"]
    assert jax.devices()[0].platform == "cpu"
