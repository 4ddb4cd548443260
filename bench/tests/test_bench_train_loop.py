"""The `train_loop` driver end to end at a tiny size on the CPU, for its two
cells: the dense loop with saves (`smollm-135m.train-ckpt`) and the hybrid
loop (`granite-4.0-h-micro-10l.train-stream-8k`). Their result lines; faults
in the program that the checks must catch, the save check's among them; and
the control and the faults planted in the reference, which must each fail
a limit. That the hybrid program fails without its conv bias or attention
multiplier is `tests/test_granite_hybrid.py`'s."""

import json

import jax
import pytest

from bench.harness import Benchmark
from bench.tests import common
from bench.tests.tiny import CONFIGS, ROOT, WORKLOADS, make_root

CKPT, HYBRID = "tiny-smollm.train-ckpt", "tiny-granite.train-stream-8k"
HYBRID_METRICS = {"loader_wait_ms", "step_mfu", "device_idle_pct.train"}
CKPT_METRICS = HYBRID_METRICS | {"save_stall_s"}

TINY_GRANITE = {
    **{k: v for k, v in json.loads(
        (ROOT / "bench" / "configs" / "granite-4.0-h-micro-10l.json").read_text()).items()
       if k not in ("published", "reduced", "assumed", "deployment")},
    "name": "tiny-granite", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 128, "shared_intermediate_size": 128,
    "mamba_d_head": 16, "mamba_n_heads": 8, "mamba_d_state": 16, "mamba_chunk_size": 32,
    "vocab_size": 512, "reduced": []}
TRAIN = {k: v for k, v in WORKLOADS["tiny-olmo.train-stream"].items() if k != "limits"}
NEW_WORKLOADS = {
    CKPT: {**TRAIN, "config": "tiny-smollm", "traffic": "train-ckpt", "driver": "train_loop",
           "save_every": 2,
           # From CPU readings at this size: the program over 6 seeds read at
           # most 6.6e-5 / 2.7e-3 / 1.4e-3, the float8 control at least
           # 3.8e-4 / 1.8e-2 / 6.4e-3 over 2, half a batch 1.8e-3 / 4.9e-2 / 1.3e-2.
           "limits": {"saved_leaves_differing": 0, "loss_gap": 1.5e-4, "grad_gap": 8e-3,
                      "change_gap": 4e-3, "data_rows_wrong": 0}},
    # 48 tokens: one whole chunk of 32 and a part of one.
    HYBRID: {**TRAIN, "config": "tiny-granite", "traffic": "train-stream-8k",
             "driver": "train_loop", "batch": 1, "seq_len": 48,
             "step": {"microbatches": 1, "q_chunk": 16, "loss_chunk": 16, "remat": True},
             # From CPU readings at this size: the program over 6 seeds read at
             # most 1.9e-5 / 0.084 / 0.096; over 2 seeds the float8 control at
             # least 1.0e-4 / 0.16 (its change 0.089, as noisy as the
             # program's: ten-element leaves), D left out of one layer 6.4e-4 /
             # 18.7, attention scaled by 1/sqrt(16) 4.2e-5 / 1.94.
             "limits": {"loss_gap": 5e-5, "grad_gap": 0.13, "change_gap": 0.15,
                        "data_rows_wrong": 0}},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"),
                     {**CONFIGS, "tiny-granite": TINY_GRANITE}, {**WORKLOADS, **NEW_WORKLOADS})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    tiny = {"smollm-135m.train-ckpt": CKPT, "granite-4.0-h-micro-10l.train-stream-8k": HYBRID}
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for group in ("end_to_end", "per_layer"):
        cells = {m["name"]: m.get("workloads") for m in real[group]}
        for m in spec[group]:
            if cells[m["name"]] is not None:
                m["workloads"] = [c for c in m["workloads"] if c in WORKLOADS] + [
                    tiny[c] for c in cells[m["name"]] if c in tiny]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_real_cells_found_by_name():
    bench = Benchmark(ROOT)
    for name, metrics in (("smollm-135m.train-ckpt", CKPT_METRICS),
                          ("granite-4.0-h-micro-10l.train-stream-8k", HYBRID_METRICS)):
        cell = bench.cell(name, common.SEED)
        assert cell.chips == 1 and cell.workload["driver"] == "train_loop"
        assert bench.driver(cell).run.__module__.endswith("train_loop")
        assert {m["name"] for m in bench.per_layer(name)} == metrics
        assert [m["name"] for m in bench.end_to_end(name)] == ["train_tokens_per_s", "setup_s"]
        for m in metrics:
            assert bench.reader(m).read is not None
    assert bench.cell("smollm-135m.train-ckpt", 1).workload["save_every"] == 30
    granite = bench.cell("granite-4.0-h-micro-10l.train-stream-8k", 1)
    assert (granite.workload["batch"], granite.workload["seq_len"]) == (1, 8192)


@pytest.mark.parametrize("cell, metrics, trace", [
    (CKPT, CKPT_METRICS, False), (CKPT, CKPT_METRICS, True), (HYBRID, HYBRID_METRICS, True)],
    ids=["ckpt-trace0", "ckpt-trace1", "hybrid-trace1"])
def test_result_line(root, monkeypatch, cell, metrics, trace):
    common.fake_trace(monkeypatch)
    result = common.run(root, cell, trace=trace, seconds=1.0 if cell == CKPT else 0.3)
    common.assert_schema(result, trace, metrics if trace else {"train_tokens_per_s", "setup_s"})
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    if cell == CKPT:
        assert result["attempted"] >= 2 and result["checks"]["saved_leaves_differing"]["value"] == 0


def saved_leaf_altered(monkeypatch):
    from repro.ckpt import manager

    save = manager.save_checkpoint

    def altered(store, prefix, step, state, **kw):
        leaves, treedef = jax.tree.flatten(state)
        leaves[-1] = leaves[-1] + 1
        return save(store, prefix, step, jax.tree.unflatten(treedef, leaves), **kw)
    monkeypatch.setattr(manager, "save_checkpoint", altered)


def nothing_saved(monkeypatch):
    from repro.ckpt import manager

    monkeypatch.setattr(manager.CheckpointManager, "maybe_save", lambda self, *a, **k: False)


def half_batch(monkeypatch):
    import repro.train

    build = repro.train.build_train_step

    def wrapped(*args):
        step = build(*args)
        return lambda state, batch: step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(repro.train, "build_train_step", wrapped)


@pytest.mark.parametrize("fault", [saved_leaf_altered, nothing_saved, half_batch],
                         ids=["saved_leaf_altered", "nothing_saved", "half_batch"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result = common.run(root, CKPT, seconds=1.0)
    assert not result["correct"], result["checks"]


def test_save_span_opens_on_a_finished_step(root, monkeypatch):
    """`save_stall_s` times the save alone: when `maybe_save` is called, the
    saving step's state is already on the device, so no step compute falls
    inside the `save` span."""
    from repro.ckpt import manager

    save, ready = manager.CheckpointManager.maybe_save, []

    def checked(self, step, state, **kw):
        ready.append(all(x.is_ready() for x in jax.tree.leaves(state)))
        return save(self, step, state, **kw)
    monkeypatch.setattr(manager.CheckpointManager, "maybe_save", checked)
    result = common.run(root, CKPT, seconds=1.0)
    assert result["correct"], result["checks"]
    assert ready and all(ready)


@pytest.mark.parametrize("cell", [CKPT, HYBRID], ids=["ckpt", "hybrid"])
def test_control_and_faults_fail_the_limits(root, cell):
    bench = Benchmark(root)
    c = bench.cell(cell, 3)
    found = bench.driver(c).readings(c)
    limits = c.workload["limits"]
    for name in set(found) - {"reference"}:
        assert any(v > limits[k] for k, v in found[name].items()), (name, found[name])
    assert jax.devices()[0].platform == "cpu"


def test_hybrid_flops_by_hand():
    from bench import hybrid

    cfg = json.loads((ROOT / "bench" / "configs" / "granite-4.0-h-micro-10l.json").read_text())
    n = 772_160_448
    attention = 12 * 1 * 32 * 64 * 8192
    ssd = 2 * 256 * 1 * 128 + 2 * 256 * 64 * 64 + 4 * 64 * 64 * 128
    assert hybrid.param_count(cfg) == n
    assert hybrid.train_flops_per_token(cfg, 8192) == 6 * n + attention + 3 * 9 * ssd
    assert round(hybrid.train_flops_per_token(cfg, 8192) / 1e9, 3) == 4.949
    shapes = jax.eval_shape(hybrid.model(cfg).init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == n


def test_reference_copy_matches_the_program_reference():
    """`bench/reference_hybrid.py` holds `repro.models.ref_hybrid`'s code
    verbatim, so that the benchmark imports nothing of the program."""
    src = (ROOT / "src" / "repro" / "models" / "ref_hybrid.py").read_text()
    copy = (ROOT / "bench" / "reference_hybrid.py").read_text()
    assert src[src.index("HIGHEST = "):] in copy
