"""Spans and counters (`repro.utils.trace`) and where the program emits them:
the restore, the serving engine, the device feeder and the serve entry
point."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro.ckpt import restore_checkpoint, save_checkpoint
from repro.ckpt.manager import RESTORE_HOST_SPANS, RESTORE_POLICY, restore_breakdown
from repro.data import DeviceFeeder
from repro.store import LinkModel, SimS3Store
from repro.utils import trace


def _state():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32),
            "step": jnp.asarray(3, jnp.int32)}


# --------------------------------------------------------------------------- #
# The module
# --------------------------------------------------------------------------- #
def test_nesting_sets_parent():
    with trace.recording() as rec:
        with trace.span("outer"):
            with trace.span("inner"):
                pass
            with trace.span("inner"):
                pass
    assert [s.name for s in rec.spans] == ["inner", "inner", "outer"]
    assert [s.parent for s in rec.spans] == ["outer", "outer", None]
    outer = rec.spans[-1]
    for s in rec.spans[:2]:
        assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns


def test_spans_keep_their_own_threads():
    entered = threading.Barrier(2, timeout=10)

    def work():
        with trace.span("worker"):
            entered.wait()

    with trace.recording() as rec:
        with trace.span("main"):
            t = threading.Thread(target=work)
            t.start()
            entered.wait()
            t.join(timeout=10)
    assert not t.is_alive()
    spans = {s.name: s for s in rec.spans}
    assert spans["main"].thread == threading.get_ident()
    assert spans["worker"].thread == t.ident != spans["main"].thread
    # The main thread's open span is not the worker's parent.
    assert spans["worker"].parent is None


def test_nothing_is_kept_outside_recording():
    with trace.span("before"):
        trace.count("before")
    assert isinstance(trace.span("x"), jax.profiler.TraceAnnotation)
    with trace.recording() as rec:
        pass
    late = None
    with trace.recording() as rec2:
        late = trace.span("late")
        late.__enter__()
    late.__exit__(None, None, None)   # ends after its recording did
    trace.count("after")
    assert rec.spans == [] and rec.counters == {}
    assert rec2.spans == [] and rec2.counters == {}


def test_counters_reset_on_entry():
    with trace.recording() as rec:
        trace.count("n")
        trace.count("n", 2)
        trace.count("bytes", 0.5)
    assert rec.counters == {"n": 3, "bytes": 0.5}
    with trace.recording() as rec:
        trace.count("n")
    assert rec.counters == {"n": 1}


def test_one_recording_at_a_time():
    with trace.recording():
        with pytest.raises(RuntimeError, match="already recording"):
            with trace.recording():
                pass
    with trace.recording():   # the failed attempt left nothing behind
        pass


def test_self_time_subtracts_children():
    with trace.recording() as rec:
        with trace.span("outer"):
            time.sleep(0.02)
            with trace.span("inner"):
                time.sleep(0.03)
    outer, inner = rec.total("outer"), rec.total("inner")
    assert inner >= 0.03 and outer >= inner + 0.02
    assert rec.self_s("outer") == pytest.approx(outer - inner)
    assert rec.self_s("inner") == pytest.approx(inner)


def test_compile_listener_only_while_recording():
    before = len(monitoring.get_event_duration_listeners())
    with trace.recording() as rec:
        assert len(monitoring.get_event_duration_listeners()) == before + 1
        # A function of its own, so this compiles whatever ran before.
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert len(monitoring.get_event_duration_listeners()) == before
    assert rec.counters["jax.compiles"] >= 1
    assert rec.counters["jax.compile_s"] > 0
    with trace.recording() as rec:
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(9.0)).block_until_ready()
    compiles = rec.counters["jax.compiles"]
    jax.jit(lambda x: x * 7)(jnp.arange(11.0)).block_until_ready()
    assert rec.counters["jax.compiles"] == compiles


# --------------------------------------------------------------------------- #
# The restore
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("step", [None, 4], ids=["latest", "given"])
def test_restore_spans_and_request_counters(step):
    store = SimS3Store(link=LinkModel())
    state = _state()
    save_checkpoint(store, "ckpt", 4, state)
    before = store.link.requests
    with trace.recording() as rec:
        restored, _ = restore_checkpoint(store, "ckpt", state, step=step,
                                         policy=RESTORE_POLICY.replace(blocksize=1024))
        jax.block_until_ready(restored)
    made = store.link.requests - before
    leaves = len(jax.tree.leaves(state))
    names = [s.name for s in rec.spans]
    for name, n in [("restore", 1), ("restore.meta", 1), ("restore.open", 1),
                    ("restore.read", leaves), ("restore.verify", leaves),
                    ("restore.put", leaves), ("restore.close", 1)]:
        assert names.count(name) == n, name
    assert {s.parent for s in rec.spans if s.name != "restore"} == {"restore"}
    c = rec.counters
    assert c["restore.count"] == 1
    assert c["restore.meta_requests"] == 1 + (step is None)
    assert c["restore.meta_requests"] + c["restore.store_requests"] == made
    assert c["restore.bytes_fetched"] == sum(x.nbytes for x in jax.tree.leaves(state))
    parts = restore_breakdown(rec)
    assert parts["requests"] == made
    assert parts["meta_s"] == rec.total("restore.meta")
    assert parts["wait_s"] == rec.total("restore.read")
    assert 0 < parts["host_s"] == pytest.approx(
        sum(rec.self_s(name) for name in RESTORE_HOST_SPANS))
    children = sum(s.seconds for s in rec.spans if s.parent == "restore")
    assert children <= rec.total("restore")


def test_breakdown_is_per_restore():
    store = SimS3Store(link=LinkModel())
    state = _state()
    save_checkpoint(store, "ckpt", 1, state)
    before = store.link.requests
    with trace.recording() as rec:
        for _ in range(2):
            restore_checkpoint(store, "ckpt", state)
    assert rec.counters["restore.count"] == 2
    assert restore_breakdown(rec)["requests"] == (store.link.requests - before) / 2


# --------------------------------------------------------------------------- #
# Serving and the feeder
# --------------------------------------------------------------------------- #
def test_first_token_time_per_request():
    from repro.configs import get_config
    from repro.models import make_model
    from repro.serve import Request, ServeEngine

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg)
    engine = ServeEngine(model, model.init(jax.random.key(0)), max_batch=2)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(3):   # two waves: the third request waits for the first
        engine.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3))
    with trace.recording() as rec:
        results = engine.run()
    wall = time.perf_counter() - t0
    assert sorted(r.rid for r in results) == [0, 1, 2]
    for r in results:
        assert 0 < r.first_token_s <= wall
    first = {r.rid: r.first_token_s for r in results}
    assert first[2] > max(first[0], first[1])
    names = [s.name for s in rec.spans]
    for name in ("serve.prefill", "serve.first_tokens", "serve.decode"):
        assert names.count(name) == 2, name


def test_feeder_put_spans_on_the_feeder_thread():
    batches = [np.full((2, 4), i, np.int32) for i in range(5)]
    with trace.recording() as rec:
        feeder = DeviceFeeder(iter(batches), depth=2)
        got = [np.asarray(x) for x in feeder]
    assert [int(x[0, 0]) for x in got] == list(range(5))
    puts = [s for s in rec.spans if s.name == "feeder.put"]
    assert len(puts) == 5
    assert {s.thread for s in puts} == {feeder._thread.ident} != {threading.get_ident()}


def test_serve_entry_point_prints_the_restore_breakdown(monkeypatch, tmp_path, capsys):
    from repro.io.stores import clear_store_cache
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax-cache"))
    cache_dir = jax.config.jax_compilation_cache_dir
    clear_store_cache()
    try:
        run = serve.main(["--batch", "2", "--prompt-len", "8", "--gen", "2",
                          "--store", "sims3://trace-test?latency_ms=0"])
    finally:
        clear_store_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    line = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("  meta ")]
    assert len(line) == 1 and line[0].endswith("store requests")
    assert run.restore_s > 0
