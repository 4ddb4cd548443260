"""PrefetchFS facade tests: registry dispatch, the one `IOPolicy` (its
checks, per-open overrides, the workload class each call site stamps, the
restore's default), stats aggregation, and the thread-safety fixes in the
rolling engine."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.ckpt import restore_checkpoint, save_checkpoint
from repro.ckpt.manager import RESTORE_POLICY
from repro.core.rolling import PrefetchStats, RollingPrefetchFile, RollingPrefetcher
from repro.core.sequential import SequentialFile
from repro.data.loader import LoaderConfig, PrefetchingDataLoader
from repro.io import (
    DirectReader,
    IOPolicy,
    PrefetchFS,
    Reader,
    RetryPolicy,
    available_engines,
    register_reader,
)
from repro.io import registry as io_registry
from repro.serve.engine import ServeEngine
from repro.store import LinkModel, MemTier, SimS3Store
from repro.store.base import ObjectMeta, ObjectStore, StoreError, TransientStoreError


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def make_store(objects: dict[str, bytes], **kw) -> SimS3Store:
    store = SimS3Store(link=LinkModel(**kw))
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


def metas(store) -> list[ObjectMeta]:
    return store.backing.list_objects()


def _checkpoint(store) -> dict:
    """Save a one-leaf checkpoint at step 1 under "ckpt"; returns its state."""
    state = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    save_checkpoint(store, "ckpt", 1, state)
    return state


def _fs_policies(monkeypatch, module: str) -> list[IOPolicy]:
    """Record the policy of every `PrefetchFS` that `module` opens."""
    seen: list[IOPolicy] = []

    class Recording(PrefetchFS):
        def __init__(self, store, policy=None, **kw):
            seen.append(policy)
            super().__init__(store, policy=policy, **kw)

    monkeypatch.setattr(f"{module}.PrefetchFS", Recording)
    return seen


OBJECTS = {f"f{i}": payload(1500 + 37 * i, seed=i) for i in range(3)}
WANT = b"".join(OBJECTS[m.key] for m in
                sorted((ObjectMeta(k, len(v)) for k, v in OBJECTS.items()),
                       key=lambda m: m.key))


# --------------------------------------------------------------------------- #
# registry dispatch
# --------------------------------------------------------------------------- #
class TestRegistry:
    def test_builtin_engines_registered(self):
        assert {"rolling", "sequential", "direct"} <= set(available_engines())

    def test_dispatch_returns_engine_types(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(blocksize=512,
                                               eviction_interval_s=0.01))
        rolling = fs.open_many(metas(store))
        sequential = fs.open_many(metas(store), engine="sequential")
        direct = fs.open_many(metas(store), engine="direct")
        try:
            assert isinstance(rolling, RollingPrefetchFile)
            assert isinstance(sequential, SequentialFile)
            assert isinstance(direct, DirectReader)
            for reader in (rolling, sequential, direct):
                assert isinstance(reader, Reader)
        finally:
            fs.close()

    def test_unknown_engine_raises(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store)
        with pytest.raises(ValueError, match="unknown reader engine"):
            fs.open_many(metas(store), engine="bogus")

    def test_new_engine_plugs_in_without_touching_call_sites(self):
        @register_reader("test-direct-alias")
        def _factory(store, files, tiers, policy):
            return DirectReader(store, files)

        try:
            store = make_store(OBJECTS)
            fs = PrefetchFS(store, policy=IOPolicy(engine="test-direct-alias"))
            with fs:
                f = fs.open_many(metas(store))
                assert f.read() == WANT
        finally:
            io_registry._REGISTRY.pop("test-direct-alias")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_reader("rolling")(lambda *a: None)


# --------------------------------------------------------------------------- #
# IOPolicy
# --------------------------------------------------------------------------- #
class TestIOPolicy:
    @pytest.mark.parametrize("bad", [
        dict(blocksize=0), dict(depth=0), dict(depth=4, max_depth=2),
        dict(coalesce=0), dict(readahead_blocks=0), dict(write_depth=0),
        dict(max_hedges=0), dict(verify="paranoid"), dict(io_class=""),
    ], ids=lambda kw: ",".join(f"{k}={v!r}" for k, v in kw.items()))
    def test_validation(self, bad):
        """Every reader field is checked once, here; the engines take the
        policy as it is."""
        with pytest.raises(ValueError):
            IOPolicy(**bad)

    def test_per_open_override_does_not_mutate_fs_policy(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine="rolling", blocksize=512,
                                               eviction_interval_s=0.01))
        with fs:
            f = fs.open_many(metas(store), engine="sequential", blocksize=64)
            assert isinstance(f, SequentialFile)
            assert f.plan.blocksize == 64
            assert fs.policy.engine == "rolling"
            assert fs.policy.blocksize == 512

    @pytest.mark.parametrize("site,stamped", [
        ("loader", "loader"), ("restore", "ckpt"), ("from_store", "serve"),
    ])
    @pytest.mark.parametrize("explicit", [False, True], ids=["default", "explicit"])
    def test_call_site_stamps_its_workload_class(self, monkeypatch, site, stamped,
                                                  explicit):
        """A policy left at io_class="default" reaches the store as the call
        site's class; a caller's own class is kept."""
        policy = IOPolicy(eviction_interval_s=0.01,
                          io_class="eval" if explicit else "default")
        store = make_store(OBJECTS)
        if site == "loader":
            seen = _fs_policies(monkeypatch, "repro.data.loader")
            cfg = LoaderConfig(seq_len=8, batch_size=2, policy=policy)
            PrefetchingDataLoader(store, metas(store), [MemTier(1 << 20)], cfg).close()
        else:
            state = _checkpoint(store)
            seen = _fs_policies(monkeypatch, "repro.ckpt.manager")
            if site == "restore":
                restore_checkpoint(store, "ckpt", state, policy=policy)
            else:
                ServeEngine.from_store(None, store, "ckpt", state, policy=policy)
        assert [p.io_class for p in seen] == ["eval" if explicit else stamped]

    def test_from_store_restores_with_the_restore_default(self, monkeypatch):
        """A cold start that names no policy reads exactly the restore's own
        default, stamped as serving traffic: one default, written once."""
        store = make_store({})
        state = _checkpoint(store)
        seen = _fs_policies(monkeypatch, "repro.ckpt.manager")
        ServeEngine.from_store(None, store, "ckpt", state)
        assert seen == [IOPolicy(engine="rolling", blocksize=8 << 20, depth=2,
                                 eviction_interval_s=0.2, io_class="serve")]
        assert seen == [RESTORE_POLICY.replace(io_class="serve")]


# --------------------------------------------------------------------------- #
# reads through the facade
# --------------------------------------------------------------------------- #
class TestFacadeReads:
    @pytest.mark.parametrize("engine", ["rolling", "sequential", "direct"])
    def test_engines_byte_identical(self, engine):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine=engine, blocksize=256,
                                               eviction_interval_s=0.01))
        with fs:
            assert fs.open_many(metas(store)).read() == WANT

    def test_open_single_key_resolves_size(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine="direct"))
        with fs:
            f = fs.open("f1")
            assert f.size == len(OBJECTS["f1"])
            assert f.read() == OBJECTS["f1"]

    def test_open_with_list_delegates_to_open_many(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine="sequential", blocksize=256))
        with fs:
            assert fs.open(metas(store)).read() == WANT

    def test_default_tiers_built_on_demand_and_swept_on_close(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine="rolling", blocksize=256,
                                               eviction_interval_s=0.01,
                                               tier_capacity=8192))
        assert fs.tiers == []          # no tier until a rolling open needs one
        f = fs.open_many(metas(store))
        assert len(fs.tiers) == 1
        assert fs.tiers[0].capacity == 8192
        f.read()
        fs.close()
        assert fs.tiers[0].used == 0   # final sweep cleaned everything

    def test_backward_seek_direct_fallback_through_fs(self):
        store = make_store({"a": payload(1024)})
        fs = PrefetchFS(store, policy=IOPolicy(engine="rolling", blocksize=128,
                                               eviction_interval_s=0.001))
        with fs:
            f = fs.open("a")
            first = f.read(512)
            time.sleep(0.1)   # let eviction claim consumed blocks
            f.seek(0)
            assert f.read(512) == first
            assert f.stats.direct_reads >= 1

    def test_stats_aggregate_across_engines(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(blocksize=256,
                                               eviction_interval_s=0.01))
        with fs:
            fs.open_many(metas(store)).read()
            fs.open_many(metas(store), engine="sequential").read()
        snap = fs.stats().snapshot()
        assert snap["opens"] == 2
        assert set(snap["per_engine"]) == {"rolling", "sequential"}
        assert snap["totals"]["bytes_read"] == 2 * len(WANT)
        assert snap["per_engine"]["rolling"]["bytes_read"] == len(WANT)

    def test_closed_readers_fold_into_stats_without_accumulating(self):
        """Per-epoch reopen loops must not retain dead reader objects:
        closed readers are pruned on the next open, but their stats stay
        in the aggregate."""
        store = make_store(OBJECTS)
        fs = PrefetchFS(store, policy=IOPolicy(engine="sequential",
                                               blocksize=256))
        with fs:
            for _ in range(5):
                f = fs.open_many(metas(store))
                f.read()
                f.close()
            assert len(fs._handles) <= 1   # dead epochs pruned
            snap = fs.stats().snapshot()
        assert snap["opens"] == 5
        assert snap["totals"]["bytes_read"] == 5 * len(WANT)

    def test_closed_fs_rejects_open(self):
        store = make_store(OBJECTS)
        fs = PrefetchFS(store)
        fs.close()
        with pytest.raises(ValueError, match="closed PrefetchFS"):
            fs.open("f0")


# --------------------------------------------------------------------------- #
# rolling-engine thread-safety fixes
# --------------------------------------------------------------------------- #
class _SlowFailThenSlowSuccessStore(ObjectStore):
    """First request sleeps then fails; later requests sleep longer and
    succeed — the exact interleaving of the hedged-fetch race (primary
    errors while the launched secondary is still in flight)."""

    def __init__(self, data: bytes) -> None:
        self.data = data
        self.calls = 0
        self._lock = threading.Lock()

    def list_objects(self, prefix: str = ""):
        return [ObjectMeta("a", len(self.data))]

    def size(self, key: str) -> int:
        return len(self.data)

    def get_range(self, key: str, start: int, end: int) -> bytes:
        with self._lock:
            self.calls += 1
            call = self.calls
        if call == 1:
            time.sleep(0.03)
            raise TransientStoreError("primary straggler fails late")
        time.sleep(0.05)
        return self.data[start:end]

    def put(self, key: str, data: bytes) -> None:
        raise NotImplementedError

    def delete(self, key: str) -> None:
        raise NotImplementedError


class TestRollingThreadSafety:
    def test_hedge_waits_for_inflight_secondary(self):
        """The failed primary must not raise while the hedged secondary is
        still in flight: with retries disabled, only the secondary's success
        can produce the bytes."""
        data = payload(512)
        store = _SlowFailThenSlowSuccessStore(data)
        pf = RollingPrefetcher(
            store, [ObjectMeta("a", len(data))], [MemTier(4096)],
            policy=IOPolicy(blocksize=512, hedge_timeout_s=0.005,
                            retry=RetryPolicy(max_retries=0), eviction_interval_s=0.01),
        )
        with pf:
            assert pf.read_range(0, len(data)) == data
        assert pf.stats.hedges >= 1

    def test_hedge_both_attempts_fail_raises(self):
        data = payload(256)
        store = make_store({"a": data}, latency_s=0.02)
        store.link.fail_next(100)
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=256, hedge_timeout_s=0.005,
            retry=RetryPolicy(max_retries=1, backoff_s=0.001), eviction_interval_s=0.01,
        ))
        with fs:
            f = fs.open_many(metas(store))
            with pytest.raises(StoreError):
                f.read()

    def test_stats_bump_is_thread_safe(self):
        stats = PrefetchStats()
        n_threads, n_iters = 8, 2000

        def worker():
            for _ in range(n_iters):
                stats.bump(retries=1, fetch_s=0.5)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert stats.retries == n_threads * n_iters
        assert stats.fetch_s == pytest.approx(0.5 * n_threads * n_iters)

    def test_snapshot_is_consistent_under_concurrent_fetches(self):
        objects = {f"f{i}": payload(2048, seed=i) for i in range(4)}
        store = make_store(objects, latency_s=0.001)
        fs = PrefetchFS(store, policy=IOPolicy(engine="rolling", blocksize=256,
                                               depth=4,
                                               eviction_interval_s=0.01))
        with fs:
            f = fs.open_many(metas(store))
            assert f.read() == b"".join(objects[m.key] for m in metas(store))
            snap = f.stats.snapshot()
        assert snap["bytes_fetched"] == sum(len(v) for v in objects.values())
        assert "_lock" not in snap
