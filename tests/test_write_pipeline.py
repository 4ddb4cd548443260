"""Write-path tests: URI store registry, write-behind Writer (flush
barrier, retry, byte/stat parity with sync put), multipart stores,
pipelined checkpoint save, and the PR's satellite fixes (exists()
transient propagation, rolling restart-after-close, PrefetchFS
concurrency)."""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.core.rolling import RollingPrefetcher
from repro.io import (
    IOPolicy,
    PrefetchFS,
    RetryPolicy,
    Writer,
    available_stores,
    clear_store_cache,
    open_store,
    parse_store_uri,
    register_store,
)
from repro.io import stores as io_stores
from repro.store import DirStore, MemStore, MemTier, SimS3Store
from repro.store.base import ObjectMeta, StoreError, TransientStoreError


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


@pytest.fixture(autouse=True)
def _fresh_store_cache():
    clear_store_cache()
    yield
    clear_store_cache()


# --------------------------------------------------------------------------- #
# store registry
# --------------------------------------------------------------------------- #
class TestStoreRegistry:
    def test_builtin_schemes(self):
        assert {"mem", "local", "sims3"} <= set(available_stores())

    def test_uri_parsing(self):
        u = parse_store_uri("sims3://bucket/pfx?latency_ms=40&bw_mbps=200")
        assert u.scheme == "sims3"
        assert u.location == "bucket/pfx"
        assert u.params == {"latency_ms": "40", "bw_mbps": "200"}

    def test_mem_local_sims3_dispatch(self, tmp_path):
        assert isinstance(open_store("mem://scratch"), MemStore)
        assert isinstance(open_store(f"local://{tmp_path}/d"), DirStore)
        s = open_store("sims3://b?latency_ms=40&bw_mbps=200")
        assert isinstance(s, SimS3Store)
        assert s.link.latency_s == pytest.approx(0.04)
        assert s.link.bandwidth_Bps == pytest.approx(200e6)

    def test_asymmetric_put_link(self):
        s = open_store("sims3://b?latency_ms=10&put_latency_ms=30&put_bw_mbps=50")
        assert s.put_link is not s.link
        assert s.put_link.latency_s == pytest.approx(0.03)
        assert s.put_link.bandwidth_Bps == pytest.approx(50e6)

    def test_same_uri_shares_instance_fresh_bypasses(self):
        a = open_store("mem://shared")
        b = open_store("mem://shared")
        c = open_store("mem://shared", fresh=True)
        d = open_store("mem://other")
        assert a is b
        assert c is not a
        assert d is not a
        a.put("k", b"x")
        assert b.get("k") == b"x"

    def test_store_instance_passthrough(self):
        s = MemStore()
        assert open_store(s) is s

    def test_param_order_shares_one_instance(self):
        """canonical() sorts params: two spellings of the same bucket must
        map to ONE cached instance (one LinkModel, one state)."""
        a = open_store("sims3://b?latency_ms=40&bw_mbps=200")
        b = open_store("sims3://b?bw_mbps=200&latency_ms=40")
        assert a is b

    def test_percent_encoded_params_do_not_collide(self):
        """Regression: parse_qsl decodes escapes, so a canonical form that
        re-joined raw values collapsed ``?a=1&b=2`` with ``?a=1%26b%3D2``
        (ONE param whose value is "1&b=2") — two different stores shared
        one cached instance."""
        u1 = parse_store_uri("x://b?a=1&b=2")
        u2 = parse_store_uri("x://b?a=1%26b%3D2")
        assert u1.params != u2.params
        assert u1.canonical() != u2.canonical()
        # And through the cache: distinct params -> distinct instances.
        made = []

        @register_store("canon-test")
        def _factory(uri):
            made.append(dict(uri.params))
            return MemStore()

        try:
            a = open_store("canon-test://b?a=1&b=2")
            b = open_store("canon-test://b?a=1%26b%3D2")
            assert a is not b
            assert made == [{"a": "1", "b": "2"}, {"a": "1&b=2"}]
        finally:
            io_stores._REGISTRY.pop("canon-test")

    def test_unknown_scheme_and_params_raise(self):
        with pytest.raises(ValueError, match="unknown store scheme"):
            open_store("bogus://x")
        with pytest.raises(ValueError, match="unknown store URI params"):
            open_store("sims3://b?latency=oops")
        with pytest.raises(ValueError, match="not a store URI"):
            open_store("no-scheme-here")

    def test_new_scheme_plugs_in(self):
        calls = []

        @register_store("test-scheme")
        def _factory(uri):
            calls.append(uri.location)
            return MemStore()

        try:
            fs = PrefetchFS("test-scheme://bucket")
            assert isinstance(fs.store, MemStore)
            assert calls == ["bucket"]
        finally:
            io_stores._REGISTRY.pop("test-scheme")

    def test_duplicate_scheme_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_store("mem")(lambda uri: MemStore())


# --------------------------------------------------------------------------- #
# write-behind Writer
# --------------------------------------------------------------------------- #
def make_fs(uri="sims3://wtest?latency_ms=1", **policy_kw) -> PrefetchFS:
    policy_kw.setdefault("blocksize", 1024)
    policy_kw.setdefault("write_depth", 3)
    policy_kw.setdefault("retry", RetryPolicy(backoff_s=0.001))
    policy_kw.setdefault("eviction_interval_s", 0.01)
    return PrefetchFS(open_store(uri, fresh=True),
                      policy=IOPolicy(**policy_kw))


class TestWriter:
    def test_multi_part_byte_identity_and_readback(self):
        data = payload(10_000)   # 10 parts of 1024 v 1 remainder
        fs = make_fs()
        with fs.open_write("obj") as w:
            # odd-sized writes crossing part boundaries
            for lo in range(0, len(data), 777):
                w.write(data[lo:lo + 777])
        assert fs.store.backing.get("obj") == data
        assert fs.open("obj", engine="direct").read() == data
        fs.close()

    def test_single_part_uses_plain_put(self):
        fs = make_fs(blocksize=1 << 20)
        with fs.open_write("small") as w:
            w.write(b"tiny")
        assert w._mp is None          # single background put, no multipart
        assert fs.store.backing.get("small") == b"tiny"
        fs.close()

    def test_object_invisible_until_close(self):
        fs = make_fs()
        w = fs.open_write("late")
        w.write(payload(4096))
        w.flush()                      # parts durable, object NOT published
        assert not fs.store.backing.exists("late")
        w.close()
        assert fs.store.backing.exists("late")
        fs.close()

    def test_flush_is_durability_barrier(self):
        fs = make_fs()
        w = fs.open_write("flushy")
        w.write(payload(3000))
        w.flush()
        snap = w.stats.snapshot()
        # every sealed part (2 full + 1 partial) uploaded before flush returned
        assert snap["parts_uploaded"] == 3
        assert snap["bytes_uploaded"] == 3000
        w.write(payload(500, seed=1))
        w.close()
        assert fs.store.backing.get("flushy") == payload(3000) + payload(500, seed=1)
        fs.close()

    def test_partial_upload_retry(self):
        fs = make_fs()
        fs.store.put_link.fail_next(2)   # two part uploads throttle once each
        data = payload(5000)
        with fs.open_write("retry") as w:
            w.write(data)
        assert fs.store.backing.get("retry") == data
        assert w.stats.snapshot()["retries"] >= 2

    def test_permanent_failure_raises_and_never_publishes(self):
        fs = make_fs(retry=RetryPolicy(max_retries=1, backoff_s=0.001))
        fs.store.put_link.fail_next(1000)
        w = fs.open_write("doomed")
        w.write(payload(5000))
        with pytest.raises(StoreError):
            w.close()
        assert w.closed
        assert not fs.store.backing.exists("doomed")

    def test_stats_parity_with_sync_put(self):
        data = payload(8192)
        sync_store = open_store("sims3://sync?latency_ms=1", fresh=True)
        sync_store.put("obj", data)
        fs = make_fs()
        with fs.open_write("obj") as w:
            w.write(data)
        assert fs.store.backing.get("obj") == sync_store.backing.get("obj")
        snap = w.stats.snapshot()
        assert snap["bytes_written"] == snap["bytes_uploaded"] == len(data)

    def test_hedged_put(self):
        fs = make_fs(uri="sims3://hedge?latency_ms=30", hedge_timeout_s=0.003)
        data = payload(2048)
        with fs.open_write("h") as w:
            w.write(data)
        assert fs.store.backing.get("h") == data
        assert w.stats.snapshot()["hedges"] >= 1

    def test_pool_refusing_job_unwinds_seal_barrier(self):
        # If the pool is closed underneath the writer, the seal's barrier
        # count must be unwound — otherwise flush()/close() wait forever
        # for an upload job that was never queued.
        fs = make_fs()
        w = fs.open_write("refused")

        class _ClosedPool:
            def submit(self, job):
                raise ValueError("submit on closed UploadPool")

        orig_pool = w._pool
        w._pool = _ClosedPool()
        with pytest.raises(ValueError):
            w.write(payload(2048))      # seals part 0, pool refuses
        # Barrier accounting balanced: flush() returns instead of hanging
        # forever on an upload job that was never queued.
        assert w._sealed == w._done
        w._pool = orig_pool
        w.flush()
        w.abort()
        fs.close()

    def test_staging_tier_write_failure_returns_budget_and_raises(self):
        fs = make_fs()
        w = fs.open_write("torn")
        tier = w.tiers[0]
        free_before = tier.available()

        def torn_write(*a, **kw):
            raise OSError("disk gone")

        orig = tier.write
        tier.write = torn_write
        try:
            with pytest.raises(OSError):
                w.write(payload(2048))
        finally:
            tier.write = orig
        # The failed reservation was cancelled, not leaked.
        assert tier.available() == free_before
        assert w._sealed == w._done
        w.abort()
        fs.close()

    def test_write_after_close_and_join_without_close_async(self):
        fs = make_fs()
        w = fs.open_write("x")
        w.write(b"abc")
        with pytest.raises(ValueError, match="join"):
            w.join()
        w.close()
        with pytest.raises(ValueError, match="closed"):
            w.write(b"more")
        fs.close()

    def test_backpressure_bounded_staging(self):
        # Tiny staging tier: the writer must block rather than buffer
        # unboundedly, and everything still lands.
        fs = PrefetchFS(open_store("sims3://bp?latency_ms=2", fresh=True),
                        policy=IOPolicy(blocksize=512, write_depth=1),
                        tiers=[MemTier(1024)])
        data = payload(8192)
        with fs.open_write("big") as w:
            w.write(data)
        assert fs.store.backing.get("big") == data
        assert fs.tiers[0].used == 0   # staging space fully released
        fs.close()

    def test_writer_stats_fold_into_fs_stats(self):
        fs = make_fs()
        with fs.open_write("a") as w:
            w.write(payload(2048))
        fs.open("a", engine="direct").read()
        snap = fs.stats().snapshot()
        assert "write-behind" in snap["per_engine"]
        assert snap["per_engine"]["write-behind"]["bytes_uploaded"] == 2048
        assert snap["totals"]["bytes_uploaded"] == 2048
        assert snap["totals"]["bytes_read"] == 2048
        fs.close()

    def test_open_write_on_closed_fs(self):
        fs = make_fs()
        fs.close()
        with pytest.raises(ValueError, match="closed PrefetchFS"):
            fs.open_write("k")


# --------------------------------------------------------------------------- #
# multipart store support
# --------------------------------------------------------------------------- #
class TestMultipart:
    def test_memstore_default_multipart(self):
        s = MemStore()
        mp = s.start_multipart("k")
        mp.put_part(1, b"world")
        mp.put_part(0, b"hello ")
        mp.complete()
        assert s.get("k") == b"hello world"

    def test_non_contiguous_parts_rejected(self):
        s = MemStore()
        mp = s.start_multipart("k")
        mp.put_part(0, b"a")
        mp.put_part(2, b"c")
        with pytest.raises(StoreError, match="non-contiguous"):
            mp.complete()

    def test_abort_never_publishes(self):
        s = MemStore()
        mp = s.start_multipart("k")
        mp.put_part(0, b"a")
        mp.abort()
        with pytest.raises(StoreError):
            mp.put_part(1, b"b")
        assert not s.exists("k")

    def test_dirstore_multipart_cleans_part_files(self, tmp_path):
        s = DirStore(str(tmp_path))
        mp = s.start_multipart("sub/obj")
        mp.put_part(0, b"aa")
        mp.put_part(1, b"bb")
        mp.complete()
        assert s.get("sub/obj") == b"aabb"
        leftovers = [m.key for m in s.list_objects() if ".mpart" in m.key]
        assert leftovers == []

    def test_sims3_multipart_charges_put_link(self):
        s = open_store("sims3://mp?latency_ms=0", fresh=True)
        mp = s.start_multipart("k")
        mp.put_part(0, payload(100))
        assert s.put_link.bytes_moved == 100   # paid at part time, not complete
        mp.complete()
        assert s.backing.get("k") == payload(100)


# --------------------------------------------------------------------------- #
# checkpoint save through the pipeline
# --------------------------------------------------------------------------- #
class TestCheckpointWritePath:
    def _state(self):
        return {
            "w": np.arange(4096, dtype=np.float32).reshape(64, 64),
            "b": np.ones(17, dtype=np.float64),
            "step": np.int32(3),
        }

    def test_byte_identical_to_legacy_sync_path(self):
        from repro.ckpt.manager import save_checkpoint

        state = self._state()
        wb_store = open_store("mem://wb-ckpt", fresh=True)
        save_checkpoint(wb_store, "ckpt", 7, state,
                        policy=IOPolicy(blocksize=4096, write_depth=3))

        legacy = open_store("mem://legacy-ckpt", fresh=True)
        import jax

        leaves = jax.device_get(jax.tree_util.tree_flatten(state)[0])
        for idx, leaf in enumerate(leaves):
            legacy.put(f"ckpt/step_{7:08d}/{idx:06d}.raw",
                       np.asarray(leaf).tobytes())
        for idx in range(len(leaves)):
            key = f"ckpt/step_{7:08d}/{idx:06d}.raw"
            assert wb_store.get(key) == legacy.get(key)
        manifest = json.loads(wb_store.get(f"ckpt/step_{7:08d}/MANIFEST.json"))
        assert manifest["step"] == 7
        assert len(manifest["leaves"]) == len(leaves)

    def test_roundtrip_through_uri_store(self):
        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        state = self._state()
        uri = "sims3://ckpt-uri?latency_ms=1"
        save_checkpoint(uri, "ckpt", 1, state,
                        policy=IOPolicy(blocksize=2048, write_depth=4))
        restored, manifest = restore_checkpoint(uri, "ckpt", state)
        assert manifest["step"] == 1
        for a, b in zip(np.asarray(restored["w"]), state["w"]):
            np.testing.assert_array_equal(a, b)

    def test_failed_save_leaves_no_manifest(self):
        from repro.ckpt.manager import latest_step, save_checkpoint

        store = open_store("sims3://ckpt-fail?latency_ms=0", fresh=True)
        store.put_link.fail_next(1000)
        with pytest.raises(StoreError):
            save_checkpoint(store, "ckpt", 5, self._state(),
                            policy=IOPolicy(retry=RetryPolicy(max_retries=0),
                                            blocksize=1024))
        # inspect the substrate directly: the failed step must be invisible
        assert latest_step(store.backing, "ckpt") is None


# --------------------------------------------------------------------------- #
# satellite fixes
# --------------------------------------------------------------------------- #
class TestSatellites:
    def test_exists_propagates_transient_errors(self):
        s = open_store("sims3://ex?latency_ms=0", fresh=True)
        s.backing.put("k", b"x")
        s.link.fail_next(1)
        with pytest.raises(TransientStoreError):
            s.exists("k")            # throttled != missing
        assert s.exists("k") is True
        assert s.exists("nope") is False

    def test_rolling_prefetcher_refuses_restart_after_close(self):
        store = open_store("mem://rp", fresh=True)
        store.put("a", payload(256))
        pf = RollingPrefetcher(
            store, [ObjectMeta("a", 256)], [MemTier(4096)],
            policy=IOPolicy(blocksize=64, eviction_interval_s=0.01),
        )
        with pf:
            assert pf.read_range(0, 256) == payload(256)
        pf.close()   # double close is a no-op
        assert pf._threads == []
        with pytest.raises(RuntimeError, match="cannot restart"):
            pf.start()

    def test_open_many_on_closed_fs_issues_no_store_requests(self):
        class CountingStore(MemStore):
            def __init__(self):
                super().__init__()
                self.size_calls = 0

            def size(self, key):
                self.size_calls += 1
                return super().size(key)

        store = CountingStore()
        store.put("k", b"x")
        fs = PrefetchFS(store)
        fs.close()
        with pytest.raises(ValueError, match="closed PrefetchFS"):
            fs.open_many(["k"])      # string key would need a size() lookup
        assert store.size_calls == 0

    def test_concurrent_open_close_stats(self):
        """Stats folding must stay consistent under concurrent
        open/read/close/stats from many threads."""
        objects = {f"f{i}": payload(2048, seed=i) for i in range(4)}
        store = open_store("mem://conc", fresh=True)
        for k, v in objects.items():
            store.put(k, v)
        fs = PrefetchFS(store, policy=IOPolicy(engine="sequential",
                                               blocksize=512))
        n_threads, n_iters = 6, 10
        errors = []

        def reader_worker(tid):
            try:
                for _ in range(n_iters):
                    f = fs.open(f"f{tid % 4}")
                    f.read()
                    f.close()
                    fs.stats()
            except Exception as e:  # repro: allow[RP005] — stashed; asserted after join
                errors.append(e)

        def writer_worker(tid):
            try:
                for i in range(n_iters):
                    with fs.open_write(f"out/{tid}/{i}", blocksize=4096) as w:
                        w.write(payload(1000, seed=tid))
                    fs.stats()
            except Exception as e:  # repro: allow[RP005] — stashed; asserted after join
                errors.append(e)

        threads = [threading.Thread(target=reader_worker, args=(t,))
                   for t in range(n_threads)]
        threads += [threading.Thread(target=writer_worker, args=(t,))
                    for t in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        snap = fs.stats().snapshot()
        want_read = sum(len(objects[f"f{t % 4}"]) * n_iters
                        for t in range(n_threads))
        assert snap["per_engine"]["sequential"]["bytes_read"] == want_read
        assert snap["per_engine"]["sequential"]["opens"] == n_threads * n_iters
        assert snap["per_engine"]["write-behind"]["bytes_uploaded"] == \
            2 * n_iters * 1000
        fs.close()

    def test_writer_protocol_surface(self):
        fs = make_fs()
        w = fs.open_write("k")
        assert isinstance(w, Writer)
        assert w.tell() == 0
        w.write(b"abcd")
        assert w.tell() == 4
        assert not w.closed
        w.close()
        assert w.closed
        fs.close()
