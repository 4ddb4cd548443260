"""Shared crash-consistent cache tiers (PR tentpole + satellites).

Covers: the persistent journaled `DirTier` (crash recovery, torn-block
discard, orphan/tmp cleanup, collision-free filenames), the shared
`CacheIndex` (single-flight fetch registration, refcount-aware eviction,
warm reuse), cross-reader sharing for the rolling AND sequential engines
through `PrefetchFS`, warm restarts (zero store GETs for recovered
blocks), and the write-path fixes (UploadPool submit/close race,
Writer.abort multipart part leak, tier `used` overwrite accounting).
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from repro.io import IOPolicy, PrefetchFS, UploadPool
from repro.store import (
    BlockMeta,
    CacheIndex,
    DirStore,
    DirTier,
    LinkModel,
    MemTier,
    SimS3Store,
)
from repro.store.base import ObjectMeta, StoreError


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def make_store(objects: dict[str, bytes], latency=0.0, **kw) -> SimS3Store:
    store = SimS3Store(link=LinkModel(latency_s=latency, **kw))
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


def metas(store) -> list[ObjectMeta]:
    return store.backing.list_objects()


# --------------------------------------------------------------------------- #
# DirTier filename encoding (satellite: key-collision fix)
# --------------------------------------------------------------------------- #
class TestDirTierPathEncoding:
    def test_slash_and_literal_underscores_do_not_collide(self, tmp_path):
        """Regression: the old `replace("/", "__")` mapped distinct ids
        `a/b` and `a__b` onto the same file and silently served wrong
        bytes."""
        tier = DirTier(1 << 20, root=str(tmp_path / "t"))
        tier.write("a/b", b"slash")
        tier.write("a__b", b"underscore")
        assert tier.read("a/b") == b"slash"
        assert tier.read("a__b") == b"underscore"

    def test_hostile_ids_roundtrip(self, tmp_path):
        tier = DirTier(1 << 20, root=str(tmp_path / "t"))
        ids = ["k@000-100", "k%2Fx", "a/b/c", "a b c", "%", "..", "blk-x",
               "_index.jsonl"]
        for i, bid in enumerate(ids):
            tier.write(bid, payload(32, seed=i))
        for i, bid in enumerate(ids):
            assert tier.read(bid) == payload(32, seed=i), bid
        # The journal survived writes of ids that mimic its own name.
        tier.close()
        tier2 = DirTier(1 << 20, root=str(tmp_path / "t"))
        assert tier2.recovered_blocks == len(ids)


# --------------------------------------------------------------------------- #
# tier `used` accounting (satellite: overwrite double-count fix)
# --------------------------------------------------------------------------- #
class TestOverwriteAccounting:
    @pytest.mark.parametrize("make_tier", [
        lambda tmp: MemTier(1 << 20),
        lambda tmp: DirTier(1 << 20, root=str(tmp / "t")),
    ])
    def test_overwrite_credits_replaced_bytes(self, tmp_path, make_tier):
        tier = make_tier(tmp_path)
        data = payload(1000)
        for _ in range(3):
            assert tier.reserve(len(data))
            tier.write("blk", data)
            tier.commit(len(data))
        # Without the credit-back, used would read 3000 until some later
        # verify_used() happened to run.
        assert tier.used == len(data)
        assert tier.verify_used() == tier.capacity - len(data)


# --------------------------------------------------------------------------- #
# DirTier journal: persistence + crash recovery (satellite: crash test)
# --------------------------------------------------------------------------- #
class TestDirTierPersistence:
    def test_restart_recovers_index_and_used(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        blocks = {f"k@{i}": payload(200 + i, seed=i) for i in range(5)}
        for bid, data in blocks.items():
            tier.write(bid, data, meta=BlockMeta(key="k", offset=0))
        tier.delete("k@0")
        del blocks["k@0"]

        tier.close()   # "process" dies; the restart owns the root
        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == len(blocks)
        assert dict(tier2.resident_blocks()) == {
            bid: len(d) for bid, d in blocks.items()
        }
        # `used` is seeded with the recovered bytes, so reserve() cannot
        # overshoot the budget, and verify_used is already consistent.
        assert tier2.used == sum(len(d) for d in blocks.values())
        for bid, data in blocks.items():
            assert tier2.read(bid) == data

    def test_crash_between_tmp_write_and_replace(self, tmp_path, monkeypatch):
        """Kill the tier mid-`_write` (after the tmp file, before the
        atomic rename): reconstruction must recover the intact blocks,
        discard the torn one, and converge verify_used."""
        import repro.store.tiers as tiers_mod

        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("good", payload(300))

        real_replace = os.replace

        def crashing_replace(src, dst):
            if os.path.basename(dst).startswith(DirTier.BLOCK_PREFIX):
                raise OSError("injected crash before rename")
            return real_replace(src, dst)

        monkeypatch.setattr(tiers_mod.os, "replace", crashing_replace)
        with pytest.raises(OSError):
            tier.write("torn", payload(400))
        monkeypatch.setattr(tiers_mod.os, "replace", real_replace)

        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == 1
        assert tier2.read("good") == payload(300)
        assert not tier2.contains("torn")
        # No leftover tmp files, and accounting converges to reality.
        assert not [f for f in os.listdir(root) if f.endswith(".tmp")]
        assert tier2.used == 300
        assert tier2.verify_used() == tier2.capacity - 300
        assert tier2._resident_bytes() == 300

    def test_torn_block_discarded_by_checksum(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("good", payload(300))
        tier.write("torn", payload(400))
        # Corrupt "torn" behind the journal's back (a partial flush the
        # rename made visible anyway, bit rot, ...).
        with open(tier._path("torn"), "wb") as f:
            f.write(payload(400)[:123])

        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == 1
        assert tier2.discarded_blocks == 1
        assert not tier2.contains("torn")          # file deleted too
        assert tier2.read("good") == payload(300)

    def test_torn_journal_tail_is_ignored(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("a", payload(100))
        with open(os.path.join(root, DirTier.INDEX_NAME), "a") as f:
            f.write('{"op": "put", "id": "half')   # crash mid-append
        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == 1
        assert tier2.read("a") == payload(100)

    def test_transient_staging_not_resurrected(self, tmp_path):
        """Write-behind staging parts (durable=False) must die with the
        process — recovery deletes them as orphans."""
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("wb/0001/out/000000", payload(256), durable=False)
        tier.write("real", payload(100))
        assert tier.contains("wb/0001/out/000000")
        assert tier.resident_blocks() == [("real", 100)]

        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        assert not tier2.contains("wb/0001/out/000000")
        assert tier2.resident_blocks() == [("real", 100)]

    def test_second_live_tier_is_nondestructive(self, tmp_path):
        """A sibling DirTier over the same root (two replicas sharing a
        node's cache dir) must never sweep the live owner's files: it
        recovers read-only and skips orphan/torn cleanup + compaction."""
        root = str(tmp_path / "cache")
        owner = DirTier(1 << 20, root=root)
        owner.write("a", payload(100))
        # A block file the journal doesn't know yet (mid-flight sibling
        # write between rename and journal append).
        with open(owner._path("inflight"), "wb") as f:
            f.write(payload(50))

        sibling = DirTier(1 << 20, root=root)
        assert sibling.owns_root is False
        assert sibling.recovered_blocks == 1          # journal replayed
        assert os.path.exists(owner._path("inflight"))  # NOT swept
        assert owner.read("a") == payload(100)

        owner.close()
        sibling.close()
        restarted = DirTier(1 << 20, root=root)        # sole owner again
        assert restarted.owns_root is True
        assert not os.path.exists(owner._path("inflight"))  # now swept

    def test_fcntl_unavailable_fallback_is_single_owner(self, tmp_path,
                                                        monkeypatch):
        """Regression (non-POSIX fallback): without fcntl, EVERY opener
        used to believe it owned the root, and two live tiers would sweep
        each other's files as orphans. The marker-file fallback makes
        ownership first-opener-wins; later openers recover read-only."""
        import repro.store.tiers as tiers_mod

        monkeypatch.setattr(tiers_mod, "fcntl", None)
        root = str(tmp_path / "cache")
        owner = DirTier(1 << 20, root=root)
        assert owner.owns_root is True
        owner.write("a", payload(100))
        # A block file the journal doesn't know (mid-flight sibling write).
        with open(owner._path("inflight"), "wb") as f:
            f.write(payload(50))

        sibling = DirTier(1 << 20, root=root)
        assert sibling.owns_root is False              # NOT a second owner
        assert sibling.recovered_blocks == 1           # journal replayed
        assert os.path.exists(owner._path("inflight"))  # not swept
        assert sibling.read("a") == payload(100)

        owner.close()
        sibling.close()
        reopened = DirTier(1 << 20, root=root)         # marker released
        assert reopened.owns_root is True
        assert not os.path.exists(owner._path("inflight"))  # owner sweeps
        reopened.close()

    def test_fcntl_unavailable_stale_marker_is_conservative(self, tmp_path,
                                                            monkeypatch):
        """A crash leaves the owner marker behind; the next opener must
        come up read-only (never destructive) until it is removed."""
        import repro.store.tiers as tiers_mod

        monkeypatch.setattr(tiers_mod, "fcntl", None)
        root = str(tmp_path / "cache")
        crashed = DirTier(1 << 20, root=root)
        crashed.write("a", payload(64))
        # No close(): simulated crash; the marker file is still there.
        after = DirTier(1 << 20, root=root)
        assert after.owns_root is False
        assert after.read("a") == payload(64)
        os.remove(os.path.join(root, DirTier.LOCK_NAME + ".owner"))
        reclaimed = DirTier(1 << 20, root=root)
        assert reclaimed.owns_root is True
        reclaimed.close()

    def test_compaction_racing_nonowner_writer_keeps_its_blocks(self,
                                                                tmp_path):
        """Satellite: owner journal compaction racing a live read-only
        sibling's writes. The compaction rewrite replays the journal under
        the cross-process flock, so records the sibling appended mid-churn
        survive — a restart recovers BOTH writers' blocks."""
        root = str(tmp_path / "cache")
        owner = DirTier(1 << 20, root=root)
        owner._COMPACT_SLACK = 10       # compact every ~15 records
        sibling = DirTier(1 << 20, root=root)
        assert sibling.owns_root is False
        stop, errs = threading.Event(), []

        def sib_writes():
            try:
                i = 0
                while not stop.is_set():
                    sibling.write(f"sib{i % 10}", payload(64, seed=i))
                    i += 1
            except Exception as e:   # repro: allow[RP005] — surfaced below
                errs.append(e)

        t = threading.Thread(target=sib_writes)
        t.start()
        # Owner churn forces repeated compaction while the sibling writes.
        for round_ in range(20):
            for i in range(5):
                owner.write(f"own{i}", payload(64, seed=round_))
        stop.set()
        t.join(timeout=30)
        assert not errs
        owner.close()
        sibling.close()

        restarted = DirTier(1 << 20, root=root)
        resident = dict(restarted.resident_blocks())
        for i in range(5):
            assert f"own{i}" in resident
            assert restarted.read(f"own{i}") == payload(64, seed=19)
        sib_blocks = [b for b in resident if b.startswith("sib")]
        assert sib_blocks, "sibling's journal records lost in compaction"
        for bid in sib_blocks:
            assert len(restarted.read(bid)) == 64
        restarted.close()

    def test_journal_compaction_preserves_state(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier._COMPACT_SLACK = 10
        for round_ in range(8):
            for i in range(5):
                tier.write(f"b{i}", payload(64, seed=round_))
        journal = os.path.join(root, DirTier.INDEX_NAME)
        with open(journal) as f:
            assert len(f.readlines()) <= 15   # compacted, not 40 records
        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == 5
        for i in range(5):
            assert tier2.read(f"b{i}") == payload(64, seed=7)


# --------------------------------------------------------------------------- #
# CacheIndex unit behaviour
# --------------------------------------------------------------------------- #
class TestCacheIndex:
    def _tier(self) -> MemTier:
        return MemTier(1 << 20)

    def test_single_flight_and_waiter_pinning(self):
        tier = self._tier()
        idx = CacheIndex([tier])
        kind, flight = idx.acquire("b")
        assert kind == "leader"
        kind2, flight2 = idx.acquire("b")
        assert kind2 == "wait" and flight2 is flight
        tier.reserve(3)
        tier.write("b", b"xyz")
        tier.commit(3)
        idx.publish(flight, tier, 3)
        assert idx.join(flight) == ("hit", tier)
        # Leader + one waiter hold pins: first want_evict unpin keeps the
        # block alive for the other reader.
        assert idx.unpin("b", want_evict=True) is False
        assert tier.contains("b")
        assert idx.unpin("b", want_evict=True) is True
        assert not tier.contains("b")
        assert tier.used == 0

    def test_keep_cached_defers_to_capacity_pressure(self):
        tier = self._tier()
        idx = CacheIndex([tier], keep_cached=True)
        kind, flight = idx.acquire("b")
        assert kind == "leader"
        tier.reserve(4)
        tier.write("b", b"data")
        tier.commit(4)
        idx.publish(flight, tier, 4)
        assert idx.unpin("b", want_evict=True) is False   # kept warm
        assert tier.contains("b")
        kind, t = idx.acquire("b")                        # next epoch: hit
        assert kind == "hit" and t is tier
        idx.unpin("b")
        assert idx.evict_from(tier, 1) == 4               # pressure evicts
        assert not tier.contains("b")

    def test_pinned_blocks_survive_pressure_eviction(self):
        tier = self._tier()
        idx = CacheIndex([tier])
        for bid in ("p", "q"):
            _, fl = idx.acquire(bid)
            tier.reserve(2)
            tier.write(bid, b"..")
            tier.commit(2)
            idx.publish(fl, tier, 2)
        idx.unpin("q")   # q unpinned -> evictable; p still pinned
        assert idx.evict_from(tier, 1 << 10) == 2
        assert tier.contains("p") and not tier.contains("q")

    def test_leader_failure_lets_waiters_take_over(self):
        idx = CacheIndex([self._tier()])
        kind, flight = idx.acquire("b")
        assert kind == "leader"
        kind, same = idx.acquire("b")
        assert kind == "wait"
        idx.abort_fetch(flight, StoreError("boom"))
        kind, err = idx.join(same)
        assert kind == "failed" and isinstance(err, StoreError)
        kind, retry = idx.acquire("b")
        assert kind == "leader"    # the waiter retries as the new leader
        idx.abort_fetch(retry)

    def test_primes_from_persistent_tier(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("warm", payload(128))
        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        idx = CacheIndex([tier2])
        assert idx.recovered == 1
        kind, t = idx.acquire("warm")
        assert kind == "hit" and t is tier2
        assert t.read("warm") == payload(128)


# --------------------------------------------------------------------------- #
# cross-reader single flight through PrefetchFS
# --------------------------------------------------------------------------- #
class TestSharedReaders:
    def test_n_rolling_readers_fetch_each_block_once(self):
        objects = {"f": payload(16 << 10)}
        store = make_store(objects, latency=0.004)
        n_readers, blocksize = 4, 1024
        nblocks = len(objects["f"]) // blocksize
        fs = PrefetchFS(store,
                        policy=IOPolicy(engine="rolling", blocksize=blocksize,
                                        keep_cached=True,
                                        eviction_interval_s=0.01),
                        tiers=[MemTier(1 << 20)])
        results, readers, errs = [None] * n_readers, [None] * n_readers, []

        def run(i):
            try:
                f = fs.open("f")
                readers[i] = f
                results[i] = f.read()
                f.close()
            except Exception as e:   # repro: allow[RP005] — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fs.close()
        assert not errs
        assert all(r == objects["f"] for r in results)
        # The tentpole claim: N concurrent readers of one file issue ~1x
        # (not Nx) block fetches — every block crosses the store once.
        total_fetched = sum(r.stats.blocks_fetched for r in readers)
        assert total_fetched == nblocks
        served = sum(r.stats.blocks_fetched + r.stats.cache_hits
                     + r.stats.flight_joins for r in readers)
        assert served == n_readers * nblocks

    def test_reopen_is_warm_with_keep_cached(self):
        objects = {"f": payload(8 << 10)}
        store = make_store(objects)
        fs = PrefetchFS(store,
                        policy=IOPolicy(engine="rolling", blocksize=1024,
                                        keep_cached=True,
                                        eviction_interval_s=0.01),
                        tiers=[MemTier(1 << 20)])
        with fs:
            f1 = fs.open("f")
            assert f1.read() == objects["f"]
            f1.close()
            f2 = fs.open("f")
            assert f2.read() == objects["f"]
            f2.close()
            assert f2.stats.blocks_fetched == 0      # second epoch: all warm
            assert f2.stats.cache_hits == 8
            assert fs.stats().cache["hits"] >= 8

    def test_backward_seek_served_from_warm_cache(self):
        """With keep_cached, a backward seek to a consumed block is a
        local cache hit, not a fresh store GET."""
        objects = {"f": payload(4096)}
        store = make_store(objects)
        fs = PrefetchFS(store,
                        policy=IOPolicy(engine="rolling", blocksize=1024,
                                        keep_cached=True,
                                        eviction_interval_s=0.01),
                        tiers=[MemTier(1 << 20)])
        with fs:
            f = fs.open("f")
            assert f.read() == objects["f"]
            f.seek(0)
            assert f.read(1024) == objects["f"][:1024]
            assert f.stats.direct_reads == 0
            assert f.stats.cache_hits >= 1
            f.close()

    def test_sequential_engine_shares_through_fs_tiers(self):
        objects = {"f": payload(4 << 10)}
        store = make_store(objects)
        fs = PrefetchFS(store,
                        policy=IOPolicy(engine="sequential", blocksize=512,
                                        keep_cached=True),
                        tiers=[MemTier(1 << 20)])
        with fs:
            r1 = fs.open("f")
            assert r1.read() == objects["f"]
            r2 = fs.open("f")
            assert r2.read() == objects["f"]
            assert r1.stats.store_requests == 8
            assert r2.stats.store_requests == 0
            assert r2.stats.cache_hits == 8

    def test_sequential_without_keep_cached_does_not_retain(self):
        """Default policy: published blocks are evicted once consumed, so
        a long-lived fs does not silently hold tier capacity."""
        objects = {"f": payload(2 << 10)}
        store = make_store(objects)
        tier = MemTier(1 << 20)
        fs = PrefetchFS(store,
                        policy=IOPolicy(engine="sequential", blocksize=512),
                        tiers=[tier])
        with fs:
            r1 = fs.open("f")
            assert r1.read() == objects["f"]
        assert tier.used == 0
        assert tier._resident_bytes() == 0

    def test_bare_sequential_baseline_unchanged(self):
        """No index -> the paper's baseline request shape is untouched."""
        from repro.core import SequentialFile

        objects = {"f": payload(4 << 10)}
        store = make_store(objects)
        f = SequentialFile(store, metas(store), policy=IOPolicy(blocksize=512))
        assert f.read() == objects["f"]
        assert f.stats.store_requests == f.stats.blocks_fetched == 8


# --------------------------------------------------------------------------- #
# warm restart: persistent DirTier + recovered index => zero store GETs
# --------------------------------------------------------------------------- #
class TestWarmRestart:
    def test_restarted_job_pays_zero_gets_for_cached_blocks(self, tmp_path):
        objects = {"f0": payload(6 << 10), "f1": payload(6 << 10, seed=1)}
        store = make_store(objects)
        root = str(tmp_path / "cache")
        policy = IOPolicy(engine="rolling", blocksize=1024, keep_cached=True,
                          eviction_interval_s=0.01)

        fs1 = PrefetchFS(store, policy=policy,
                         tiers=[DirTier(1 << 20, root=root)])
        with fs1:
            f = fs1.open_many(metas(store))
            assert f.read() == objects["f0"] + objects["f1"]
            f.close()
        cold_fetched = fs1.stats().totals["blocks_fetched"]
        assert cold_fetched == 12

        # "Restart": a brand-new tier object recovers the journal, a
        # brand-new fs primes its index from it.
        bytes_before = store.link.bytes_moved
        fs2 = PrefetchFS(store, policy=policy,
                         tiers=[DirTier(1 << 20, root=root)])
        with fs2:
            f = fs2.open_many(metas(store))
            assert f.read() == objects["f0"] + objects["f1"]
            f.close()
        snap = fs2.stats()
        assert snap.totals["blocks_fetched"] == 0
        assert snap.totals["cache_hits"] == 12
        assert snap.cache["recovered"] == 12
        # Only metadata (size HEADs) touched the link — zero data bytes.
        assert store.link.bytes_moved == bytes_before

    def test_ckpt_restore_cache_dir_makes_second_restore_warm(self, tmp_path):
        pytest.importorskip("jax")
        import numpy as np

        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        store = make_store({})
        rng = np.random.default_rng(0)
        state = {"w": rng.normal(size=(64, 16)).astype(np.float32),
                 "b": rng.normal(size=(256,)).astype(np.float32)}
        save_checkpoint(store, "ckpt", 3, state)
        cache = str(tmp_path / "wcache")

        r1, _ = restore_checkpoint(store, "ckpt", state, cache_dir=cache,
                                   policy=IOPolicy(engine="rolling",
                                                   blocksize=2048,
                                                   eviction_interval_s=0.01))
        bytes_before = store.link.bytes_moved
        r2, _ = restore_checkpoint(store, "ckpt", state, cache_dir=cache,
                                   policy=IOPolicy(engine="rolling",
                                                   blocksize=2048,
                                                   eviction_interval_s=0.01))
        for k in state:
            assert np.array_equal(np.asarray(r1[k]), state[k])
            assert np.array_equal(np.asarray(r2[k]), state[k])
        # Second restore re-reads the manifest but no leaf blocks.
        leaf_bytes = sum(a.nbytes for a in state.values())
        assert store.link.bytes_moved - bytes_before < leaf_bytes


# --------------------------------------------------------------------------- #
# UploadPool submit/close race (satellite)
# --------------------------------------------------------------------------- #
class TestUploadPoolClose:
    def test_jobs_accepted_before_close_all_run(self):
        pool = UploadPool()
        pool.ensure(2)
        done = []
        lock = threading.Lock()

        def job(i):
            def run():
                time.sleep(0.002)
                with lock:
                    done.append(i)
            return run

        for i in range(20):
            pool.submit(job(i))
        pool.close()   # sentinels must land BEHIND every accepted job
        assert sorted(done) == list(range(20))

    def test_submit_after_close_raises(self):
        pool = UploadPool()
        pool.ensure(1)
        pool.close()
        with pytest.raises(ValueError, match="closed UploadPool"):
            pool.submit(lambda: None)


# --------------------------------------------------------------------------- #
# Writer.abort multipart part leak (satellite)
# --------------------------------------------------------------------------- #
class TestWriterAbort:
    def test_abort_leaves_no_orphaned_parts_on_dirstore(self, tmp_path):
        store_root = str(tmp_path / "store")
        fs = PrefetchFS(DirStore(store_root),
                        policy=IOPolicy(blocksize=512, write_depth=2))
        w = fs.open_write("out/obj")
        for i in range(6):
            w.write(payload(512, seed=i))   # several multipart parts
        w.abort()
        fs.close()                          # drains in-flight pool jobs
        leftovers = [
            os.path.join(d, f)
            for d, _, files in os.walk(store_root) for f in files
        ]
        assert leftovers == [], f"orphaned part files: {leftovers}"

    def test_abort_drops_sims3_parts_and_never_publishes(self):
        store = make_store({})
        fs = PrefetchFS(store, policy=IOPolicy(blocksize=512, write_depth=2))
        w = fs.open_write("out/obj")
        for i in range(4):
            w.write(payload(512, seed=i))
        mp = w._mp
        w.abort()
        fs.close()
        assert mp._parts == {}
        assert not store.backing.list_objects("out/obj")

    def test_part_landing_during_abort_sweep_is_cleaned(self, tmp_path,
                                                        monkeypatch):
        """The race the fix closes: abort() sweeps part files while a
        `put_part` is between its abort-check and its rename — the rename
        used to resurrect the part file forever."""
        import repro.store.local as local_mod

        store = DirStore(str(tmp_path / "store"))
        mp = store.start_multipart("k")
        real_replace = os.replace

        def replace_then_abort(src, dst):
            real_replace(src, dst)
            mp.abort()   # abort lands right after the rename

        monkeypatch.setattr(local_mod.os, "replace", replace_then_abort)
        with pytest.raises(StoreError, match="aborted"):
            mp.put_part(0, b"data")
        monkeypatch.setattr(local_mod.os, "replace", real_replace)
        leftovers = [
            f for d, _, files in os.walk(str(tmp_path / "store"))
            for f in files
        ]
        assert leftovers == []


# --------------------------------------------------------------------------- #
# write staging stays transient on persistent tiers
# --------------------------------------------------------------------------- #
class TestStagingOnPersistentTier:
    def test_writer_not_starved_by_retained_cache_blocks(self):
        """A tier filled to capacity with keep_cached blocks must not
        starve the write path: staging backpressure pressure-evicts
        unpinned cache blocks instead of waiting forever on uploads that
        free nothing."""
        store = make_store({})
        data = bytes(256) * 512            # 128 KiB
        store.backing.put("f", data)
        tier = MemTier(128 << 10)          # exactly dataset-sized
        fs = PrefetchFS(store,
                        policy=IOPolicy(blocksize=32 << 10, keep_cached=True,
                                        eviction_interval_s=0.01),
                        tiers=[tier])
        f = fs.open("f")
        assert f.read() == data
        f.close()
        assert tier.used == 128 << 10      # fully retained
        done: list = []

        def produce():
            w = fs.open_write("out")
            for i in range(4):
                w.write(bytes([i]) * (32 << 10))
            w.close()
            done.append(True)

        t = threading.Thread(target=produce)
        t.start()
        t.join(timeout=20)
        assert done, "writer starved by retained cache blocks"
        fs.close()
        assert store.backing.get("out") == b"".join(
            bytes([i]) * (32 << 10) for i in range(4)
        )

    def test_staged_parts_never_journal(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        store = make_store({})
        fs = PrefetchFS(store, policy=IOPolicy(blocksize=512, write_depth=2),
                        tiers=[tier])
        with fs:
            w = fs.open_write("out/obj")
            for i in range(4):
                w.write(payload(512, seed=i))
            w.close()
        assert store.backing.get("out/obj") == b"".join(
            payload(512, seed=i) for i in range(4)
        )
        # Nothing about the staging survived into the journal/index.
        assert DirTier(1 << 20, root=root).recovered_blocks == 0

    def test_journal_is_valid_jsonl(self, tmp_path):
        root = str(tmp_path / "cache")
        tier = DirTier(1 << 20, root=root)
        tier.write("k@0-9", payload(9), meta=BlockMeta(key="k", offset=0))
        with open(os.path.join(root, DirTier.INDEX_NAME)) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        assert recs[-1]["op"] == "put"
        assert recs[-1]["id"] == "k@0-9"
        assert recs[-1]["key"] == "k"
        assert recs[-1]["off"] == 0
        assert recs[-1]["len"] == 9
        assert "crc" in recs[-1]
