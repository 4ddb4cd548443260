"""Unit + property tests for the Rolling Prefetch core (paper §II-A)."""

from __future__ import annotations

import threading
import time

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import BlockPlan, BlockState, RollingPrefetcher, SequentialFile
from repro.io import IOPolicy, PrefetchFS, RetryPolicy
from repro.store import LinkModel, MemTier, SimS3Store
from repro.store.base import ObjectMeta, StoreError


def make_store(objects: dict[str, bytes], latency=0.0, bandwidth=float("inf"), **kw):
    store = SimS3Store(link=LinkModel(latency_s=latency, bandwidth_Bps=bandwidth, **kw))
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


def payload(n: int, seed: int = 0) -> bytes:
    # Deterministic, position-dependent bytes so offset bugs surface.
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def metas(store) -> list[ObjectMeta]:
    return store.backing.list_objects()


def open_rolling(store, tiers, **policy):
    """A rolling reader over every object in `store`, as one stream."""
    fs = PrefetchFS(store, policy=IOPolicy(engine="rolling", **policy), tiers=tiers)
    return fs.open_many(metas(store))


# --------------------------------------------------------------------------- #
# BlockPlan
# --------------------------------------------------------------------------- #
class TestBlockPlan:
    def test_blocks_cover_stream_exactly(self):
        files = [ObjectMeta("a", 100), ObjectMeta("b", 64), ObjectMeta("c", 1)]
        plan = BlockPlan(files, blocksize=64)
        assert plan.total_bytes == 165
        # Coverage: concatenation of all block ranges == the whole stream.
        pos = 0
        for b in plan.blocks:
            assert b.global_start == pos
            pos = b.global_end
        assert pos == plan.total_bytes
        # Blocks never span files.
        for b in plan.blocks:
            assert b.end <= files[b.file_index].size

    def test_block_at(self):
        files = [ObjectMeta("a", 100), ObjectMeta("b", 50)]
        plan = BlockPlan(files, blocksize=30)
        for off in [0, 29, 30, 99, 100, 149]:
            b = plan.block_at(off)
            assert b.global_start <= off < b.global_end
        with pytest.raises(IndexError):
            plan.block_at(150)

    @given(
        sizes=st.lists(st.integers(1, 500), min_size=1, max_size=8),
        blocksize=st.integers(1, 200),
    )
    @settings(max_examples=50, deadline=None)
    def test_plan_properties(self, sizes, blocksize):
        files = [ObjectMeta(f"f{i}", s) for i, s in enumerate(sizes)]
        plan = BlockPlan(files, blocksize)
        assert plan.total_bytes == sum(sizes)
        assert all(1 <= b.size <= blocksize for b in plan.blocks)
        ids = [b.block_id for b in plan.blocks]
        assert len(set(ids)) == len(ids)  # block ids unique


# --------------------------------------------------------------------------- #
# Rolling Prefetch engine
# --------------------------------------------------------------------------- #
class TestRollingPrefetch:
    def test_reads_are_byte_identical(self):
        objects = {f"f{i}": payload(1000 + i * 37, seed=i) for i in range(4)}
        store = make_store(objects)
        tiers = [MemTier(capacity=4096)]
        with open_rolling(
            store, tiers, blocksize=256, eviction_interval_s=0.01
        ) as f:
            got = f.read()
        want = b"".join(objects[m.key] for m in metas(store))
        assert got == want

    def test_chunked_reads_match_full_read(self):
        objects = {"a": payload(5000), "b": payload(3000, seed=1)}
        store = make_store(objects)
        with open_rolling(
            store, [MemTier(8192)], blocksize=512,
            eviction_interval_s=0.01,
        ) as f:
            chunks = []
            while True:
                chunk = f.read(777)
                if not chunk:
                    break
                chunks.append(chunk)
        assert b"".join(chunks) == payload(5000) + payload(3000, seed=1)

    def test_cache_budget_never_exceeded(self):
        """The paper's core guarantee: bounded local footprint even when the
        dataset is much larger than the cache."""
        objects = {f"f{i}": payload(2048, seed=i) for i in range(8)}  # 16 KiB
        store = make_store(objects)
        tier = MemTier(capacity=1024)  # 4 blocks of 256
        peak = [0]
        stop = threading.Event()

        def monitor():
            while not stop.is_set():
                peak[0] = max(peak[0], tier.used)
                time.sleep(0.0005)

        t = threading.Thread(target=monitor, daemon=True)
        t.start()
        with open_rolling(
            store, [tier], blocksize=256, eviction_interval_s=0.001
        ) as f:
            data = f.read()
        stop.set()
        t.join()
        assert len(data) == 8 * 2048
        assert peak[0] <= 1024
        assert tier.used == 0  # final sweep cleaned everything

    def test_dataset_larger_than_cache_streams_through(self):
        objects = {f"f{i}": payload(4096, seed=i) for i in range(4)}
        store = make_store(objects)
        tier = MemTier(capacity=512)  # far smaller than 16 KiB dataset
        with open_rolling(
            store, [tier], blocksize=256, eviction_interval_s=0.001
        ) as f:
            want = b"".join(objects[m.key] for m in metas(store))
            assert f.read() == want

    def test_multi_tier_spill(self):
        """When tier 0 fills, blocks go to tier 1 (priority order)."""
        objects = {"a": payload(4096)}
        store = make_store(objects)
        t0, t1 = MemTier(capacity=256, name="t0"), MemTier(capacity=4096, name="t1")
        pf = RollingPrefetcher(
            store, metas(store), [t0, t1],
            policy=IOPolicy(blocksize=256, eviction_interval_s=10.0),
        )
        with pf:
            # Wait until prefetching stalls or finishes.
            deadline = time.time() + 5.0
            while time.time() < deadline:
                states = [i.state for i in pf._info]
                if sum(s == BlockState.CACHED for s in states) >= 8:
                    break
                time.sleep(0.005)
            cached_tiers = {i.tier.name for i in pf._info if i.tier is not None}
            assert "t1" in cached_tiers  # spilled beyond tier 0
            data = pf.read_range(0, 4096)
            assert data == payload(4096)

    def test_eviction_marks_and_frees(self):
        objects = {"a": payload(1024)}
        store = make_store(objects)
        tier = MemTier(capacity=2048)
        pf = RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=256, eviction_interval_s=0.001),
        )
        with pf:
            pf.read_range(0, 1024)
            deadline = time.time() + 5.0
            while time.time() < deadline and pf.stats.blocks_evicted < 4:
                time.sleep(0.005)
            assert pf.stats.blocks_evicted == 4

    def test_seek_forward_and_tell(self):
        objects = {"a": payload(1000), "b": payload(1000, seed=2)}
        store = make_store(objects)
        with open_rolling(
            store, [MemTier(4096)], blocksize=128,
            eviction_interval_s=0.01,
        ) as f:
            f.seek(500)
            assert f.tell() == 500
            got = f.read(700)
            want = (payload(1000) + payload(1000, seed=2))[500:1200]
            assert got == want

    def test_backward_seek_after_eviction_falls_back_to_direct_read(self):
        objects = {"a": payload(1024)}
        store = make_store(objects)
        with open_rolling(
            store, [MemTier(4096)], blocksize=128,
            eviction_interval_s=0.001,
        ) as f:
            first = f.read(512)
            time.sleep(0.1)  # let eviction claim consumed blocks
            f.seek(0)
            again = f.read(512)
            assert first == again
        assert f.stats.direct_reads >= 1

    def test_transient_failures_are_retried(self):
        objects = {"a": payload(2048)}
        store = make_store(objects)
        store.link.fail_next(2)
        with open_rolling(
            store, [MemTier(4096)], blocksize=512, eviction_interval_s=0.01,
            retry=RetryPolicy(max_retries=5, backoff_s=0.001),
        ) as f:
            assert f.read() == payload(2048)
        assert f.stats.retries >= 2

    def test_permanent_failure_raises(self):
        objects = {"a": payload(2048)}
        store = make_store(objects)
        store.link.fail_next(100)
        with open_rolling(
            store, [MemTier(4096)], blocksize=512, eviction_interval_s=0.01,
            retry=RetryPolicy(max_retries=1, backoff_s=0.001),
        ) as f:
            with pytest.raises(StoreError):
                f.read()

    def test_depth_gt_one_still_correct(self):
        objects = {f"f{i}": payload(3000, seed=i) for i in range(3)}
        store = make_store(objects, latency=0.002)
        with open_rolling(
            store, [MemTier(16384)], blocksize=512,
            depth=4, eviction_interval_s=0.01,
        ) as f:
            want = b"".join(objects[m.key] for m in metas(store))
            assert f.read() == want

    def test_hedged_fetch_fires_on_straggler(self):
        objects = {"a": payload(4096)}
        store = make_store(objects, latency=0.05)
        with open_rolling(
            store, [MemTier(8192)], blocksize=1024,
            hedge_timeout_s=0.01, eviction_interval_s=0.01,
        ) as f:
            assert f.read() == payload(4096)
        assert f.stats.hedges >= 1

    @given(
        nfiles=st.integers(1, 4),
        size=st.integers(1, 2000),
        blocksize=st.integers(1, 512),
        readsize=st.integers(1, 999),
    )
    @settings(max_examples=15, deadline=None)
    def test_property_stream_integrity(self, nfiles, size, blocksize, readsize):
        """Any (files, blocksize, read-chunking) combination returns exactly
        the concatenated object bytes."""
        objects = {f"f{i}": payload(size, seed=i) for i in range(nfiles)}
        store = make_store(objects)
        with open_rolling(
            store, [MemTier(max(blocksize * 4, 2048))],
            blocksize=blocksize, eviction_interval_s=0.001,
        ) as f:
            got = bytearray()
            while True:
                chunk = f.read(readsize)
                if not chunk:
                    break
                got.extend(chunk)
        assert bytes(got) == b"".join(objects[m.key] for m in metas(store))

    def test_reserve_failure_fails_group_without_leaking_flights(self):
        # _reserve runs eviction I/O; if it raises with the group's
        # flights registered, those flights must be aborted — a leaked
        # flight parks every waiter (this reader included) until the
        # reclaim TTL.
        objects = {"a": payload(1024)}
        store = make_store(objects)
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(4096)],
            policy=IOPolicy(blocksize=256, eviction_interval_s=10.0),
        )

        def broken_reserve(nbytes):
            raise RuntimeError("eviction I/O exploded")

        pf._reserve = broken_reserve
        with pf:
            with pytest.raises(StoreError):
                pf.read_range(0, 256)
            # Every flight the failed group registered was aborted.
            assert not pf.index._flights
            failed = [i for i in pf._info if i.state == BlockState.FAILED]
            assert failed and all(i.error is not None for i in failed)


# --------------------------------------------------------------------------- #
# Sequential baseline equivalence
# --------------------------------------------------------------------------- #
class TestSequentialFile:
    def test_matches_rolling_output(self):
        objects = {f"f{i}": payload(1500, seed=i) for i in range(3)}
        store = make_store(objects)
        seq = SequentialFile(store, metas(store), policy=IOPolicy(blocksize=400))
        data_seq = seq.read()
        with open_rolling(
            store, [MemTier(8192)], blocksize=400,
            eviction_interval_s=0.01,
        ) as f:
            data_pf = f.read()
        assert data_seq == data_pf

    def test_no_overlap_costs_are_serial(self):
        """With latency only on the store link, the sequential file pays one
        latency per block fetched."""
        objects = {"a": payload(4096)}
        store = make_store(objects, latency=0.01)
        seq = SequentialFile(store, metas(store), policy=IOPolicy(blocksize=1024))
        t0 = time.perf_counter()
        seq.read()
        elapsed = time.perf_counter() - t0
        assert seq.stats.blocks_fetched == 4
        assert elapsed >= 4 * 0.01


# --------------------------------------------------------------------------- #
# Overlap actually happens (the paper's central claim, miniaturized)
# --------------------------------------------------------------------------- #
class TestOverlap:
    def test_prefetch_overlaps_compute(self):
        """With per-block cloud time ~= per-block compute time, rolling
        prefetch should approach 2x over sequential (Eq. 3)."""
        nbytes, nblocks = 64 * 1024, 16
        blocksize = nbytes // nblocks
        per_block_cloud = 0.02
        objects = {"a": payload(nbytes)}

        def run_sequential():
            store = make_store(objects, latency=per_block_cloud)
            f = SequentialFile(
                store, metas(store),
                policy=IOPolicy(blocksize=blocksize),
            )
            t0 = time.perf_counter()
            while True:
                chunk = f.read(blocksize)
                if not chunk:
                    break
                time.sleep(per_block_cloud)  # "compute"
            return time.perf_counter() - t0

        def run_rolling():
            store = make_store(objects, latency=per_block_cloud)
            with open_rolling(
                store, [MemTier(nbytes)], blocksize=blocksize,
                eviction_interval_s=0.005,
            ) as f:
                t0 = time.perf_counter()
                while True:
                    chunk = f.read(blocksize)
                    if not chunk:
                        break
                    time.sleep(per_block_cloud)  # "compute"
                return time.perf_counter() - t0

        t_seq = run_sequential()
        t_pf = run_rolling()
        speedup = t_seq / t_pf
        # Theory bound is <2; require clear overlap, not an exact value.
        assert speedup > 1.3, f"expected overlap speedup, got {speedup:.2f}"
        assert speedup < 2.2
