"""Tests for the adaptive prefetch scheduler: vectorized `get_ranges`,
coalesced fetches, readahead-horizon bounds, AIMD depth control, and the
closed autotune loop (PR: adaptive prefetch scheduling)."""

from __future__ import annotations

import time

import pytest

from repro.core import cost_model
from repro.core.autotune import AimdDepthController, BlockSizeTuner
from repro.core.rolling import BlockState, RollingPrefetcher
from repro.core.sequential import SequentialFile
from repro.io import IOPolicy, PrefetchFS, RetryPolicy
from repro.store import DirStore, LinkModel, MemStore, MemTier, SimS3Store
from repro.store.base import ObjectMeta, StoreError, adjacent_runs


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def make_store(objects, latency=0.0, bandwidth=float("inf"), **kw):
    store = SimS3Store(
        link=LinkModel(latency_s=latency, bandwidth_Bps=bandwidth, **kw)
    )
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


def metas(store) -> list[ObjectMeta]:
    return store.backing.list_objects()


# --------------------------------------------------------------------------- #
# vectorized store API
# --------------------------------------------------------------------------- #
SPAN_SETS = [
    [(0, 100)],
    [(0, 64), (64, 128), (128, 200)],          # one adjacent run
    [(0, 50), (100, 150), (150, 160), (400, 401)],  # mixed runs
    [(10, 10), (10, 40)],                      # empty span
    [(500, 600), (0, 100)],                    # out of order
]


class TestGetRanges:
    @pytest.fixture(params=["mem", "dir", "sims3"])
    def store(self, request, tmp_path):
        data = payload(1000)
        if request.param == "mem":
            s = MemStore()
        elif request.param == "dir":
            s = DirStore(str(tmp_path / "store"))
        else:
            s = SimS3Store(link=LinkModel())
        s.put("obj", data)
        return s

    @pytest.mark.parametrize("spans", SPAN_SETS)
    def test_parity_with_per_span_get_range(self, store, spans):
        want = [store.get_range("obj", a, b) for a, b in spans]
        assert store.get_ranges("obj", spans) == want

    def test_missing_key_raises(self, store):
        with pytest.raises(StoreError):
            store.get_ranges("nope", [(0, 1)])

    def test_whole_get_parity(self, store):
        assert store.get("obj") == payload(1000)

    def test_whole_get_missing_raises(self, store):
        with pytest.raises(StoreError):
            store.get("nope")

    def test_adjacent_runs_grouping(self):
        runs = adjacent_runs([(0, 4), (4, 8), (9, 12), (12, 13), (0, 2)])
        assert runs == [[(0, 4), (4, 8)], [(9, 12), (12, 13)], [(0, 2)]]

    def test_sims3_coalesces_adjacent_spans_into_one_request(self):
        store = make_store({"obj": payload(4096)})
        r0 = store.link.requests
        store.get_ranges("obj", [(0, 512), (512, 1024), (1024, 1536)])
        assert store.link.requests - r0 == 1
        assert store.link.coalesced_requests == 1
        assert store.link.spans_served >= 3

    def test_sims3_nonadjacent_spans_pay_per_run(self):
        store = make_store({"obj": payload(4096)})
        r0 = store.link.requests
        store.get_ranges("obj", [(0, 512), (1024, 1536), (1536, 2048)])
        assert store.link.requests - r0 == 2  # two adjacent runs

    def test_sims3_whole_get_is_one_request(self):
        """The old default paid a HEAD (size) plus a ranged GET — two
        latencies per object; whole-object gets are now one request."""
        store = make_store({"obj": payload(4096)})
        r0 = store.link.requests
        assert store.get("obj") == payload(4096)
        assert store.link.requests - r0 == 1


# --------------------------------------------------------------------------- #
# coalesced prefetch correctness
# --------------------------------------------------------------------------- #
class TestCoalescedPrefetch:
    def test_coalesced_run_bytes_identical_and_fewer_requests(self):
        objects = {f"f{i}": payload(4096, seed=i) for i in range(3)}
        store = make_store(objects)
        with RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=512, coalesce=8, eviction_interval_s=0.01),
        ) as pf:
            got = pf.read_range(0, pf.plan.total_bytes)
        assert got == b"".join(objects[m.key] for m in metas(store))
        s = pf.stats.snapshot()
        assert s["store_requests"] < s["blocks_fetched"]
        assert s["coalesced_requests"] >= 1
        assert s["coalesced_blocks"] > s["coalesced_requests"]

    def test_runs_never_span_files(self):
        """A coalesced request covers one key only: per-file byte content
        must survive coalescing with many small files."""
        objects = {f"f{i}": payload(700 + i * 13, seed=i) for i in range(6)}
        store = make_store(objects)
        with RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=256, coalesce=16, eviction_interval_s=0.01),
        ) as pf:
            got = pf.read_range(0, pf.plan.total_bytes)
        assert got == b"".join(objects[m.key] for m in metas(store))

    def test_coalesced_fetch_retries_transient_failures(self):
        objects = {"a": payload(8192)}
        store = make_store(objects)
        store.link.fail_next(3)
        with RollingPrefetcher(
            store, metas(store), [MemTier(32 << 10)],
            policy=IOPolicy(blocksize=512, coalesce=4, eviction_interval_s=0.01,
                            retry=RetryPolicy(max_retries=6, backoff_s=0.001)),
        ) as pf:
            assert pf.read_range(0, 8192) == payload(8192)
        assert pf.stats.retries >= 3

    def test_coalesced_fetch_under_hedging(self):
        objects = {"a": payload(16384)}
        store = make_store(objects, latency=0.05)
        with RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=2048, coalesce=4, hedge_timeout_s=0.01,
                            eviction_interval_s=0.01),
        ) as pf:
            assert pf.read_range(0, 16384) == payload(16384)
        assert pf.stats.hedges >= 1

    def test_permanent_failure_fails_whole_run(self):
        objects = {"a": payload(4096)}
        store = make_store(objects)
        store.link.fail_next(100)
        with RollingPrefetcher(
            store, metas(store), [MemTier(32 << 10)],
            policy=IOPolicy(blocksize=512, coalesce=4, eviction_interval_s=0.01,
                            retry=RetryPolicy(max_retries=1, backoff_s=0.001)),
        ) as pf:
            with pytest.raises(StoreError):
                pf.read_range(0, 4096)

    def test_run_shrinks_when_tier_cannot_hold_it(self):
        """coalesce=8 with a tier that fits only 2 blocks: the scheduler
        must degrade to narrower runs, not deadlock."""
        objects = {"a": payload(8192)}
        store = make_store(objects)
        with RollingPrefetcher(
            store, metas(store), [MemTier(1024)],
            policy=IOPolicy(blocksize=512, coalesce=8, eviction_interval_s=0.005),
        ) as pf:
            assert pf.read_range(0, 8192) == payload(8192)


# --------------------------------------------------------------------------- #
# readahead horizon
# --------------------------------------------------------------------------- #
class TestReadaheadHorizon:
    def test_slow_reader_bounds_fetch_window(self):
        objects = {"a": payload(16384)}
        store = make_store(objects)
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=512, readahead_blocks=4, eviction_interval_s=10.0),
        )
        with pf:
            time.sleep(0.25)   # reader never reads: horizon stays [0, 4)
            in_flight = sum(
                i.state in (BlockState.FETCHING, BlockState.CACHED)
                for i in pf._info
            )
            assert in_flight <= 4
            # Reader progress slides the horizon and the stream finishes.
            assert pf.read_range(0, 16384) == payload(16384)
            assert pf.stats.blocks_fetched >= 32 - pf.stats.direct_reads

    def test_horizon_bounds_coalesced_runs(self):
        objects = {"a": payload(16384)}
        store = make_store(objects)
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=512, coalesce=16, readahead_blocks=6,
                            eviction_interval_s=10.0),
        )
        with pf:
            time.sleep(0.25)
            in_flight = sum(
                i.state in (BlockState.FETCHING, BlockState.CACHED)
                for i in pf._info
            )
            assert in_flight <= 6
            assert pf.read_range(0, 16384) == payload(16384)


# --------------------------------------------------------------------------- #
# AIMD depth control
# --------------------------------------------------------------------------- #
class TestAimdDepth:
    def test_additive_increase_while_throughput_holds(self):
        ctl = AimdDepthController(1, 8, window=2)
        now = [0.0]
        for _ in range(40):
            now[0] += 0.01
            ctl.on_fetch(1 << 20, now[0])   # steady throughput
        assert ctl.target == 8              # ramped to the ceiling
        assert ctl.peak == 8

    def test_multiplicative_decrease_on_regression(self):
        ctl = AimdDepthController(1, 8, window=2)
        now = 0.0
        for _ in range(40):
            now += 0.01
            ctl.on_fetch(1 << 20, now)
        assert ctl.target == 8
        # Throughput collapses 10x: the next windows must halve the target.
        for _ in range(4):
            now += 0.1
            ctl.on_fetch(1 << 20, now)
        assert ctl.target <= 4
        assert 1 <= ctl.target <= ctl.max_depth

    def test_never_leaves_bounds(self):
        ctl = AimdDepthController(3, 4, window=1)
        now = 0.0
        for i in range(100):
            now += 0.001 if i % 7 else 1.0   # wildly noisy throughput
            ctl.on_fetch(1024, now)
            assert 1 <= ctl.target <= 4

    def test_engine_grows_streams_on_latency_bound_link(self):
        objects = {f"f{i}": payload(2048, seed=i) for i in range(8)}
        store = make_store(objects, latency=0.005)
        with RollingPrefetcher(
            store, metas(store), [MemTier(64 << 10)],
            policy=IOPolicy(blocksize=512, depth=1, max_depth=6,
                            eviction_interval_s=0.01),
        ) as pf:
            got = pf.read_range(0, pf.plan.total_bytes)
        assert got == b"".join(objects[m.key] for m in metas(store))
        assert pf.stats.depth_peak > 1
        assert pf.stats.depth_peak <= 6


# --------------------------------------------------------------------------- #
# event-driven eviction (the 5-second-cliff fix)
# --------------------------------------------------------------------------- #
class TestEvictionNotify:
    def test_full_tier_does_not_wait_out_the_eviction_interval(self):
        """Tier fits 2 of 16 blocks and the eviction interval is 30 s: the
        consume/demand notifications must keep the pipeline rolling — the
        old timed poll would stall for up to eviction_interval_s per
        eviction round."""
        objects = {"a": payload(8192)}
        store = make_store(objects)
        tier = MemTier(1024)   # 2 blocks of 512
        t0 = time.perf_counter()
        with RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=512, eviction_interval_s=30.0),
        ) as pf:
            assert pf.read_range(0, 8192) == payload(8192)
        elapsed = time.perf_counter() - t0
        assert elapsed < 5.0, f"eviction stalled the pipeline: {elapsed:.1f}s"
        assert pf.stats.blocks_evicted >= 1


# --------------------------------------------------------------------------- #
# copy reduction
# --------------------------------------------------------------------------- #
class TestZeroCopyReads:
    def test_read_range_view_returns_memoryview_within_block(self):
        objects = {"a": payload(4096)}
        store = make_store(objects)
        with RollingPrefetcher(
            store, metas(store), [MemTier(16 << 10)],
            policy=IOPolicy(blocksize=1024, eviction_interval_s=0.05),
        ) as pf:
            first = pf.read_range(0, 512, view=True)
            assert isinstance(first, memoryview)
            assert bytes(first) == payload(4096)[:512]
            # Multi-block requests still return bytes.
            rest = pf.read_range(512, 4096, view=True)
            assert isinstance(rest, bytes)
            assert rest == payload(4096)[512:]

    def test_readview_file_api(self):
        objects = {"a": payload(2048)}
        store = make_store(objects)
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=1024, eviction_interval_s=0.05))
        with fs:
            f = fs.open_many(metas(store))
            got = bytearray()
            while True:
                chunk = f.readview(256)
                if not chunk:
                    break
                got += chunk
            assert bytes(got) == payload(2048)

    def test_put_part_keeps_immutable_bytes_without_copy(self):
        store = MemStore()
        mp = store.start_multipart("k")
        part = payload(512)
        mp.put_part(0, part)
        assert mp._parts[0] is part        # no defensive re-copy
        mp.put_part(1, bytearray(payload(16, seed=1)))  # mutable: copied
        mp.complete()
        assert store.get("k") == part + payload(16, seed=1)


# --------------------------------------------------------------------------- #
# sequential engine read-ahead
# --------------------------------------------------------------------------- #
class TestSequentialReadahead:
    def test_multiblock_cache_fills_with_one_request(self):
        objects = {"a": payload(8192)}
        store = make_store(objects)
        f = SequentialFile(
            store, metas(store),
            policy=IOPolicy(blocksize=512, cache_blocks=4),
        )
        assert f.read() == payload(8192)
        assert f.stats.blocks_fetched == 16
        assert f.stats.store_requests == 4      # 4-block runs, one GET each
        assert store.link.coalesced_requests >= 1

    def test_single_block_cache_keeps_baseline_request_shape(self):
        objects = {"a": payload(4096)}
        store = make_store(objects)
        f = SequentialFile(store, metas(store), policy=IOPolicy(blocksize=512))
        assert f.read() == payload(4096)
        assert f.stats.store_requests == f.stats.blocks_fetched == 8


# --------------------------------------------------------------------------- #
# the closed autotune loop
# --------------------------------------------------------------------------- #
class TestClosedLoopAutotune:
    def test_request_fit_separates_latency_and_bandwidth(self):
        tuner = BlockSizeTuner(min_blocksize=1024)
        lat, bw = 0.02, 100e6
        for w in [1, 2, 4, 8, 1, 3, 6, 2, 5, 7]:
            nbytes = w * 65536
            tuner.observe_request(nbytes, lat + nbytes / bw)
        assert tuner.latency_s == pytest.approx(lat, rel=0.05)
        assert tuner.bandwidth_Bps == pytest.approx(bw, rel=0.05)

    def test_uniform_sizes_stay_underdetermined(self):
        tuner = BlockSizeTuner()
        for _ in range(20):
            tuner.observe_request(65536, 0.02)
        assert tuner.latency_s is None       # no variance, no fit
        assert tuner.suggest_coalesce(65536, 16) == 1

    def test_suggest_coalesce_matches_cost_model(self):
        tuner = BlockSizeTuner()
        tuner.observe_latency(0.02)
        tuner.observe_bandwidth(200e6)
        want = cost_model.coalesce_width(0.02, 200e6, 32 << 10, 16)
        assert tuner.suggest_coalesce(32 << 10, 16) == want
        assert want > 1                      # latency-bound: coalescing on
        assert cost_model.coalesce_width(0.001, 45e6, 256 << 10, 16) == 1

    def test_fsstats_surfaces_tuner_estimates(self):
        objects = {f"f{i}": payload(4096, seed=i) for i in range(4)}
        store = make_store(objects, latency=0.003)
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=512, autotune=True,
            eviction_interval_s=0.02))
        with fs:
            f = fs.open_many(metas(store))
            f.read()
            f.close()
            snap = fs.stats().snapshot()
        assert snap["tuner"] is not None
        assert snap["tuner"]["requests_observed"] > 0
        assert snap["tuner"]["latency_s"] is not None
        assert snap["totals"]["store_requests"] < snap["totals"]["blocks_fetched"]

    def test_autotuned_blocksize_converges_to_eq4_optimum(self):
        """Acceptance: with autotune=True the blocksize chosen for the
        second open lands within 20% of Eq. 4's optimum for the simulated
        link's known l_c / b_cr and the reader's compute rate."""
        l_c, b_cr = 0.03, 200e6
        c = 2e-7                       # compute seconds per byte (sleept)
        objects = {f"f{i}": payload((768 << 10) + 1000 * i, seed=i)
                   for i in range(4)}
        store = make_store(objects, latency=l_c, bandwidth=b_cr)
        total = sum(len(v) for v in objects.values())
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=32 << 10, autotune=True,
            eviction_interval_s=0.02))
        with fs:
            f = fs.open_many(metas(store))
            chunk = 128 << 10
            while True:
                data = f.read(chunk)
                if not data:
                    break
                time.sleep(c * len(data))   # the application's compute
            f.close()
            g = fs.open_many(metas(store))   # retuned from observations
            chosen = g._pf.plan.blocksize
            g.close()
        want = cost_model.optimal_blocksize(total, c, l_c)
        assert 0.8 * want <= chosen <= 1.2 * want, (
            f"chosen {chosen} vs Eq.4 optimum {want:.0f} "
            f"(tuner: {fs.tuner.estimates()})"
        )

    def test_loader_exposes_fs_tuner(self):
        from repro.data.loader import LoaderConfig, PrefetchingDataLoader
        from repro.data.tokens import synth_token_shard
        import numpy as np

        rng = np.random.default_rng(0)
        store = make_store(
            {f"s{i}": synth_token_shard(rng, 3000) for i in range(2)}
        )
        cfg = LoaderConfig(seq_len=64, batch_size=2,
                           policy=IOPolicy(engine="rolling", blocksize=8192,
                                           autotune=True,
                                           eviction_interval_s=0.02))
        loader = PrefetchingDataLoader(store, metas(store), [MemTier(1 << 20)],
                                       cfg)
        for _ in loader.batches(max_batches=2):
            pass
        assert loader.tuner is not None
        assert loader.tuner.n_requests_observed > 0
        loader.close()

    def test_retune_respects_explicit_coalesce_cap(self):
        """An explicit IOPolicy.coalesce — including 1, i.e. coalescing
        off — bounds the payload one request may carry; autotune only
        opens the ceiling when coalesce was left unset (None)."""
        objects = {"f0": payload(64 << 10)}
        for coalesce, want in [(2, lambda w: w == 2),
                               (1, lambda w: w == 1),
                               (None, lambda w: w > 1)]:
            store = make_store(objects, latency=0.005)
            fs = PrefetchFS(store, policy=IOPolicy(
                engine="rolling", blocksize=4096, autotune=True,
                coalesce=coalesce, eviction_interval_s=0.02))
            with fs:
                f = fs.open("f0")
                assert want(f._pf.coalesce), (coalesce, f._pf.coalesce)
                f.read()
                f.close()

    def test_depth_peak_folds_as_max_not_sum(self):
        """depth_peak is a high-water mark: folding reopened readers (and
        cross-engine totals) must keep the peak, not sum peaks."""
        class FakeStats:
            def __init__(self, snap):
                self._snap = snap

            def snapshot(self):
                return dict(self._snap)

        class FakeReader:
            def __init__(self, snap):
                self.stats = FakeStats(snap)

        bucket: dict = {}
        PrefetchFS._fold_snapshot(
            bucket, FakeReader({"depth_peak": 8, "blocks_fetched": 10}))
        PrefetchFS._fold_snapshot(
            bucket, FakeReader({"depth_peak": 5, "blocks_fetched": 7}))
        assert bucket["depth_peak"] == 8     # max, not 13
        assert bucket["blocks_fetched"] == 17  # counters still sum

    def test_sequential_engine_feeds_tuner(self):
        """autotune=True is not a rolling-only loop: the sequential
        engine's synchronous fetches are observed too."""
        objects = {f"f{i}": payload(20000 + 1000 * i, seed=i)
                   for i in range(3)}
        store = make_store(objects, latency=0.002)
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="sequential", blocksize=4096, autotune=True))
        with fs:
            f = fs.open_many(metas(store))
            f.read()
            f.close()
            snap = fs.stats().snapshot()
        assert snap["tuner"] is not None
        assert snap["tuner"]["requests_observed"] > 0


# --------------------------------------------------------------------------- #
# coalesced-fetch failure cleanup + lazy stream spawning (review fixes)
# --------------------------------------------------------------------------- #
class TestCoalescedWriteFailureCleanup:
    def test_mid_run_tier_write_failure_leaves_no_orphans(self):
        """A tier.write failure mid-way through a coalesced run must not
        strand the blocks already written: FAILED blocks are invisible to
        eviction, so orphans would stay resident past the cancelled
        reservation forever."""
        class FlakyWriteTier(MemTier):
            def __init__(self, capacity: int, fail_at: int) -> None:
                super().__init__(capacity)
                self.writes = 0
                self.fail_at = fail_at

            def _write(self, block_id: str, data: bytes) -> None:
                self.writes += 1
                if self.writes == self.fail_at:
                    raise StoreError("tier write blew up")
                super()._write(block_id, data)

        objects = {"a": payload(4096)}
        store = make_store(objects)
        tier = FlakyWriteTier(32 << 10, fail_at=3)
        with RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=512, coalesce=8, eviction_interval_s=0.01),
        ) as pf:
            with pytest.raises(StoreError):
                pf.read_range(0, 4096)
            assert tier._resident_bytes() == 0   # writes 1-2 cleaned up
            tier.verify_used()
            assert tier.available() == tier.capacity


class TestLazyStreamSpawn:
    def test_streams_spawn_lazily_up_to_aimd_target(self):
        objects = {"a": payload(32 << 10)}
        store = make_store(objects, latency=0.05)
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=2048, depth=2, max_depth=32,
                            eviction_interval_s=0.01),
        )
        pf.start()
        assert pf._spawned == 2              # not the 32-thread ceiling
        assert pf.read_range(0, 32 << 10) == payload(32 << 10)
        assert pf._spawned <= max(2, pf.stats.depth_peak)
        assert pf._spawned < 32
        pf.close()

    def test_non_store_error_write_failure_fails_run_not_deadlocks(self):
        """ENOSPC-style failures (not StoreError) must also cancel the
        reservation and FAIL the run — otherwise the blocks stay FETCHING
        and the reader waits forever."""
        class Enospc(MemTier):
            def _write(self, block_id: str, data: bytes) -> None:
                raise OSError(28, "No space left on device")

        objects = {"a": payload(2048)}
        store = make_store(objects)
        tier = Enospc(32 << 10)
        with RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=512, coalesce=4, eviction_interval_s=0.01),
        ) as pf:
            with pytest.raises(StoreError):
                pf.read_range(0, 2048)
            tier.verify_used()
            assert tier.available() == tier.capacity
