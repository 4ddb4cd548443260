"""Resilience-layer tests: RetryPolicy/Retrier/Hedger units, the
LinkModel throttle/failure-cost model, the FaultyStore chaos harness,
and end-to-end chaos runs across read (both engines), write-behind, and
checkpoint save/restore."""

from __future__ import annotations

import threading
import time

import pytest

from repro.core.autotune import AimdDepthController
from repro.core.rolling import RollingPrefetcher, RollingPrefetchFile
from repro.core.sequential import SequentialFile
from repro.io import IOPolicy, PrefetchFS, open_store
from repro.io.retry import Hedger, Retrier, RetryPolicy
from repro.store import (
    FaultSchedule,
    FaultyStore,
    LinkModel,
    MemStore,
    MemTier,
    SimS3Store,
)
from repro.store.base import (
    ObjectMeta,
    StoreError,
    ThrottleError,
    TransientStoreError,
)


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def make_store(objects: dict[str, bytes], latency=0.0,
               bandwidth=float("inf"), **kw) -> SimS3Store:
    store = SimS3Store(link=LinkModel(latency_s=latency,
                                      bandwidth_Bps=bandwidth, **kw))
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


def metas(store) -> list[ObjectMeta]:
    backing = getattr(store, "backing", None)
    if backing is None:                      # FaultyStore wrapper
        backing = store.inner.backing
    return backing.list_objects()


# --------------------------------------------------------------------------- #
# RetryPolicy / Retrier
# --------------------------------------------------------------------------- #
class TestRetryPolicy:
    def test_full_jitter_bounds(self):
        import random

        pol = RetryPolicy(backoff_s=0.1, backoff_cap_s=10.0)
        rng = random.Random(42)
        for attempt in range(6):
            for _ in range(50):
                d = pol.backoff(attempt, rng)
                assert 0.0 <= d <= 0.1 * (2 ** attempt)

    def test_no_jitter_is_exact_exponential(self):
        import random

        pol = RetryPolicy(backoff_s=0.1, backoff_cap_s=10.0, jitter="none")
        rng = random.Random(0)
        assert [pol.backoff(a, rng) for a in range(4)] == [
            0.1, 0.2, 0.4, 0.8]

    def test_backoff_cap(self):
        import random

        pol = RetryPolicy(backoff_s=1.0, backoff_cap_s=2.0, jitter="none")
        assert pol.backoff(10, random.Random(0)) == 2.0

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_retries=-1)
        with pytest.raises(ValueError):
            RetryPolicy(jitter="bogus")
        with pytest.raises(ValueError):
            RetryPolicy(deadline_s=0)

    def test_retries_then_succeeds(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise TransientStoreError("flaky")
            return "ok"

        r = Retrier(RetryPolicy(max_retries=5, backoff_s=0.0))
        assert r.call(fn) == "ok"
        assert len(calls) == 3
        assert r.retries == 2

    def test_exhaustion_raises_storeerror_chained(self):
        def fn():
            raise TransientStoreError("always")

        r = Retrier(RetryPolicy(max_retries=2, backoff_s=0.0))
        with pytest.raises(StoreError, match="exhausted 3 attempts") as ei:
            r.call(fn, label="op")
        assert isinstance(ei.value.__cause__, TransientStoreError)

    def test_permanent_errors_not_retried(self):
        calls = []

        def fn():
            calls.append(1)
            raise StoreError("permanent")

        r = Retrier(RetryPolicy(max_retries=5, backoff_s=0.0))
        with pytest.raises(StoreError, match="permanent"):
            r.call(fn)
        assert len(calls) == 1

    def test_budget_spans_calls(self):
        r = Retrier(RetryPolicy(max_retries=10, backoff_s=0.0, budget=3))

        def fail():
            raise TransientStoreError("x")

        with pytest.raises(StoreError, match="budget"):
            r.call(fail)           # spends the whole budget
        assert r.budget_left == 0
        calls = []

        def fail_once():
            calls.append(1)
            raise TransientStoreError("x")

        # No budget left: a later call gets zero retries.
        with pytest.raises(StoreError, match="budget"):
            r.call(fail_once)
        assert len(calls) == 1

    def test_deadline_stops_early(self):
        fake_now = [0.0]
        sleeps = []
        r = Retrier(
            RetryPolicy(max_retries=100, backoff_s=1.0, backoff_cap_s=1.0,
                        jitter="none", deadline_s=2.5),
            sleep=lambda s: (sleeps.append(s),
                             fake_now.__setitem__(0, fake_now[0] + s)),
            clock=lambda: fake_now[0],
        )

        def fail():
            raise TransientStoreError("x")

        with pytest.raises(StoreError, match="deadline"):
            r.call(fail)
        # Backoffs of 1s each: two fit inside the 2.5s deadline.
        assert len(sleeps) == 2

    def test_on_throttle_fires_even_when_retry_succeeds(self):
        seen = []
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                raise ThrottleError("503")
            return "ok"

        r = Retrier(RetryPolicy(max_retries=3, backoff_s=0.0),
                    on_throttle=lambda: seen.append(1))
        assert r.call(fn) == "ok"
        assert seen == [1]
        assert r.throttles == 1

    def test_desynchronized_backoff_regression(self):
        """Satellite: N concurrent streams tripped by the same transient
        fault must not re-collide within one backoff window. The old
        unjittered ``2 ** attempt`` loops put every stream's retry at
        exactly the same instant; full jitter spreads them."""
        n = 8

        def collect(policy: RetryPolicy, seed_base: int) -> list[float]:
            times = []
            for i in range(n):
                sleeps = []
                r = Retrier(policy, seed=seed_base + i,
                            sleep=sleeps.append)
                calls = []

                def fn():
                    calls.append(1)
                    if len(calls) == 1:
                        raise TransientStoreError("shared fault at t=0")
                    return "ok"

                r.call(fn)
                times.append(sleeps[0])   # the stream's first retry time
            return times

        window = 0.1
        sync = collect(RetryPolicy(backoff_s=window, jitter="none"), 0)
        # The storm: all N retries at the identical instant.
        assert len(set(sync)) == 1
        jittered = collect(RetryPolicy(backoff_s=window), 100)
        assert all(0.0 <= t <= window for t in jittered)
        # Spread check: no re-collision — minimum pairwise separation is
        # nonzero and the retries span a real fraction of the window.
        ordered = sorted(jittered)
        gaps = [b - a for a, b in zip(ordered, ordered[1:])]
        assert min(gaps) > 0.0
        assert max(ordered) - min(ordered) > window / 4


# --------------------------------------------------------------------------- #
# Hedger
# --------------------------------------------------------------------------- #
class TestHedger:
    def test_disabled_runs_inline_and_times(self):
        h = Hedger(None)
        result, secs = h.call(lambda: "x")
        assert result == "x" and secs is not None and secs >= 0.0
        assert h.hedges == 0

    def test_hedge_fires_on_straggler_and_withholds_timing(self):
        slow_first = [True]

        def fn():
            if slow_first[0]:
                slow_first[0] = False
                time.sleep(0.2)
            return "x"

        h = Hedger(0.01)
        result, secs = h.call(fn)
        assert result == "x"
        assert secs is None          # hedged sample: timing contaminated
        assert h.hedges == 1

    def test_in_flight_cap(self):
        release = threading.Event()

        def stuck():
            release.wait(5.0)
            return "x"

        h = Hedger(0.01, max_in_flight=1)
        results = []
        threads = [threading.Thread(target=lambda: results.append(h.call(stuck)))
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)              # all four primaries straggle
        release.set()
        for t in threads:
            t.join(5.0)
        assert len(results) == 4
        # Only ONE hedge could ever be in flight despite 4 stragglers.
        assert h.hedges <= 1
        assert h.peak_in_flight <= 1

    def test_failure_waits_for_inflight_hedge(self):
        """A failed primary must not raise while the hedged duplicate can
        still rescue the call."""
        calls = []

        def fn():
            calls.append(threading.get_ident())
            if len(calls) == 1:
                time.sleep(0.05)
                raise TransientStoreError("primary fails late")
            return "rescued"

        h = Hedger(0.01)
        result, secs = h.call(fn)
        assert result == "rescued"

    def test_all_attempts_fail_raises(self):
        def fn():
            time.sleep(0.02)
            raise TransientStoreError("down")

        h = Hedger(0.005)
        with pytest.raises(TransientStoreError):
            h.call(fn)


# --------------------------------------------------------------------------- #
# LinkModel: throttle model + honest failure costs
# --------------------------------------------------------------------------- #
class TestLinkModel:
    def test_failed_request_pays_latency(self):
        link = LinkModel(latency_s=0.05)
        link.fail_next(1)
        t0 = time.perf_counter()
        with pytest.raises(TransientStoreError):
            link.transfer(1000)
        assert time.perf_counter() - t0 >= 0.05   # repro: allow[RP008] — lower bound; load only increases elapsed
        assert link.failed_requests == 1
        assert link.requests == 1
        assert link.latency_paid_s >= 0.05
        assert link.bytes_moved == 0

    def test_rps_limit_throttles_burst(self):
        link = LinkModel(rps_limit=5.0, rps_burst=2.0)
        ok, throttled = 0, 0
        for _ in range(10):
            try:
                link.transfer(0)
                ok += 1
            except ThrottleError:
                throttled += 1
        assert ok >= 2               # the burst allowance
        assert throttled >= 1
        assert link.throttled == throttled
        assert link.failed_requests >= throttled

    def test_rps_recovers_after_backoff(self):
        link = LinkModel(rps_limit=50.0, rps_burst=1.0)
        link.transfer(0)
        with pytest.raises(ThrottleError):
            link.transfer(0)
        time.sleep(0.05)             # > 1/rps: a token has refilled
        link.transfer(0)

    def test_sims3_uri_rps_params(self):
        s = open_store(
            "sims3://throttled?rps_limit=100&rps_burst=3&rps_penalty=0.5",
            fresh=True)
        assert s.link.rps_limit == 100.0
        assert s.link.rps_burst == 3.0
        assert s.link.rps_penalty == 0.5

    def test_rps_penalty_escalates_throttling(self):
        # SlowDown escalation: hammering a penalized link drains the
        # bucket below zero, so recovery needs a longer quiet period
        # than the plain token refill — backing off early is cheaper
        # than retrying at pressure.
        def hammer(link, n=6):
            for _ in range(n):
                with pytest.raises(ThrottleError):
                    link.transfer(0)

        plain = LinkModel(rps_limit=20.0, rps_burst=1.0)
        plain.transfer(0)            # spend the burst
        hammer(plain)
        time.sleep(0.06)             # > 1/rps: a token refilled
        plain.transfer(0)            # no penalty: instant recovery

        hot = LinkModel(rps_limit=20.0, rps_burst=1.0, rps_penalty=1.0)
        hot.transfer(0)
        hammer(hot)                  # drains to the -burst floor
        time.sleep(0.06)
        with pytest.raises(ThrottleError):
            hot.transfer(0)          # still in the penalty hole
        time.sleep(0.12)             # (1 + burst)/rps: hole repaid
        hot.transfer(0)

    def test_throttle_is_transient(self):
        assert issubclass(ThrottleError, TransientStoreError)


# --------------------------------------------------------------------------- #
# FaultSchedule / FaultyStore
# --------------------------------------------------------------------------- #
class TestFaultSchedule:
    def test_deterministic_given_seed(self):
        def run(seed):
            sched = FaultSchedule(seed=seed).transient(
                prob=0.5, ops=("get_range",))
            fired = []
            for i in range(50):
                fired.append(bool(sched.decide("get_range", f"k{i}")))
            return fired

        assert run(7) == run(7)
        assert run(7) != run(8)

    def test_every_and_times_and_after(self):
        sched = FaultSchedule().transient(ops=("get_range",), every=3,
                                          times=2, after=1)
        fired = [bool(sched.decide("get_range", "k")) for _ in range(12)]
        # Skip 1, then every 3rd matching request, at most twice.
        assert sum(fired) == 2
        assert fired.index(True) == 3   # requests 2,3,4 -> 3rd match fires

    def test_key_filter(self):
        sched = FaultSchedule().transient(key="shard_3", ops=("get_range",))
        assert not sched.decide("get_range", "shard_1")
        assert sched.decide("get_range", "prefix/shard_3.trk")

    def test_throttle_and_transient_raise(self):
        inner = MemStore()
        inner.put("k", b"abcdef")
        st = FaultyStore(inner, FaultSchedule()
                         .throttle(ops=("get_range",), times=1)
                         .transient(ops=("get_range",), times=1, after=1))
        with pytest.raises(ThrottleError):
            st.get_range("k", 0, 6)
        with pytest.raises(TransientStoreError):
            st.get_range("k", 0, 6)
        assert st.get_range("k", 0, 6) == b"abcdef"
        assert st.snapshot()["throttle"] == 1
        assert st.snapshot()["transient"] == 1

    def test_truncate_and_corrupt_shapes(self):
        inner = MemStore()
        inner.put("k", payload(64))
        st = FaultyStore(inner, FaultSchedule()
                         .truncate(nbytes=16, ops=("get_range",), times=1))
        assert st.get_range("k", 0, 64) == payload(64)[:-16]
        assert st.get_range("k", 0, 64) == payload(64)

        st2 = FaultyStore(inner, FaultSchedule(seed=3)
                          .corrupt(ops=("get_range",), times=1))
        bad = st2.get_range("k", 0, 64)
        assert bad != payload(64) and len(bad) == 64
        # Exactly one byte differs.
        assert sum(a != b for a, b in zip(bad, payload(64))) == 1

    def test_stall_delays(self):
        inner = MemStore()
        inner.put("k", b"x")
        st = FaultyStore(inner, FaultSchedule().stall(0.05, times=1))
        t0 = time.perf_counter()
        st.get_range("k", 0, 1)
        assert time.perf_counter() - t0 >= 0.05   # repro: allow[RP008] — lower bound; load only increases elapsed

    def test_cut_pays_partial_bandwidth(self):
        store = make_store({"k": payload(4096)})
        st = FaultyStore(store, FaultSchedule()
                         .cut(after_bytes=1000, ops=("get_range",), times=1))
        with pytest.raises(TransientStoreError, match="cut"):
            st.get_range("k", 0, 4096)
        # The partial transfer crossed the simulated link for real.
        assert store.link.bytes_moved == 1000
        assert st.get_range("k", 0, 4096) == payload(4096)

    def test_get_ranges_payload_fault_on_last_span(self):
        inner = MemStore()
        inner.put("k", payload(100))
        st = FaultyStore(inner, FaultSchedule()
                         .truncate(nbytes=5, ops=("get_ranges",), times=1))
        out = st.get_ranges("k", [(0, 10), (10, 30)])
        assert out[0] == payload(100)[0:10]
        assert out[1] == payload(100)[10:25]   # tail truncated

    def test_multipart_faults(self):
        inner = MemStore()
        st = FaultyStore(inner, FaultSchedule()
                         .transient(ops=("put_part",), times=1))
        mp = st.start_multipart("k")
        with pytest.raises(TransientStoreError):
            mp.put_part(0, b"aa")
        mp.put_part(0, b"aa")
        mp.put_part(1, b"bb")
        mp.complete()
        assert inner.get("k") == b"aabb"


# --------------------------------------------------------------------------- #
# AIMD throttle feedback
# --------------------------------------------------------------------------- #
class TestThrottleAimd:
    def test_on_throttle_halves_target(self):
        c = AimdDepthController(8, 16, throttle_cooldown_s=0.0)
        assert c.on_throttle() == 4
        assert c.on_throttle() == 2
        assert c.on_throttle() == 1
        assert c.on_throttle() == 1

    def test_throttle_cooldown_coalesces_bursts(self):
        # One halving per cooldown window (TCP's one-cut-per-RTT rule):
        # 8 streams throttled by the same pressure burst must count as
        # ONE signal, not 8 halvings straight to the floor.
        c = AimdDepthController(8, 16, throttle_cooldown_s=1.0)
        assert c.on_throttle(now=10.0) == 4
        assert c.on_throttle(now=10.1) == 4   # within cooldown: coalesced
        assert c.on_throttle(now=10.9) == 4
        assert c.throttle_cuts == 1
        assert c.on_throttle(now=11.1) == 2   # new window: cuts again
        assert c.throttle_cuts == 2

    def test_rolling_engine_shrinks_depth_on_throttle(self):
        objects = {"a": payload(64 << 10)}
        store = make_store(objects)
        sched = FaultSchedule().throttle(ops=("get_range", "get_ranges"),
                                         every=4)
        pf = RollingPrefetcher(
            FaultyStore(store, sched), metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=2048, depth=8, max_depth=8,
                            retry=RetryPolicy(max_retries=8, backoff_s=0.001),
                            eviction_interval_s=0.01),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == objects["a"]
        f.close()
        assert pf.stats.throttles > 0
        # Backend pushback reached the depth controller.
        assert pf._aimd.target < 8

    def test_throttle_oblivious_mode_keeps_depth(self):
        objects = {"a": payload(32 << 10)}
        store = make_store(objects)
        sched = FaultSchedule().throttle(ops=("get_range", "get_ranges"),
                                         every=5)
        throttle_cuts = []
        pf = RollingPrefetcher(
            FaultyStore(store, sched), metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=2048, depth=4, max_depth=4, throttle_aimd=False,
                            retry=RetryPolicy(max_retries=8, backoff_s=0.001),
                            eviction_interval_s=0.01),
        )
        pf._aimd.on_throttle = lambda: throttle_cuts.append(1)  # spy
        f = RollingPrefetchFile(pf)
        assert f.read() == objects["a"]
        f.close()
        assert pf.stats.throttles > 0
        # Oblivious: throttles retried, but none reached the controller
        # (the throughput-window AIMD still runs — that is the point of
        # the A/B: backoff alone, no pushback-driven cut).
        assert not throttle_cuts


# --------------------------------------------------------------------------- #
# End-to-end chaos
# --------------------------------------------------------------------------- #
def chaos_schedule(seed: int = 11) -> FaultSchedule:
    """The standard mixed read-fault script: throttles, transients,
    stalls, truncations, and mid-transfer cuts. Corruption faults live
    in `tests/test_integrity.py`: with verify-on-read (the
    ``IOPolicy.verify`` digest layer) they are detected and healed like
    any other transient."""
    return (FaultSchedule(seed=seed)
            .throttle(ops=("get_range", "get_ranges"), prob=0.08)
            .transient(ops=("get_range", "get_ranges", "get"), prob=0.08)
            .stall(0.002, ops=("get_range", "get_ranges"), prob=0.1)
            .truncate(nbytes=7, ops=("get_range", "get_ranges"), prob=0.05)
            .cut(after_bytes=512, ops=("get_range", "get_ranges"), prob=0.05))


class TestChaosEndToEnd:
    RETRY = RetryPolicy(max_retries=10, backoff_s=0.001, backoff_cap_s=0.01)

    def _dataset(self):
        return {f"f{i}": payload(20_000, seed=i) for i in range(3)}

    def test_rolling_survives_chaos_byte_identical(self):
        objects = self._dataset()
        store = FaultyStore(make_store(objects), chaos_schedule())
        want = b"".join(objects[m.key] for m in metas(store))
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=4096, depth=2,
            retry=self.RETRY, eviction_interval_s=0.01))
        with fs:
            f = fs.open_many(metas(store))
            assert f.read() == want
            f.close()
            snap = fs.stats().snapshot()
        assert snap["totals"]["retries"] > 0
        assert store.schedule.total_fired() > 0

    def test_sequential_survives_chaos_byte_identical(self):
        """Satellite regression: pre-resilience-layer the sequential
        engine propagated the FIRST transient fault."""
        objects = self._dataset()
        store = FaultyStore(make_store(objects), chaos_schedule(seed=13))
        want = b"".join(objects[m.key] for m in metas(store))
        f = SequentialFile(
            store, metas(store),
            policy=IOPolicy(blocksize=4096, retry=self.RETRY),
        )
        assert f.read() == want
        assert f.stats.retries > 0
        f.close()

    def test_sequential_single_fault_regression(self):
        objects = {"a": payload(4096)}
        store = make_store(objects)
        store.link.fail_next(1)
        f = SequentialFile(store, metas(store), policy=IOPolicy(blocksize=1024))
        # Old behaviour: TransientStoreError propagated to the caller.
        assert f.read() == objects["a"]
        assert f.stats.retries == 1

    def test_both_engines_same_schedule_same_bytes(self):
        objects = self._dataset()
        want = b"".join(v for _, v in sorted(objects.items()))
        for engine in ("rolling", "sequential"):
            store = FaultyStore(make_store(objects), chaos_schedule(seed=29))
            fs = PrefetchFS(store, policy=IOPolicy(
                engine=engine, blocksize=2048, retry=self.RETRY,
                eviction_interval_s=0.01))
            with fs:
                f = fs.open_many(metas(store))
                assert f.read() == want, engine
                f.close()

    def test_write_behind_survives_chaos(self):
        store = FaultyStore(
            make_store({}),
            FaultSchedule(seed=5)
            .throttle(ops=("put_part",), prob=0.15)
            .transient(ops=("put_part", "complete", "put"), prob=0.15)
            .stall(0.002, ops=("put_part",), prob=0.2))
        data = payload(100_000, seed=9)
        fs = PrefetchFS(store, policy=IOPolicy(
            blocksize=8192, write_depth=4, retry=self.RETRY))
        with fs:
            w = fs.open_write("out/key")
            for off in range(0, len(data), 3000):
                w.write(data[off:off + 3000])
            w.close()
            assert w.stats.snapshot()["retries"] > 0
        assert store.inner.backing.get("out/key") == data

    def test_ckpt_save_restore_under_chaos(self):
        import numpy as np

        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        sched = (FaultSchedule(seed=23)
                 .transient(ops=("put", "put_part", "complete"), prob=0.1)
                 .throttle(ops=("size", "list_objects"), prob=0.1)
                 .transient(ops=("get_range", "get_ranges", "get"), prob=0.1))
        store = FaultyStore(make_store({}), sched)
        state = {"w": np.arange(4096, dtype=np.float32).reshape(64, 64),
                 "b": np.ones((257,), dtype=np.float32)}
        pol = IOPolicy(blocksize=4096, retry=self.RETRY,
                       eviction_interval_s=0.01)
        save_checkpoint(store, "ckpt", 3, state, policy=pol)
        restored, manifest = restore_checkpoint(store, "ckpt", state,
                                                policy=pol)
        assert manifest["step"] == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])
        np.testing.assert_array_equal(np.asarray(restored["b"]), state["b"])
        assert sched.total_fired() > 0

    def test_no_leaked_threads_after_close(self):
        objects = self._dataset()
        store = FaultyStore(
            make_store(objects, latency=0.002),
            FaultSchedule(seed=31)
            .stall(0.02, ops=("get_range", "get_ranges"), prob=0.3)
            .transient(ops=("get_range", "get_ranges"), prob=0.1))
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=4096, depth=3,
            hedge_timeout_s=0.005, max_hedges=2, retry=self.RETRY,
            eviction_interval_s=0.01))
        with fs:
            f = fs.open_many(metas(store))
            want = b"".join(objects[m.key] for m in metas(store))
            assert f.read() == want
            f.close()
        # Hedge attempts are daemon threads bounded by the in-flight
        # cap; after close everything drains (store calls complete).
        deadline = time.time() + 5.0
        while time.time() < deadline:
            leaked = [t for t in threading.enumerate()
                      if t.name.startswith(("rp-", "hedge-"))
                      and t.is_alive()]
            if not leaked:
                break
            time.sleep(0.02)
        assert not leaked, leaked

    def test_hedges_bounded_under_systemic_slowdown(self):
        objects = {"a": payload(64 << 10)}
        store = FaultyStore(
            make_store(objects, latency=0.005),
            FaultSchedule(seed=37).stall(0.03, ops=("get_range",
                                                    "get_ranges"), prob=1.0))
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=4096, depth=4, hedge_timeout_s=0.002,
                            max_hedges=2, retry=self.RETRY, eviction_interval_s=0.01),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == objects["a"]
        f.close()
        # EVERY request straggled, but duplicates stayed capped.
        assert pf._hedger.peak_in_flight <= 2
        assert pf.stats.hedges == pf._hedger.hedges

    def test_writer_upload_pool_drains_after_chaos_close(self):
        store = FaultyStore(
            make_store({}),
            FaultSchedule(seed=41).transient(ops=("put_part",), prob=0.2))
        fs = PrefetchFS(store, policy=IOPolicy(blocksize=2048,
                                               write_depth=3,
                                               retry=self.RETRY))
        with fs:
            for i in range(4):
                w = fs.open_write(f"k{i}")
                w.write(payload(10_000, seed=i))
                w.close_async()
                w.join()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            leaked = [t for t in threading.enumerate()
                      if t.name.startswith("fs-upload") and t.is_alive()]
            if not leaked:
                break
            time.sleep(0.02)
        assert not leaked, leaked
        for i in range(4):
            assert store.inner.backing.get(f"k{i}") == payload(10_000, seed=i)

    def test_truncated_response_detected_and_retried(self):
        objects = {"a": payload(8192)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule().truncate(nbytes=3, ops=("get_range",), times=1))
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=2048, retry=self.RETRY, eviction_interval_s=0.01),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == objects["a"]   # NOT silently short
        f.close()
        assert pf.stats.retries >= 1


# --------------------------------------------------------------------------- #
# Chaos through the peer transport
# --------------------------------------------------------------------------- #
class TestPeerChaos:
    """`FaultSchedule` rules routed through the ``peer_*`` ops hit the
    `PeerClient` transport (see `repro.peer.protocol.PEER_OPS`): peer
    stalls, transient refusals, mid-transfer cuts, and dead heartbeats
    must all degrade to direct store GETs — byte-identical reads, zero
    errors surfaced to readers."""

    N_HOSTS = 3
    BLOCKSIZE = 4096

    def _dataset(self):
        return {f"p{i}": payload(16_384, seed=i) for i in range(3)}

    def _read_all_hosts(self, cluster, objects):
        want = b"".join(objects[k] for k in sorted(objects))
        outs, errors = {}, []

        def run(h):
            try:
                host = cluster.host(h)
                fs = host.open_fs(IOPolicy(
                    engine="rolling", blocksize=self.BLOCKSIZE, depth=2,
                    keep_cached=True, eviction_interval_s=0.05))
                files = sorted(host.store.list_objects(),
                               key=lambda m: m.key)
                f = fs.open_many(files)
                try:
                    outs[h] = f.read()
                finally:
                    f.close()
            except BaseException as e:  # repro: allow[RP005] — stashed; asserted after join
                errors.append((h, e))

        threads = [threading.Thread(target=run, args=(h,))
                   for h in range(self.N_HOSTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for h in range(self.N_HOSTS):
            assert outs[h] == want, f"host {h} bytes diverged under chaos"
        return outs

    def _cluster(self, objects, faults, **kw):
        from repro.peer.sim import SimCluster

        backing = MemStore()
        for k, v in objects.items():
            backing.put(k, v)
        return SimCluster(self.N_HOSTS, backing, faults=faults, **kw)

    def test_peer_transients_degrade_byte_identical(self):
        objects = self._dataset()
        sched = FaultSchedule(seed=17).transient(ops=("peer_fetch",),
                                                 prob=0.3)
        cluster = self._cluster(objects, sched)
        try:
            self._read_all_hosts(cluster, objects)
            failures = sum(
                cluster.host(h).store.peer_snapshot()["group"]["rpc_failures"]
                for h in range(self.N_HOSTS))
            assert failures > 0          # the chaos actually landed
            assert sched.total_fired() > 0
        finally:
            cluster.close()

    def test_peer_stalls_within_rpc_timeout(self):
        objects = self._dataset()
        sched = FaultSchedule(seed=19).stall(0.005, ops=("peer_fetch",),
                                             prob=0.3)
        cluster = self._cluster(objects, sched)
        try:
            self._read_all_hosts(cluster, objects)
            assert sched.total_fired() > 0
        finally:
            cluster.close()

    def test_peer_cut_mid_transfer_rereads_identically(self):
        """A cut declares the connection dead AFTER the bytes crossed the
        wire: the retry (or the store fallback) must observe the same
        bytes — no torn or duplicated block may reach a reader."""
        objects = self._dataset()
        sched = FaultSchedule(seed=23).cut(after_bytes=512,
                                           ops=("peer_fetch",), prob=0.25)
        cluster = self._cluster(objects, sched)
        try:
            self._read_all_hosts(cluster, objects)
            assert sched.total_fired() > 0
        finally:
            cluster.close()

    def test_dead_heartbeats_fail_everything_over_to_the_store(self):
        """Every heartbeat ping fails: all siblings get marked dead, all
        remote-owned blocks degrade to direct backing GETs, and the reads
        stay exact."""
        objects = self._dataset()
        sched = FaultSchedule(seed=29).transient(ops=("peer_ping",),
                                                 prob=1.0)
        cluster = self._cluster(objects, sched,
                                heartbeat_interval_s=0.02, miss_limit=2)
        try:
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if all(cluster.host(h).group.alive_ids() == [h]
                       for h in range(self.N_HOSTS)):
                    break
                time.sleep(0.02)
            self._read_all_hosts(cluster, objects)
            for h in range(self.N_HOSTS):
                snap = cluster.host(h).store.peer_snapshot()
                # Nothing remote-owned was served by a peer...
                assert snap["peer_hits"] == 0
                # ...every read degraded to the backing store.
                assert (snap["dead_peer_fallbacks"] > 0
                        or snap["local_fetches"] > 0)
        finally:
            cluster.close()

    def test_mixed_peer_chaos_with_store_chaos(self):
        """Peer faults AND backing-store faults at once: the peer layer
        degrades to the store, the store's own retry machinery absorbs
        its faults, and the bytes stay exact."""
        objects = self._dataset()
        sched = (FaultSchedule(seed=31)
                 .transient(ops=("peer_fetch",), prob=0.2)
                 .stall(0.002, ops=("peer_fetch",), prob=0.2)
                 .cut(after_bytes=256, ops=("peer_fetch",), prob=0.1))
        backing = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=37).transient(
                ops=("get_range", "get_ranges"), prob=0.1))
        from repro.peer.sim import SimCluster

        cluster = SimCluster(self.N_HOSTS, backing, faults=sched)
        try:
            self._read_all_hosts(cluster, objects)
            assert sched.total_fired() > 0
        finally:
            cluster.close()
