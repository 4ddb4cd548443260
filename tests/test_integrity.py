"""End-to-end block integrity (PR tentpole + satellites).

Covers: the digest helpers (`repro.io.integrity`), verified store reads
(the wrapper stores attest authoritative bytes, not mangled ones),
`DirTier` steady-state rot detection (the post-recovery regression),
`CacheIndex.quarantine` semantics, engine self-healing under corruption
chaos for both engines and all three ``IOPolicy.verify`` modes,
checkpoint manifest digests, and the acceptance scenario: simultaneous
store-read corruption, at-rest tier rot, and peer-frame corruption with
byte-identical reads and zero `IntegrityError`s surfaced."""

from __future__ import annotations

import zlib

import pytest

from repro.core.rolling import RollingPrefetcher, RollingPrefetchFile
from repro.core.sequential import SequentialFile
from repro.io import IOPolicy, PrefetchFS
from repro.io.integrity import (
    IntegrityError,
    block_digest,
    check_block,
    crc_digest,
    digest_matches,
)
from repro.io.retry import RetryPolicy
from repro.store import (
    BlockMeta,
    CacheIndex,
    DirTier,
    FaultSchedule,
    FaultyStore,
    MemStore,
    MemTier,
)
from repro.store.base import ObjectMeta, StoreError

RETRY = RetryPolicy(max_retries=10, backoff_s=0.001, backoff_cap_s=0.01)


def payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


def make_store(objects: dict[str, bytes]) -> MemStore:
    store = MemStore()
    for k, v in objects.items():
        store.put(k, v)
    return store


def metas(store) -> list[ObjectMeta]:
    inner = getattr(store, "inner", store)
    return inner.list_objects()


# --------------------------------------------------------------------------- #
# digest helpers
# --------------------------------------------------------------------------- #
class TestDigestHelpers:
    def test_crc32_format_matches_zlib(self):
        data = payload(1000)
        assert block_digest(data) == f"crc32:{zlib.crc32(data) & 0xFFFFFFFF:08x}"

    def test_crc_digest_agrees_with_block_digest(self):
        data = payload(333, seed=4)
        assert crc_digest(zlib.crc32(data)) == block_digest(data)

    def test_blake2_format(self):
        d = block_digest(b"hello", algo="blake2")
        assert d.startswith("blake2:") and len(d.split(":", 1)[1]) == 32

    def test_unknown_algo_rejected(self):
        with pytest.raises(ValueError):
            block_digest(b"x", algo="md5")

    def test_check_block_none_digest_is_noop(self):
        check_block(b"anything", None)   # pre-digest producers verify nothing

    def test_check_block_mismatch_raises_with_context(self):
        good = payload(64)
        dig = block_digest(good)
        with pytest.raises(IntegrityError, match="blk@0-64"):
            check_block(good[:-1] + b"\x00", dig, what="blk@0-64")

    def test_digest_matches_fails_closed_on_garbage(self):
        data = b"abc"
        assert digest_matches(data, block_digest(data))
        for junk in ("", "crc32", "crc32:zzzz", "sha9000:00", "crc32:"):
            assert not digest_matches(data, junk)

    def test_memoryview_accepted(self):
        data = payload(128)
        assert block_digest(memoryview(data)) == block_digest(data)
        check_block(memoryview(data), block_digest(data))


# --------------------------------------------------------------------------- #
# verified store reads
# --------------------------------------------------------------------------- #
class TestVerifiedReads:
    def test_default_verified_reads_attest_returned_bytes(self):
        store = make_store({"k": payload(4096)})
        data, dig = store.get_range_verified("k", 100, 600)
        assert data == payload(4096)[100:600]
        check_block(data, dig)
        pairs = store.get_ranges_verified("k", [(0, 10), (10, 50)])
        for d, g in pairs:
            check_block(d, g)

    def test_digest_range_matches_block_digest(self):
        store = make_store({"k": payload(2048)})
        assert store.digest_range("k", 64, 512) == block_digest(
            payload(2048)[64:512])

    def test_faulty_store_digest_attests_inner_bytes(self):
        """THE detection contract: a corrupting wrapper must hand out the
        digest of the authoritative bytes, so the mangled payload fails
        its own attestation instead of sailing through."""
        store = FaultyStore(
            make_store({"k": payload(4096)}),
            FaultSchedule(seed=3).corrupt(ops=("get_range",), times=1))
        data, dig = store.get_range_verified("k", 0, 4096)
        assert data != payload(4096)           # the fault landed...
        assert dig == block_digest(payload(4096))   # ...the digest did not
        with pytest.raises(IntegrityError):
            check_block(data, dig)
        # The next read is clean and self-consistent.
        data, dig = store.get_range_verified("k", 0, 4096)
        check_block(data, dig)

    def test_faulty_store_vectorized_corruption_detected(self):
        store = FaultyStore(
            make_store({"k": payload(8192)}),
            FaultSchedule(seed=5).corrupt(ops=("get_ranges",), times=1))
        pairs = store.get_ranges_verified("k", [(0, 4096), (4096, 8192)])
        bad = [not digest_matches(d, g) for d, g in pairs]
        assert any(bad)                        # last span got mangled
        assert not all(bad)                    # earlier spans stayed honest


# --------------------------------------------------------------------------- #
# DirTier at-rest rot (satellite: post-recovery reads were unchecked)
# --------------------------------------------------------------------------- #
class TestDirTierRot:
    def _flip_on_disk(self, tier: DirTier, bid: str) -> None:
        path = tier._path(bid)
        with open(path, "r+b") as f:
            raw = bytearray(f.read())
            raw[len(raw) // 2] ^= 0xFF
            f.seek(0)
            f.write(raw)

    def test_rot_after_write_raises_integrity_error(self, tmp_path):
        tier = DirTier(1 << 20, root=str(tmp_path / "t"))
        data = payload(512)
        tier.write("blk@0-512", data, meta=BlockMeta(key="blk", offset=0))
        assert tier.read("blk@0-512") == data
        self._flip_on_disk(tier, "blk@0-512")
        with pytest.raises(IntegrityError):
            tier.read("blk@0-512")

    def test_rot_after_recovery_regression(self, tmp_path):
        """Regression: recovery has always crc-checked blocks, but a
        block that rotted AFTER recovery was served as-is for the life of
        the process. Steady-state reads now recompute the journal crc."""
        root = str(tmp_path / "t")
        tier = DirTier(1 << 20, root=root)
        data = payload(1024, seed=2)
        tier.write("k@0-1024", data, meta=BlockMeta(key="k", offset=0))
        tier.close()

        tier2 = DirTier(1 << 20, root=root)
        assert tier2.recovered_blocks == 1
        assert tier2.read("k@0-1024") == data   # recovered AND clean
        self._flip_on_disk(tier2, "k@0-1024")
        with pytest.raises(IntegrityError):
            tier2.read("k@0-1024")              # rotted post-recovery

    def test_partial_reads_not_coverable_by_journal_crc(self, tmp_path):
        # The journal crc covers the full block; a sliced read cannot be
        # checked against it, which is why engines under verify promote
        # backward-seek hits to full-block reads.
        tier = DirTier(1 << 20, root=str(tmp_path / "t"))
        data = payload(512)
        tier.write("b@0-512", data, meta=BlockMeta(key="b", offset=0))
        self._flip_on_disk(tier, "b@0-512")
        assert len(tier.read("b@0-512", 0, 10)) == 10   # served unchecked
        with pytest.raises(IntegrityError):
            tier.read("b@0-512")                        # full read: caught

    def test_verify_reads_off_serves_rot(self, tmp_path):
        tier = DirTier(1 << 20, root=str(tmp_path / "t"), verify_reads=False)
        data = payload(256)
        tier.write("b@0-256", data, meta=BlockMeta(key="b", offset=0))
        self._flip_on_disk(tier, "b@0-256")
        assert tier.read("b@0-256") != data   # the documented escape hatch

    def test_flip_at_rest_fault_hook(self, tmp_path):
        tier = DirTier(1 << 20, root=str(tmp_path / "t"),
                       faults=FaultSchedule(seed=7).flip_at_rest(times=1))
        data = payload(512, seed=3)
        tier.write("b@0-512", data, meta=BlockMeta(key="b", offset=0))
        with pytest.raises(IntegrityError):
            tier.read("b@0-512")
        # The rule fired once; after quarantine+rewrite the block is fine.
        tier.delete("b@0-512")
        tier.write("b@0-512", data, meta=BlockMeta(key="b", offset=0))
        assert tier.read("b@0-512") == data

    def test_digest_of_matches_helper(self, tmp_path):
        tier = DirTier(1 << 20, root=str(tmp_path / "t"))
        data = payload(300)
        tier.write("b@0-300", data, meta=BlockMeta(key="b", offset=0))
        assert tier.digest_of("b@0-300") == block_digest(data)


# --------------------------------------------------------------------------- #
# quarantine semantics
# --------------------------------------------------------------------------- #
class TestQuarantine:
    def test_quarantine_evicts_and_counts(self):
        tiers = [MemTier(1 << 20)]
        index = CacheIndex(tiers, keep_cached=True)
        kind, fl = index.acquire("b@0-4")
        assert kind == "leader"
        tiers[0].write("b@0-4", b"data")
        index.publish(fl, tiers[0], 4, digest=block_digest(b"data"))
        assert index.contains("b@0-4")
        assert index.digest_of("b@0-4") == block_digest(b"data")

        assert index.quarantine("b@0-4")
        assert not index.contains("b@0-4")
        assert not tiers[0].contains("b@0-4")   # tier copy deleted too
        assert index.snapshot()["quarantined"] == 1
        assert not index.quarantine("b@0-4")    # second call: nothing left

    def test_quarantine_ignores_pins(self):
        tiers = [MemTier(1 << 20)]
        index = CacheIndex(tiers, keep_cached=True)
        kind, fl = index.acquire("b@0-4")
        assert kind == "leader"
        tiers[0].write("b@0-4", b"data")
        index.publish(fl, tiers[0], 4)
        # publish leaves the leader pin; quarantine must not wait on it —
        # every pinned reader would read the same corrupt bytes.
        assert index.quarantine("b@0-4")
        index.unpin("b@0-4")                    # late unpin is a no-op

    def test_recovered_dir_tier_primes_digests(self, tmp_path):
        root = str(tmp_path / "t")
        tier = DirTier(1 << 20, root=root)
        data = payload(400)
        tier.write("k@0-400", data, meta=BlockMeta(key="k", offset=0))
        tier.close()
        tier2 = DirTier(1 << 20, root=root)
        index = CacheIndex([tier2], keep_cached=True)
        assert index.contains("k@0-400")
        assert index.digest_of("k@0-400") == block_digest(data)


# --------------------------------------------------------------------------- #
# engine healing under corruption chaos
# --------------------------------------------------------------------------- #
class TestEngineHealing:
    def _objects(self):
        return {f"f{i}": payload(20_000, seed=i) for i in range(3)}

    @pytest.mark.parametrize("verify", ["edges", "full"])
    def test_rolling_heals_store_corruption(self, verify):
        objects = self._objects()
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=11).corrupt(
                ops=("get_range", "get_ranges"), prob=0.1))
        want = b"".join(objects[m.key] for m in metas(store))
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=4096, retry=RETRY, eviction_interval_s=0.01,
                            verify=verify),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == want            # byte-identical, zero errors
        f.close()
        assert pf.stats.integrity_failures > 0   # chaos landed + detected
        assert pf.stats.retries > 0              # healed by re-fetch
        assert pf.stats.blocks_verified > 0

    def test_sequential_heals_store_corruption(self):
        objects = self._objects()
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=13).corrupt(
                ops=("get_range", "get_ranges"), prob=0.25))
        want = b"".join(objects[m.key] for m in metas(store))
        f = SequentialFile(
            store, metas(store),
            policy=IOPolicy(blocksize=4096, retry=RETRY),
        )
        assert f.read() == want
        assert f.stats.integrity_failures > 0
        f.close()

    def test_verify_off_trusts_the_wire(self):
        """The zero-overhead baseline stays selectable — and therefore
        stays vulnerable, which is the A/B the benchmark quantifies."""
        objects = {"a": payload(8192)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=3).corrupt(ops=("get_range", "get_ranges"),
                                          times=1))
        f = SequentialFile(
            store, metas(store),
            policy=IOPolicy(blocksize=8192, retry=RETRY, verify="off"),
        )
        assert f.read() != objects["a"]    # corruption sailed through
        assert f.stats.integrity_failures == 0
        f.close()

    def test_rolling_heals_at_rest_rot_on_cached_read(self, tmp_path):
        """A cached block rots in the DirTier between reads: the re-read
        detects (journal crc), quarantines, and transparently re-fetches
        from the store."""
        objects = {"a": payload(32_768)}
        store = make_store(objects)
        tier = DirTier(1 << 20, root=str(tmp_path / "t"),
                       faults=FaultSchedule(seed=17).flip_at_rest(prob=0.3))
        pf = RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=4096, retry=RETRY, eviction_interval_s=10.0,
                            verify="edges"),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == objects["a"]    # populate the cache
        for _ in range(4):                 # rot fires on later reads
            f.seek(0)
            assert f.read() == objects["a"]
        f.close()
        assert pf.stats.integrity_failures > 0
        assert pf.index.snapshot()["quarantined"] > 0

    def test_unhealable_corruption_raises_typed_error(self):
        """EVERY store response corrupt: retries exhaust and the caller
        gets the typed IntegrityError, not a silent wrong read."""
        objects = {"a": payload(4096)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=19).corrupt(ops=("get_range", "get_ranges"),
                                           prob=1.0))
        f = SequentialFile(
            store, metas(store),
            policy=IOPolicy(blocksize=4096,
                            retry=RetryPolicy(max_retries=2, backoff_s=0.0)),
        )
        with pytest.raises(IntegrityError):
            f.read()
        f.close()

    def test_unhealable_corruption_stays_typed_in_rolling(self):
        """Same guarantee through the rolling reader: the scheduler-side
        failure must not be re-wrapped into a generic StoreError."""
        objects = {"a": payload(4096)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=19).corrupt(ops=("get_range", "get_ranges"),
                                           prob=1.0))
        pf = RollingPrefetcher(
            store, metas(store), [MemTier(1 << 20)],
            policy=IOPolicy(blocksize=4096,
                            retry=RetryPolicy(max_retries=2, backoff_s=0.0),
                            eviction_interval_s=10.0, verify="edges"),
        )
        f = RollingPrefetchFile(pf)
        with pytest.raises(IntegrityError):
            f.read()
        f.close()

    def test_policy_verify_reaches_engines(self):
        with pytest.raises(ValueError):
            IOPolicy(verify="paranoid")
        objects = {"a": payload(4096)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=3).corrupt(ops=("get_range", "get_ranges"),
                                          times=1))
        fs = PrefetchFS(store, policy=IOPolicy(
            engine="rolling", blocksize=2048, retry=RETRY,
            eviction_interval_s=0.01, verify="edges"))
        with fs:
            with fs.open_many(metas(store)) as f:
                assert f.read() == objects["a"]
            snap = fs.stats().snapshot()
        assert snap["integrity"]["failures"] > 0
        assert snap["integrity"]["blocks_verified"] > 0


# --------------------------------------------------------------------------- #
# checkpoint manifest digests
# --------------------------------------------------------------------------- #
class TestCheckpointDigests:
    def _state(self):
        import numpy as np

        return {"w": np.arange(4096, dtype=np.float32).reshape(64, 64),
                "b": np.ones((513,), dtype=np.float32)}

    def test_manifest_carries_per_leaf_digests(self):
        import json

        from repro.ckpt.manager import save_checkpoint

        store = MemStore()
        save_checkpoint(store, "ckpt", 1, self._state(),
                        policy=IOPolicy(blocksize=4096))
        manifests = [m.key for m in store.list_objects()
                     if m.key.endswith("MANIFEST.json")]
        assert manifests
        manifest = json.loads(store.get(manifests[0]))
        assert manifest["leaves"]
        for entry in manifest["leaves"]:
            assert entry["digest"].startswith("crc32:")

    def test_restore_detects_rotted_leaf(self):
        import numpy as np

        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        store = MemStore()
        state = self._state()
        save_checkpoint(store, "ckpt", 2, state,
                        policy=IOPolicy(blocksize=4096))
        # Rot one leaf object at rest, self-consistently: the store now
        # honestly serves wrong bytes, so only the manifest digest — the
        # attestation minted at save time — can catch it.
        leaf = next(m.key for m in store.list_objects()
                    if m.key.endswith(".raw"))
        raw = bytearray(store.get(leaf))
        raw[len(raw) // 2] ^= 0xFF
        store.put(leaf, bytes(raw))
        with pytest.raises(IntegrityError, match="checkpoint leaf"):
            restore_checkpoint(store, "ckpt", state,
                               policy=IOPolicy(blocksize=4096))
        # verify="off" restores the rot without complaint (the baseline).
        restored, _ = restore_checkpoint(store, "ckpt", state,
                                         policy=IOPolicy(blocksize=4096,
                                                         verify="off"))
        assert any(
            not np.array_equal(np.asarray(restored[k]), state[k])
            for k in state)

    def test_roundtrip_under_transit_corruption(self):
        import numpy as np

        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        store = FaultyStore(
            MemStore(),
            FaultSchedule(seed=23).corrupt(
                ops=("get_range", "get_ranges"), prob=0.1))
        state = self._state()
        pol = IOPolicy(blocksize=4096, retry=RETRY)
        save_checkpoint(store, "ckpt", 3, state, policy=pol)
        restored, manifest = restore_checkpoint(store, "ckpt", state,
                                                policy=pol)
        assert manifest["step"] == 3
        for k in state:
            np.testing.assert_array_equal(np.asarray(restored[k]), state[k])


# --------------------------------------------------------------------------- #
# acceptance: simultaneous corruption on every path
# --------------------------------------------------------------------------- #
class TestAcceptanceChaos:
    def test_all_paths_corrupting_at_once_single_host(self, tmp_path):
        """Store reads corrupt at ~5%, the local DirTier rots blocks at
        rest, and the read + checkpoint round trips stay byte-identical
        with ZERO IntegrityErrors surfaced to callers."""
        import numpy as np

        from repro.ckpt.manager import restore_checkpoint, save_checkpoint

        objects = {f"s{i}": payload(24_576, seed=i) for i in range(3)}
        store = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=29).corrupt(
                ops=("get_range", "get_ranges", "get"), prob=0.05))
        want = b"".join(objects[m.key] for m in metas(store))
        tier = DirTier(4 << 20, root=str(tmp_path / "t"),
                       faults=FaultSchedule(seed=31).flip_at_rest(prob=0.05))
        pf = RollingPrefetcher(
            store, metas(store), [tier],
            policy=IOPolicy(blocksize=4096, retry=RETRY, eviction_interval_s=10.0,
                            verify="edges"),
        )
        f = RollingPrefetchFile(pf)
        assert f.read() == want
        f.seek(0)
        assert f.read() == want            # cached pass, with at-rest rot
        f.close()
        assert pf.stats.integrity_failures > 0

        state = {"w": np.arange(8192, dtype=np.float32).reshape(128, 64)}
        pol = IOPolicy(blocksize=4096, retry=RETRY)
        save_checkpoint(store, "ckpt", 9, state, policy=pol)
        restored, manifest = restore_checkpoint(store, "ckpt", state,
                                                policy=pol)
        assert manifest["step"] == 9
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])

    def test_peer_frame_corruption_heals_cluster_wide(self):
        """Peer BLOCK frames corrupt in transit AND the backing store
        corrupts reads: every host's bytes stay exact."""
        import threading

        from repro.peer.sim import SimCluster

        objects = {f"p{i}": payload(16_384, seed=i) for i in range(3)}
        backing = FaultyStore(
            make_store(objects),
            FaultSchedule(seed=37).corrupt(
                ops=("get_range", "get_ranges"), prob=0.05))
        peer_faults = FaultSchedule(seed=41).corrupt(ops=("peer_fetch",),
                                                     prob=0.25)
        cluster = SimCluster(3, backing, faults=peer_faults)
        try:
            want = b"".join(objects[k] for k in sorted(objects))
            outs, errors = {}, []

            def run(h):
                try:
                    host = cluster.host(h)
                    fs = host.open_fs(IOPolicy(
                        engine="rolling", blocksize=4096, depth=2,
                        keep_cached=True, retry=RETRY,
                        eviction_interval_s=0.05))
                    files = sorted(host.store.list_objects(),
                                   key=lambda m: m.key)
                    with fs.open_many(files) as f:
                        outs[h] = f.read()
                except BaseException as e:  # repro: allow[RP005] — stashed; asserted after join
                    errors.append((h, e))

            threads = [threading.Thread(target=run, args=(h,))
                       for h in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            for h in range(3):
                assert outs[h] == want, f"host {h} diverged"
            # The chaos was DETECTED (frame digests at clients, store
            # attestation at owner-fetching servers), not just absent.
            detected = sum(
                c.integrity_failures
                for h in range(3)
                for c in cluster.host(h).group._clients.values())
            detected += sum(cluster.host(h).server.integrity_failures
                            for h in range(3))
            assert detected > 0
            assert peer_faults.total_fired() > 0
        finally:
            cluster.close()
