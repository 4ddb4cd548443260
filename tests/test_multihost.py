"""Multi-host input-pipeline simulation: N hosts stream disjoint shard
sets from one shared object store — with failures, stragglers, and a
host replacement mid-epoch — asserting the properties a thousand-node
job depends on. The peer-cluster tests at the bottom add the
distributed-prefetch claim: N hosts streaming ONE shared dataset through
a `PeerGroup` issue ~1x backing-store GETs, including across a host
death mid-epoch."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from repro.data import DataCursor, LoaderConfig, PrefetchingDataLoader, synth_token_shard
from repro.io import IOPolicy
from repro.peer.sim import SimCluster
from repro.store import LinkModel, MemTier, SimS3Store

N_HOSTS = 8
N_SHARDS = 32


@pytest.fixture()
def store():
    rng = np.random.default_rng(7)
    s = SimS3Store(link=LinkModel(latency_s=0.001, bandwidth_Bps=200e6))
    for i in range(N_SHARDS):
        s.backing.put(f"tok{i:03d}.bin", synth_token_shard(rng, 3000, vocab=1000))
    return s


def _loader(store, host, cursor=None, **policy):
    cfg = LoaderConfig(
        seq_len=64, batch_size=2, host_id=host, num_hosts=N_HOSTS,
        policy=IOPolicy(blocksize=4096, eviction_interval_s=0.2, **policy),
    )
    return PrefetchingDataLoader(
        store, store.backing.list_objects(), [MemTier(1 << 20)], cfg,
        cursor=cursor,
    )


class TestMultiHost:
    def test_hosts_cover_disjoint_shards(self, store):
        files = store.backing.list_objects()
        assigned = []
        for h in range(N_HOSTS):
            loader = _loader(store, h)
            assigned.extend(m.key for m in loader.my_files)
            loader.close()
        assert sorted(assigned) == sorted(m.key for m in files)
        assert len(set(assigned)) == len(assigned)

    def test_concurrent_hosts_stream_correct_data(self, store):
        """All hosts pull batches concurrently through the SHARED link;
        every host's stream must equal its single-threaded reference."""
        results: dict[int, list] = {}
        errors: list = []

        def run(host):
            try:
                loader = _loader(store, host)
                results[host] = [b[0] for b in loader.batches(max_batches=3)]
                loader.close()
            except BaseException as e:  # repro: allow[RP005] — stashed; asserted after join
                errors.append((host, e))

        threads = [threading.Thread(target=run, args=(h,))
                   for h in range(N_HOSTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors

        for h in range(N_HOSTS):
            ref_loader = _loader(store, h)
            ref = [b[0] for b in ref_loader.batches(max_batches=3)]
            ref_loader.close()
            for a, b in zip(results[h], ref):
                np.testing.assert_array_equal(a, b)

    def test_host_replacement_resumes_deterministically(self, store):
        """Host 3 'dies' after 2 batches; its replacement restores the
        cursor and must produce exactly the batches the original would
        have produced next."""
        loader = _loader(store, 3)
        consumed = [b for b in loader.batches(max_batches=2)]
        cursor = DataCursor(**loader.cursor.to_dict())
        loader.close()  # host dies

        # Uninterrupted reference.
        ref_loader = _loader(store, 3)
        ref = [b for b in ref_loader.batches(max_batches=5)]
        ref_loader.close()

        # Replacement host resumes from the checkpointed cursor.
        repl = _loader(store, 3, cursor=cursor)
        resumed = [b for b in repl.batches(max_batches=3)]
        repl.close()
        for (a, _), (b, _) in zip(resumed, ref[2:]):
            np.testing.assert_array_equal(a, b)

    def test_transient_store_failures_do_not_corrupt_streams(self, store):
        store.link.fail_prob = 0.02
        store.link._rng.seed(123)
        loader = _loader(store, 0)
        batches = [b for b in loader.batches(max_batches=4)]
        loader.close()
        store.link.fail_prob = 0.0
        ref_loader = _loader(store, 0)
        ref = [b for b in ref_loader.batches(max_batches=4)]
        ref_loader.close()
        for (a, _), (b, _) in zip(batches, ref):
            np.testing.assert_array_equal(a, b)

    def test_straggler_hedging_under_jitter(self, store):
        store.link.jitter = 2.0  # heavy-tailed latencies
        loader = _loader(store, 1, hedge_timeout_s=0.01)
        batches = [b for b in loader.batches(max_batches=3)]
        stats = loader.stats
        loader.close()
        assert len(batches) == 3
        assert stats is not None  # hedges counter exists (may or may not fire)


# --------------------------------------------------------------------------- #
# Distributed prefetch: peer cluster over one shared dataset
# --------------------------------------------------------------------------- #
PEER_HOSTS = 4
PEER_BLOCKSIZE = 4096


def peer_payload(n: int, seed: int = 0) -> bytes:
    return bytes((i * 31 + seed * 7) % 256 for i in range(n))


@pytest.fixture()
def peer_dataset():
    return {f"shard{i:02d}": peer_payload(24_576, seed=i) for i in range(6)}


@pytest.fixture()
def peer_backing(peer_dataset):
    s = SimS3Store(link=LinkModel(latency_s=0.001, bandwidth_Bps=200e6))
    for k, v in peer_dataset.items():
        s.backing.put(k, v)
    return s


def _stream_all(cluster, hosts, *, engine="rolling"):
    """Every listed host reads the FULL dataset through its peer store;
    returns ({host: bytes}, [errors])."""
    outs: dict[int, bytes] = {}
    errors: list = []

    def run(h):
        try:
            host = cluster.host(h)
            fs = host.open_fs(IOPolicy(
                engine=engine, blocksize=PEER_BLOCKSIZE, depth=2,
                keep_cached=True, eviction_interval_s=0.05))
            files = sorted(host.store.list_objects(), key=lambda m: m.key)
            f = fs.open_many(files)
            try:
                outs[h] = f.read()
            finally:
                f.close()
        except BaseException as e:  # repro: allow[RP005] — stashed; asserted after join
            errors.append((h, e))

    threads = [threading.Thread(target=run, args=(h,)) for h in hosts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs, errors


class TestPeerCluster:
    def test_shared_dataset_issues_one_x_backing_gets(self, peer_dataset,
                                                      peer_backing):
        """The headline claim: N hosts each streaming the WHOLE dataset
        through a shared PeerGroup cost ~1x backing GETs (each block's
        home host does the one WAN fetch; siblings pull over the LAN),
        not Nx — and every host's bytes are exact."""
        n_blocks = sum(-(-len(v) // PEER_BLOCKSIZE)
                       for v in peer_dataset.values())
        want = b"".join(peer_dataset[k] for k in sorted(peer_dataset))
        cluster = SimCluster(PEER_HOSTS, peer_backing)
        try:
            outs, errors = _stream_all(cluster, range(PEER_HOSTS))
            assert not errors, errors
            for h in range(PEER_HOSTS):
                assert outs[h] == want, f"host {h} bytes diverged"
            amplification = cluster.backing_fetches / n_blocks
            assert amplification <= 1.2, (
                f"{cluster.backing_fetches} backing GETs for {n_blocks} "
                f"blocks = {amplification:.2f}x (expected ~1x, "
                f"Nx would be {PEER_HOSTS}.0x)"
            )
            # The LAN actually carried the fan-out.
            peer_hits = sum(
                cluster.host(h).store.peer_snapshot()["peer_hits"]
                for h in range(PEER_HOSTS))
            assert peer_hits > 0
        finally:
            cluster.close()

    def test_without_peers_costs_n_x(self, peer_dataset, peer_backing):
        """Control arm: the same N-host read with every host routing all
        blocks to itself (single-member groups) pays ~Nx — the
        amplification the peer layer removes."""
        n_blocks = sum(-(-len(v) // PEER_BLOCKSIZE)
                       for v in peer_dataset.values())
        clusters = [SimCluster(1, peer_backing) for _ in range(PEER_HOSTS)]
        try:
            total = 0
            for c in clusters:
                outs, errors = _stream_all(c, [0])
                assert not errors, errors
                total += c.backing_fetches
            assert total >= PEER_HOSTS * n_blocks
        finally:
            for c in clusters:
                c.close()

    def test_host_death_mid_epoch_survivors_reown_blocks(self, peer_dataset,
                                                         peer_backing):
        """Host 3 dies halfway through the epoch. Survivors mark it dead
        on the first failed RPC (miss_limit=1), rendezvous re-owns its
        blocks across the remaining hosts, and every survivor finishes
        with byte-identical data and ZERO read errors."""
        want = b"".join(peer_dataset[k] for k in sorted(peer_dataset))
        half = len(want) // 2
        cluster = SimCluster(PEER_HOSTS, peer_backing, miss_limit=1)
        survivors = range(PEER_HOSTS - 1)
        outs: dict[int, bytes] = {}
        errors: list = []
        # Two barriers bracket the kill: every survivor finishes the
        # first half, host 3 dies, then the second half proceeds against
        # a silently-dead peer.
        reached_half = threading.Barrier(len(survivors) + 1)
        killed = threading.Barrier(len(survivors) + 1)

        def run(h):
            try:
                host = cluster.host(h)
                fs = host.open_fs(IOPolicy(
                    engine="sequential", blocksize=PEER_BLOCKSIZE,
                    keep_cached=True))
                files = sorted(host.store.list_objects(),
                               key=lambda m: m.key)
                f = fs.open_many(files)
                try:
                    first = f.read(half)
                    reached_half.wait(timeout=30)
                    killed.wait(timeout=30)
                    outs[h] = first + f.read()
                finally:
                    f.close()
            except BaseException as e:  # repro: allow[RP005] — stashed; asserted after join
                errors.append((h, e))

        threads = [threading.Thread(target=run, args=(h,))
                   for h in survivors]
        for t in threads:
            t.start()
        reached_half.wait(timeout=30)
        cluster.kill(PEER_HOSTS - 1)
        killed.wait(timeout=30)
        for t in threads:
            t.join()
        try:
            assert not errors, errors
            for h in survivors:
                assert outs[h] == want, f"survivor {h} bytes diverged"
            snaps = {h: cluster.host(h).store.peer_snapshot()
                     for h in survivors}
            # At least one survivor hit the dead host and degraded.
            assert sum(s["dead_peer_fallbacks"] for s in snaps.values()) > 0
            assert any(s["group"]["deaths"] > 0 for s in snaps.values())
            # Survivors converge on the dead peer's absence.
            for h in survivors:
                host = cluster.host(h)
                if not host.group.is_alive(PEER_HOSTS - 1):
                    assert PEER_HOSTS - 1 not in host.group.alive_ids()
        finally:
            cluster.close()
