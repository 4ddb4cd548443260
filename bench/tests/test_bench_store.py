"""The benchmark's emulated S3 charges what the program's simulator charges:
the same requests, bytes and link floor for one tiny restore and for one
loader epoch."""

from unittest import mock

import numpy as np
import pytest

from bench.store import LinkedStore

LATENCY, BANDWIDTH = 0.002, 200e6


def program_store():
    from repro.store import LinkModel, SimS3Store

    return SimS3Store(link=LinkModel(latency_s=LATENCY, bandwidth_Bps=BANDWIDTH))


def counts(store):
    if isinstance(store, LinkedStore):
        c = store.link.counters()
        return c["requests"], c["bytes"]
    return store.link.requests, store.link.bytes_moved


def floor_s(nbytes):
    return LATENCY + nbytes / BANDWIDTH


def restore(store):
    import jax
    import jax.numpy as jnp

    from repro.ckpt import restore_checkpoint, save_checkpoint
    from repro.io import IOPolicy

    state = {"a": jnp.arange(70_000, dtype=jnp.float32), "b": jnp.ones((300, 7)),
             "c": {"d": jnp.full((5,), 3, jnp.int32)}}
    # The manifest carries the save time: hold it fixed so both stores hold
    # objects of the same sizes.
    with mock.patch("repro.ckpt.manager.time.time", return_value=1.5e9):
        save_checkpoint(store.backing, "ckpt", 0, state, policy=IOPolicy(write_depth=2))
    before = counts(store)
    back, _ = restore_checkpoint(store, "ckpt", state, policy=IOPolicy(
        engine="rolling", blocksize=64 << 10, depth=2, eviction_interval_s=0.2))
    for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    after = counts(store)
    return after[0] - before[0], after[1] - before[1]


def epoch(store):
    from repro.data import LoaderConfig, PrefetchingDataLoader, write_token_shard
    from repro.io import IOPolicy
    from repro.store import MemTier

    rng = np.random.default_rng(0)
    for i in range(3):
        store.backing.put(f"data/tok{i:04d}.bin",
                          write_token_shard(rng.integers(0, 1000, 3000, dtype=np.uint32)))
    files = store.backing.list_objects("data/")
    loader = PrefetchingDataLoader(store, files, [MemTier(1 << 16)], LoaderConfig(
        seq_len=63, batch_size=2, policy=IOPolicy(
            engine="rolling", blocksize=4096, depth=2, eviction_interval_s=0.2)))
    try:
        n = sum(1 for _ in loader.batches(max_batches=9000 // 128))
    finally:
        loader.close()
    assert n == 9000 // 128
    return counts(store)


@pytest.mark.parametrize("case", [restore, epoch], ids=["restore", "loader_epoch"])
def test_same_charges_as_program_simulator(case):
    ours, theirs = case(LinkedStore(LATENCY, BANDWIDTH)), case(program_store())
    assert ours == theirs
    assert ours[0] > 1 and ours[1] > 0
    assert floor_s(ours[1]) == floor_s(theirs[1])


def test_link_floor_and_reservation_queue():
    import threading
    import time

    store = LinkedStore(0.01, 1e6)
    store.backing.put("k", b"x" * 20_000)
    t0 = time.perf_counter()
    threads = [threading.Thread(target=store.get, args=("k",)) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    took = time.perf_counter() - t0
    assert not any(t.is_alive() for t in threads)
    # Latency overlaps across threads; bytes queue on one link: 0.01 + 0.06 s.
    assert store.link.floor_s(60_000) == pytest.approx(0.07)
    assert took >= 0.07 - 1e-3
    assert store.link.counters() == {"requests": 3, "bytes": 60_000}
