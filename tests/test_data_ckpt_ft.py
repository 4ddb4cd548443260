"""Data pipeline, checkpointing, and fault-tolerance tests."""

from __future__ import annotations

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.ckpt.manager import RESTORE_POLICY, CheckpointManager, gc_checkpoints
from repro.data import (
    DataCursor,
    DeviceFeeder,
    LazyTrkReader,
    LoaderConfig,
    PrefetchingDataLoader,
    iter_streamlines_multi,
    synth_token_shard,
    synth_trk,
    write_trk,
)
from repro.core.rolling import RollingPrefetchFile, RollingPrefetcher
from repro.ft import RestartManager, run_with_restarts
from repro.io import IOPolicy, RetryPolicy
from repro.store import LinkModel, MemTier, SimS3Store


def make_store(objects: dict[str, bytes], **kw) -> SimS3Store:
    store = SimS3Store(link=LinkModel(**kw))
    for k, v in objects.items():
        store.backing.put(k, v)
    return store


# `LoaderConfig`'s default reader at 4 KiB blocks.
POLICY_4K = IOPolicy(blocksize=4096, eviction_interval_s=0.2)


# --------------------------------------------------------------------------- #
# .trk codec
# --------------------------------------------------------------------------- #
class TestTrk:
    def test_roundtrip(self):
        rng = np.random.default_rng(0)
        pts = [rng.normal(size=(5 + i, 3)).astype(np.float32) for i in range(4)]
        props = [rng.normal(size=2).astype(np.float32) for _ in range(4)]
        raw = write_trk(list(zip(pts, props)))
        reader = LazyTrkReader(io.BytesIO(raw))
        assert reader.header.n_count == 4
        got = list(reader.streamlines())
        assert len(got) == 4
        for sl, p, pr in zip(got, pts, props):
            np.testing.assert_allclose(sl.points, p, rtol=1e-6)  # identity affine
            np.testing.assert_allclose(sl.properties, pr)

    def test_affine_applied_on_read(self):
        affine = np.eye(4, dtype=np.float32)
        affine[:3, 3] = [1.0, 2.0, 3.0]
        pts = np.zeros((3, 3), np.float32)
        raw = write_trk([(pts, np.zeros(0, np.float32))], affine=affine,
                        n_properties=0)
        sl = next(LazyTrkReader(io.BytesIO(raw)).streamlines())
        np.testing.assert_allclose(sl.points, np.tile([1, 2, 3], (3, 1)))

    def test_multi_file_stream_via_rolling_prefetch(self):
        rng = np.random.default_rng(1)
        objects = {f"shard{i}.trk": synth_trk(rng, 20) for i in range(3)}
        store = make_store(objects)
        files = store.backing.list_objects()
        f = RollingPrefetchFile(
            RollingPrefetcher(
                store, files, [MemTier(1 << 20)],
                policy=IOPolicy(blocksize=4096, eviction_interval_s=0.01),
            )
        )
        with f:
            got = list(iter_streamlines_multi(f, f.size))
        assert len(got) == 60
        assert all(s.points.shape[1] == 3 for s in got)


# --------------------------------------------------------------------------- #
# Token shards + loader
# --------------------------------------------------------------------------- #
class TestTokenLoader:
    def _dataset(self, n_shards=4, tokens_per_shard=5000, **link_kw):
        rng = np.random.default_rng(2)
        objects = {
            f"tok{i:03d}.bin": synth_token_shard(rng, tokens_per_shard)
            for i in range(n_shards)
        }
        return make_store(objects, **link_kw)

    def test_rolling_and_sequential_yield_identical_batches(self):
        store = self._dataset()
        files = store.backing.list_objects()
        out = {}
        for mode in ("rolling", "sequential"):
            cfg = LoaderConfig(seq_len=128, batch_size=4, policy=IOPolicy(
                engine=mode, blocksize=4096, eviction_interval_s=0.2))
            loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)], cfg)
            batches = [b for b in loader.batches(max_batches=5)]
            loader.close()
            out[mode] = batches
        for (i1, l1), (i2, l2) in zip(out["rolling"], out["sequential"]):
            np.testing.assert_array_equal(i1, i2)
            np.testing.assert_array_equal(l1, l2)

    def test_labels_are_shifted_inputs(self):
        store = self._dataset(n_shards=1)
        files = store.backing.list_objects()
        cfg = LoaderConfig(seq_len=64, batch_size=2, policy=POLICY_4K)
        loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)], cfg)
        inputs, labels = next(iter(loader.batches(max_batches=1)))
        loader.close()
        np.testing.assert_array_equal(inputs[:, 1:], labels[:, :-1])

    def test_per_host_sharding_partitions_files(self):
        store = self._dataset(n_shards=4)
        files = store.backing.list_objects()
        seen = []
        for host in range(2):
            cfg = LoaderConfig(seq_len=32, batch_size=1, host_id=host,
                               num_hosts=2, policy=POLICY_4K)
            loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)], cfg)
            assert [m.key for m in loader.my_files] == [
                m.key for m in files[host::2]
            ]
            seen.append(next(iter(loader.batches(max_batches=1)))[0])
            loader.close()
        assert not np.array_equal(seen[0], seen[1])

    def test_cursor_resume_continues_stream(self):
        store = self._dataset(n_shards=2)
        files = store.backing.list_objects()

        def collect(cursor, n):
            cfg = LoaderConfig(seq_len=64, batch_size=2, policy=POLICY_4K)
            loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)],
                                           cfg, cursor=cursor)
            bs = [b for b in loader.batches(max_batches=n)]
            cur = DataCursor(**loader.cursor.to_dict())
            loader.close()
            return bs, cur

        all_batches, _ = collect(DataCursor(), 6)
        first3, cur = collect(DataCursor(), 3)
        resumed, _ = collect(cur, 3)
        for (a, _), (b, _) in zip(all_batches[3:], resumed):
            np.testing.assert_array_equal(a, b)

    def test_epoch_wraparound(self):
        store = self._dataset(n_shards=1, tokens_per_shard=200)
        files = store.backing.list_objects()
        cfg = LoaderConfig(seq_len=64, batch_size=2, policy=POLICY_4K)
        loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)], cfg)
        batches = [b for b in loader.batches(max_batches=4)]
        loader.close()
        assert len(batches) == 4
        assert loader.cursor.epoch >= 1

    def test_device_feeder(self):
        store = self._dataset(n_shards=1)
        files = store.backing.list_objects()
        cfg = LoaderConfig(seq_len=32, batch_size=2, policy=POLICY_4K)
        loader = PrefetchingDataLoader(store, files, [MemTier(1 << 20)], cfg)
        feeder = DeviceFeeder(loader.batches(max_batches=3), depth=2)
        out = list(feeder)
        loader.close()
        assert len(out) == 3
        assert all(isinstance(x[0], jax.Array) for x in out)
        # Exhausting the iterator reaps the feeder thread.
        assert not feeder._thread.is_alive()

    def test_device_feeder_close_mid_stream(self):
        # A consumer that stops early must be able to reap the feeder
        # even while it is parked on a full queue (regression: the
        # feeder thread used to be unjoinable — nothing stopped it).
        import itertools

        import numpy as np_

        batches = (np_.zeros((2, 8), np_.int32) for _ in itertools.count())
        feeder = DeviceFeeder(batches, depth=1)
        next(iter(feeder))
        feeder.close()
        assert not feeder._thread.is_alive()
        feeder.close()  # idempotent


# --------------------------------------------------------------------------- #
# Checkpointing
# --------------------------------------------------------------------------- #
def _state(key=0):
    k = jax.random.key(key)
    return {
        "params": {
            "w": jax.random.normal(k, (32, 64), jnp.float32),
            "b": jnp.zeros((64,), jnp.bfloat16),
        },
        "step": jnp.asarray(7, jnp.int32),
    }


class TestCheckpoint:
    @pytest.mark.parametrize("mode", ["rolling", "sequential"])
    def test_save_restore_roundtrip(self, mode):
        store = make_store({})
        state = _state()
        save_checkpoint(store, "ckpt", 10, state, extra={"cursor": {"epoch": 1}})
        restored, manifest = restore_checkpoint(
            store, "ckpt", jax.tree.map(lambda x: x, state),
            policy=RESTORE_POLICY.replace(engine=mode),
        )
        assert manifest["step"] == 10
        assert manifest["extra"]["cursor"]["epoch"] == 1
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype

    def test_latest_step_and_gc(self):
        store = make_store({})
        for s in (5, 10, 15, 20):
            save_checkpoint(store, "ckpt", s, _state())
        assert latest_step(store, "ckpt") == 20
        gc_checkpoints(store, "ckpt", keep_last=2)
        assert latest_step(store, "ckpt") == 20
        with pytest.raises(Exception):
            restore_checkpoint(store, "ckpt", _state(), step=5)

    def test_manifest_is_commit_point(self):
        """A save interrupted before the manifest is invisible."""
        store = make_store({})
        save_checkpoint(store, "ckpt", 10, _state())
        # Simulate partial save of step 20: leaves but no manifest.
        from repro.ckpt.manager import _leaf_key

        store.put(_leaf_key("ckpt", 20, 0), b"garbage")
        assert latest_step(store, "ckpt") == 10

    def test_async_manager(self):
        store = make_store({})
        mgr = CheckpointManager(store, "ckpt", interval_steps=2, keep_last=2)
        state = _state()
        saved = [mgr.maybe_save(s, state) for s in range(1, 7)]
        mgr.wait()
        assert saved == [False, True, False, True, False, True]
        assert latest_step(store, "ckpt") == 6

    @pytest.mark.parametrize("step", [3, None], ids=["given", "latest"])
    def test_restore_takes_leaf_sizes_from_manifest(self, step):
        """A restore asks the store for no object sizes, and the manifest's
        sizes are byte-exact for 0-d, empty, bfloat16 and int32 leaves."""

        class CountingStore(SimS3Store):
            size_calls = 0

            def size(self, key):
                self.size_calls += 1
                return super().size(key)

        store = CountingStore(link=LinkModel())
        state = {
            "scalar": jnp.asarray(2.5, jnp.float32),
            "empty": jnp.zeros((0, 4), jnp.float32),
            "half": jax.random.normal(jax.random.key(1), (5, 7), jnp.bfloat16),
            "ids": jnp.arange(-6, 15, dtype=jnp.int32).reshape(3, 7),
        }
        save_checkpoint(store, "ckpt", 3, state)
        restored, manifest = restore_checkpoint(
            store, "ckpt", state, step=step,
            policy=RESTORE_POLICY.replace(blocksize=16))
        assert store.size_calls == 0
        assert manifest["step"] == 3
        for name, leaf in state.items():
            got = np.asarray(restored[name])
            assert got.dtype == leaf.dtype and got.shape == leaf.shape, name
            assert got.tobytes() == np.asarray(leaf).tobytes(), name

    @pytest.mark.parametrize("engine", ["rolling", "sequential", "direct"])
    @pytest.mark.parametrize("verify", ["edges", "off"])
    def test_truncated_leaf_object_fails_restore(self, engine, verify):
        """A leaf object cut short in the store after the save fails the
        restore; it never comes back as a short or zero-padded leaf."""
        from repro.ckpt.manager import _leaf_key
        from repro.store.base import StoreError

        store = make_store({})
        state = _state()
        save_checkpoint(store, "ckpt", 2, state)
        # Leaves flatten by key: params/b, params/w, step; cut w mid-block.
        key = _leaf_key("ckpt", 2, 1)
        full = store.backing.get(key)
        store.backing.put(key, full[: len(full) - 100])
        policy = IOPolicy(engine=engine, verify=verify, blocksize=1024,
                          retry=RetryPolicy(backoff_s=0.001))
        with pytest.raises(StoreError):
            restore_checkpoint(store, "ckpt", state, policy=policy)

    def test_restore_with_abstract_template(self):
        """Templates may be ShapeDtypeStructs (the dry-run/elastic path)."""
        store = make_store({})
        state = _state()
        save_checkpoint(store, "ckpt", 1, state)
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        )
        restored, _ = restore_checkpoint(store, "ckpt", template)
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
        )


# --------------------------------------------------------------------------- #
# Fault tolerance: crash injection + restart
# --------------------------------------------------------------------------- #
class TestRestart:
    def test_crash_resume_matches_uninterrupted_run(self):
        """Training with injected crashes must land on the same final state
        as an uninterrupted run (determinism of plan + checkpoint/restore)."""
        rng = np.random.default_rng(3)
        objects = {f"tok{i}.bin": synth_token_shard(rng, 4000, vocab=100)
                   for i in range(2)}

        def build(crash_at):
            store = make_store(dict(objects))
            ckpt_store = make_store({})
            mgr = RestartManager(ckpt_store, "run", ckpt_interval=2)

            def make_initial_state():
                return {"w": jnp.zeros((8,), jnp.float32),
                        "count": jnp.asarray(0, jnp.int32)}

            def make_loader(cursor):
                cfg = LoaderConfig(seq_len=32, batch_size=2, policy=IOPolicy(
                    blocksize=2048, eviction_interval_s=0.2))
                return PrefetchingDataLoader(
                    store, store.backing.list_objects(),
                    [MemTier(1 << 20)], cfg, cursor=cursor,
                )

            @jax.jit
            def step_fn(state, inputs, labels):
                upd = jnp.bincount(
                    inputs.reshape(-1) % 8, length=8
                ).astype(jnp.float32)
                new = {"w": state["w"] + upd, "count": state["count"] + 1}
                return new, {"loss": jnp.sum(upd)}

            return run_with_restarts(
                total_steps=9,
                make_initial_state=make_initial_state,
                make_loader=make_loader,
                train_step=step_fn,
                restart_mgr=mgr,
                crash_at=crash_at,
            ), ckpt_store

        clean, _ = build(crash_at=None)
        crashed, ckpt_store = build(crash_at={4, 7})
        assert clean.restarts == 0
        assert crashed.restarts == 2
        assert crashed.final_step == clean.final_step == 9
        # Final checkpoint states identical.
        t = {"w": jnp.zeros((8,), jnp.float32), "count": jnp.asarray(0, jnp.int32)}
        s1, _ = restore_checkpoint(ckpt_store, "run", t)
        assert int(s1["count"]) == 9

    def test_store_failures_during_restore_are_retried(self):
        store = make_store({})
        save_checkpoint(store, "ckpt", 3, _state())
        store.link.fail_next(2)
        restored, _ = restore_checkpoint(store, "ckpt", _state())
        assert int(restored["step"]) == 7


# --------------------------------------------------------------------------- #
# Elastic resharding
# --------------------------------------------------------------------------- #
class TestElastic:
    def test_restore_onto_different_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        store = make_store({})
        state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        save_checkpoint(store, "ckpt", 1, state)
        from jax.sharding import AxisType

        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        template = {
            "w": jax.ShapeDtypeStruct(
                (8, 8), jnp.float32,
                sharding=NamedSharding(mesh, P("data", None)),
            )
        }
        restored, _ = restore_checkpoint(store, "ckpt", template)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert restored["w"].sharding.is_equivalent_to(
            template["w"].sharding, 2
        )
