"""Sharded checkpointing to the object store, with Rolling-Prefetch restore
and write-behind save.

Save: every state leaf serializes to one object under
``{prefix}/step_{N:08d}/``; the manifest is written LAST and is the atomic
commit point — a crash mid-save leaves no visible checkpoint (restart
resumes from the previous manifest). Leaf bytes flow through
``PrefetchFS.open_write``: serializing leaf k+1 overlaps with uploading
leaf k, and ``IOPolicy.write_depth`` part uploads run concurrently — the
paper's max(T_cloud, T_comp) pipeline pointed at the producer side
(checkpoint/upload stalls dominate cloud pipelines the same way cold
reads do; cf. arXiv:2108.06322). Closing every leaf writer before the
manifest writer preserves manifest-last commit exactly.

Stores may be passed as `ObjectStore` instances, `PrefetchFS` facades, or
registry URIs (``"sims3://ckpt?latency_ms=10"``) — see
``repro.io.open_store``.

Restore: the leaf objects form exactly the sequential multi-file stream
Rolling Prefetch was built for; they stream through the `PrefetchFS`
facade. The stream's layout comes from the manifest alone: each leaf's
size is its entry's ``prod(shape) * itemsize``, the bytes `save_checkpoint`
wrote, so a restore asks the store for no sizes. Its only metadata
requests are the manifest GET and, without a ``step``, the LIST.
The default policy, `RESTORE_POLICY`, runs the three-thread rolling
engine, so fetching leaf k+1..k+d from the store overlaps with
deserializing + `device_put`-ing leaf k — the paper's max(T_cloud, T_comp)
pipeline applied to checkpoint load. `ServeEngine.from_store` restores
with the same default.
`engine="sequential"` is the S3Fs-style baseline the benchmarks A/B
against.

Elastic: the restore template's shardings may come from a different mesh
than save time; `device_put` reshards each leaf onto the new topology.

Each restore is one `restore` span (`repro.utils.trace`) whose children
split it into the serial metadata requests (`restore.meta`: the LIST and
the manifest), the facade's set-up (`restore.open`), and per leaf the
stream's `read` of its bytes (`restore.read`), the digest check
(`restore.verify`) and the decode and `device_put` (`restore.put`), then
`restore.close`.
"""

from __future__ import annotations

import contextlib
import json
import math
import re
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.io import IOPolicy, PrefetchFS, open_store
from repro.io.integrity import block_digest, check_block
from repro.io.retry import Retrier, RetryPolicy
from repro.store.base import ObjectMeta, ObjectStore
from repro.store.tiers import CacheTier
from repro.utils import get_logger, trace

log = get_logger("ckpt")

MANIFEST = "MANIFEST.json"

# Metadata ops (list/get-manifest) retry through the shared
# resilience layer — full-jitter backoff, so a fleet of restarting
# workers hitting the same manifest does not re-collide in one backoff
# window. Bulk leaf reads retry inside the reader engines themselves.
META_RETRY = RetryPolicy(max_retries=4, backoff_s=0.02, backoff_cap_s=1.0)

# ONE long-lived executor for the default policy: the Retrier's state
# (seeded jitter rng, retry budget, telemetry) is designed to span calls
# — a fresh instance per metadata op would silently degrade a policy
# budget to a per-call cap.
_META_RETRIER = Retrier(META_RETRY)

# The restore's reader when the caller passes no policy: the rolling engine
# two 8 MiB blocks ahead of the consumer.
RESTORE_POLICY = IOPolicy(engine="rolling", blocksize=8 << 20, depth=2,
                          eviction_interval_s=0.2)


def _with_retries(fn, *, policy: RetryPolicy = META_RETRY):
    retrier = _META_RETRIER if policy is META_RETRY else Retrier(policy)
    return retrier.call(fn, label="checkpoint metadata")


def _step_prefix(prefix: str, step: int) -> str:
    return f"{prefix}/step_{step:08d}"


def _leaf_key(prefix: str, step: int, idx: int) -> str:
    return f"{_step_prefix(prefix, step)}/{idx:06d}.raw"


def _dtype_from_str(name: str) -> np.dtype:
    import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names with numpy

    return np.dtype(name)


def _flatten(state):
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return leaves, treedef


def save_checkpoint(
    store: ObjectStore | PrefetchFS | str,
    prefix: str,
    step: int,
    state,
    *,
    extra: dict | None = None,
    policy: IOPolicy | None = None,
) -> dict:
    """Blocking save; returns the manifest.

    Leaf objects stream through the write-behind pipeline
    (`PrefetchFS.open_write`): every leaf writer is opened and written in
    serialization order but closed only after the last leaf, so uploads
    overlap with serializing subsequent leaves. The manifest writer closes
    after every leaf writer — the commit point stays manifest-last and the
    stored bytes are identical to a synchronous per-leaf ``put``.
    ``policy`` carries the write knobs (``write_depth``, ``blocksize`` as
    part size, retries/hedging); `store` may be an `ObjectStore`, an
    already-open `PrefetchFS`, or a store URI.
    """
    leaves, _ = _flatten(state)
    host_leaves = jax.device_get(leaves)
    own_fs = not isinstance(store, PrefetchFS)
    fs = PrefetchFS(store, policy=policy) if own_fs else store
    entries = []
    writers = []
    try:
        for idx, leaf in enumerate(host_leaves):
            arr = np.asarray(leaf)
            key = _leaf_key(prefix, step, idx)
            # Raw little-endian bytes; manifest shape/dtype are
            # authoritative (np.save cannot represent bfloat16 and friends).
            raw = arr.tobytes()
            w = fs.open_write(key, policy=policy)
            w.write(raw)
            w.close_async()   # publish in the background, barrier below
            writers.append(w)
            # Per-leaf digest: restore verifies the streamed bytes against
            # the manifest, so a leaf corrupted anywhere between this
            # serialization and a later frombuffer fails loudly instead of
            # resuming training from silently wrong weights.
            entries.append(
                dict(key=key, shape=list(arr.shape), dtype=str(arr.dtype),
                     digest=block_digest(raw))
            )
        for w in writers:   # durability barrier: all leaves published
            w.join()
        manifest = dict(
            step=step,
            leaves=entries,
            extra=extra or {},
            format_version=1,
            saved_unix_time=time.time(),
        )
        with fs.open_write(f"{_step_prefix(prefix, step)}/{MANIFEST}",
                           policy=policy) as w:
            w.write(json.dumps(manifest).encode())
        return manifest
    except BaseException:
        # A failed save must stay invisible: drop in-flight leaf uploads;
        # without a manifest the step can never be restored.
        for w in writers:
            with contextlib.suppress(Exception):
                w.abort()
        raise
    finally:
        if own_fs:
            with contextlib.suppress(Exception):
                fs.close()


def latest_step(store: ObjectStore | str, prefix: str) -> int | None:
    """Largest step with a committed manifest."""
    store = open_store(store)
    best = None
    pat = re.compile(re.escape(prefix) + r"/step_(\d+)/" + re.escape(MANIFEST) + "$")
    for meta in _with_retries(lambda: store.list_objects(prefix)):
        m = pat.match(meta.key)
        if m:
            s = int(m.group(1))
            best = s if best is None or s > best else best
    return best


def _load_manifest(store: ObjectStore, prefix: str, step: int) -> dict:
    return json.loads(
        _with_retries(lambda: store.get(f"{_step_prefix(prefix, step)}/{MANIFEST}"))
    )


def _warm_shard(fs: PrefetchFS, files: list[ObjectMeta],
                policy: IOPolicy, shard: tuple[int, int]) -> None:
    """Pre-read this host's rendezvous-owned blocks of the restore stream
    into the (shared, keep_cached) cache. The warm reader uses the SAME
    blocksize as the main stream, so the published block ids are exactly
    the content-addressed ids sibling hosts' peer fetches arrive with."""
    from repro.core.plan import BlockPlan

    host_id, num_hosts = shard
    if num_hosts <= 1:
        return   # a 1-host "mesh" owns everything; the stream warms itself
    mine = BlockPlan(files, policy.blocksize).shard(host_id, num_hosts)
    if not mine:
        return
    warm = fs.open_many(files, engine="sequential", depth=1,
                        keep_cached=True)
    try:
        for b in mine:
            warm.seek(b.global_start)
            warm.read(b.size)
    finally:
        warm.close()
    log.info("restore shard %d/%d warmed %d blocks (%.1f MiB)",
             host_id, num_hosts, len(mine),
             sum(b.size for b in mine) / (1 << 20))


def restore_checkpoint(
    store: ObjectStore | str,
    prefix: str,
    template,
    *,
    step: int | None = None,
    policy: IOPolicy | None = None,
    tiers: list[CacheTier] | None = None,
    cache_dir: str | None = None,
    cache_capacity: int | None = None,
    shard: tuple[int, int] | None = None,
):
    """Restore into the structure (and shardings, if any) of `template`.
    Returns (state, manifest). `template` leaves may be arrays or
    ShapeDtypeStructs (with or without shardings).

    Leaf bytes stream through `PrefetchFS`; pass ``policy`` to select the
    reader engine and its knobs (default `RESTORE_POLICY`).

    ``cache_dir`` makes the restore crash-warm: leaf blocks cache in a
    persistent journaled `DirTier` under that directory and stay resident
    after the restore (``keep_cached``), so a restarted job — a replaced
    serve replica, a preempted trainer — restores the same step with zero
    store GETs for every block that survived on local disk. The journal's
    checksums discard torn blocks from a mid-write crash.
    ``cache_capacity`` bounds the directory (default: 4x blocksize or
    256 MiB, whichever is larger).

    ``shard=(host_id, num_hosts)`` makes the restore mesh-aware: before
    the full stream, the host warms ONLY its rendezvous-owned sub-plan
    (`BlockPlan.shard`) into the cache — the exact blocks its siblings'
    peer layers will route to it — and ``keep_cached`` is forced so the
    warmed blocks stay servable. Over a ``peer://`` store, every host
    then restores the full state while the backing store is read ~once
    in aggregate: each block's WAN fetch happens on its one home host,
    everything else moves over the LAN. Without a peer store the shard
    warm pass is still correct, just not shared.
    """
    with trace.span("restore"):
        trace.count("restore.count")
        store = open_store(store)
        if policy is None:
            policy = RESTORE_POLICY
        warm_cache = cache_dir is not None and tiers is None
        if warm_cache:
            from repro.store.tiers import DirTier

            cap = cache_capacity
            if cap is None:
                cap = max(4 * policy.blocksize, 256 << 20)
            tiers = [DirTier(cap, root=cache_dir, name="ckpt.cache")]
        # Everything past tier construction runs under the finally that
        # releases the cache root's advisory lock — a missing manifest or a
        # failed metadata call must not leak the lock in a long-lived process
        # (the retry's DirTier would silently become a non-owner).
        try:
            if policy.io_class == "default":
                # Restore streams are the checkpoint workload class (top-tier
                # HSM admission); an explicit io_class — e.g. "serve" from
                # `ServeEngine.from_store` — wins.
                policy = policy.replace(io_class="ckpt")
            if (warm_cache or shard is not None) and not policy.keep_cached:
                # Sharded restore serves warmed blocks to siblings: they must
                # outlive their own consumption.
                policy = policy.replace(keep_cached=True)
            with trace.span("restore.meta"):
                manifest, files = _restore_meta(store, prefix, step)
            t_leaves, treedef = _flatten(template)
            entries = manifest["leaves"]
            if len(entries) != len(t_leaves):
                raise ValueError(
                    f"template has {len(t_leaves)} leaves, checkpoint {len(entries)}"
                )
            fs = None
            try:
                with trace.span("restore.open"):
                    fs = PrefetchFS(store, policy=policy, tiers=tiers)
                    if shard is not None:
                        with trace.span("restore.warm"):
                            _warm_shard(fs, files, policy, shard)
                    stream = fs.open_many(files)
                out = _read_leaves(stream, files, entries, t_leaves, policy)
                totals = fs.stats().totals
                for name in ("store_requests", "bytes_fetched", "reader_wait_s"):
                    trace.count(f"restore.{name}", totals.get(name, 0))
            finally:
                if fs is not None:
                    with trace.span("restore.close"):
                        fs.close()
        finally:
            if warm_cache:
                # Release the lock; blocks stay on disk for the next —
                # possibly warm — restore.
                for t in tiers:
                    with contextlib.suppress(Exception):
                        t.close()
        return jax.tree_util.tree_unflatten(treedef, out), manifest


def _restore_meta(store: ObjectStore, prefix: str,
                  step: int | None) -> tuple[dict, list[ObjectMeta]]:
    """The restore's serial metadata requests: the LIST that finds the latest
    step (when `step` is None) and the manifest GET. Each leaf's size comes
    from its manifest entry, with no request: `save_checkpoint` writes a leaf
    as its raw bytes, `prod(shape) * itemsize` of them. A leaf object shorter
    than that fails as a truncated read; one with other bytes fails its
    digest unless `verify="off"`."""
    if step is None:
        trace.count("restore.meta_requests")
        step = latest_step(store, prefix)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {prefix!r}")
    trace.count("restore.meta_requests")
    manifest = _load_manifest(store, prefix, step)
    files = [
        ObjectMeta(e["key"], math.prod(e["shape"]) * _dtype_from_str(e["dtype"]).itemsize)
        for e in manifest["leaves"]
    ]
    return manifest, files


def _read_leaves(stream, files: list[ObjectMeta], entries: list[dict],
                 t_leaves: list, policy: IOPolicy) -> list:
    """Each leaf's bytes from the stream, verified against the manifest's
    digest and put on the device; `device_put` of leaf k overlaps with the
    prefetch of the leaves after it."""
    read = getattr(stream, "readview", stream.read)
    out = []
    for meta, entry, tmpl in zip(files, entries, t_leaves):
        # readview: a leaf inside one cached block decodes zero-copy
        # (np.frombuffer over the block buffer's memoryview).
        with trace.span("restore.read"):
            raw = read(meta.size)
        if policy.verify != "off":
            # End-to-end: the digest minted over the serialized leaf at save
            # time must match the bytes about to become model state —
            # whatever path they took (store, cache tiers, peers). Manifests
            # predating digests verify nothing (entry.get -> None).
            with trace.span("restore.verify"):
                check_block(raw, entry.get("digest"),
                            what=f"checkpoint leaf {entry['key']}")
        with trace.span("restore.put"):
            arr = np.frombuffer(
                raw, dtype=_dtype_from_str(entry["dtype"])
            ).reshape(entry["shape"])
            out.append(jax.device_put(arr, getattr(tmpl, "sharding", None)))
    return out


# Spans whose self time is the restore's host work, not waiting.
RESTORE_HOST_SPANS = ("restore.open", "restore.verify", "restore.put", "restore.close")


def restore_breakdown(rec: trace.Recording) -> dict[str, float]:
    """Per restore kept in `rec`: seconds of serial metadata requests
    (`meta_s`), in the stream's `read` for leaf bytes (`wait_s`) and of
    host work (`host_s`), and the store requests made (`requests`)."""
    c = rec.counters
    n = c["restore.count"]
    return {
        "meta_s": rec.total("restore.meta") / n,
        "wait_s": rec.total("restore.read") / n,
        "host_s": sum(rec.self_s(name) for name in RESTORE_HOST_SPANS) / n,
        "requests": (c["restore.meta_requests"] + c["restore.store_requests"]) / n,
    }


def gc_checkpoints(store: ObjectStore | str, prefix: str,
                   keep_last: int = 3) -> int:
    """Delete all but the newest `keep_last` committed checkpoints."""
    store = open_store(store)
    steps = sorted(
        {
            int(m.group(1))
            for meta in store.list_objects(prefix)
            if (m := re.match(re.escape(prefix) + r"/step_(\d+)/", meta.key))
        }
    )
    deleted = 0
    for s in steps[:-keep_last] if keep_last else steps:
        for meta in store.list_objects(_step_prefix(prefix, s)):
            store.delete(meta.key)
            deleted += 1
    return deleted


@dataclass
class CheckpointManager:
    """Periodic async checkpointing for the train loop. `store` may be an
    `ObjectStore` or a registry URI; `policy` forwards write-behind knobs
    to `save_checkpoint`."""

    store: ObjectStore | str
    prefix: str
    interval_steps: int = 100
    keep_last: int = 3
    policy: IOPolicy | None = None

    def __post_init__(self) -> None:
        self.store = open_store(self.store)
        self._thread: threading.Thread | None = None
        self._err: list[BaseException] = []

    def maybe_save(self, step: int, state, *, extra: dict | None = None,
                   force: bool = False) -> bool:
        if not force and (step == 0 or step % self.interval_steps != 0):
            return False
        self.wait()
        # Snapshot synchronously (cheap device_get), upload in background —
        # training continues while bytes stream to the store.
        leaves, treedef = _flatten(state)
        host = jax.device_get(leaves)
        snapshot = jax.tree_util.tree_unflatten(treedef, host)

        def upload() -> None:
            try:
                save_checkpoint(self.store, self.prefix, step, snapshot,
                                extra=extra, policy=self.policy)
                gc_checkpoints(self.store, self.prefix, self.keep_last)
            except BaseException as e:  # repro: allow[RP005] — stashed; wait() re-raises
                self._err.append(e)

        self._thread = threading.Thread(target=upload, daemon=True)
        self._thread.start()
        return True

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._err:
            raise self._err[0]
