"""Built-in reader engines.

  * ``rolling``    — the paper's Rolling Prefetch (three-thread engine over
    bounded cache tiers); requires tiers, which `PrefetchFS` supplies;
  * ``sequential`` — the S3Fs/fsspec-style on-demand block cache baseline;
  * ``direct``     — uncached pass-through range reads.

Each factory receives ``(store, files, tiers, policy)`` and returns a
`Reader`; the rolling and sequential engines take the `IOPolicy` whole and
read their own fields from it. New engines (real S3, async, sharded
multi-host) register the same way and become reachable from every
`PrefetchFS` call site.

The core engine modules are imported lazily inside the factories: they
depend on ``repro.io.retry`` (the unified resilience layer), and a
module-level import here would close an import cycle through the
``repro.io`` package init.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from repro.io.policy import IOPolicy
from repro.io.registry import register_reader
from repro.store.base import ObjectMeta, ObjectStore
from repro.store.tiers import CacheIndex, CacheTier

if TYPE_CHECKING:
    from repro.core.autotune import BlockSizeTuner


@register_reader("rolling", needs_tiers=True, accepts_tuner=True,
                 accepts_index=True)
def open_rolling(store: ObjectStore, files: list[ObjectMeta],
                 tiers: list[CacheTier], policy: IOPolicy,
                 tuner: "BlockSizeTuner | None" = None,
                 index: CacheIndex | None = None):
    from repro.core.rolling import RollingPrefetcher, RollingPrefetchFile

    return RollingPrefetchFile(RollingPrefetcher(store, files, tiers, policy,
                                                 tuner=tuner, index=index))


@register_reader("sequential", accepts_tuner=True, accepts_index=True)
def open_sequential(store: ObjectStore, files: list[ObjectMeta],
                    tiers: list[CacheTier], policy: IOPolicy,
                    tuner: "BlockSizeTuner | None" = None,
                    index: CacheIndex | None = None):
    from repro.core.sequential import SequentialFile

    return SequentialFile(store, files, policy, tuner=tuner, index=index)


@register_reader("direct")
def open_direct(store: ObjectStore, files: list[ObjectMeta],
                tiers: list[CacheTier], policy: IOPolicy):
    from repro.io.reader import DirectReader

    return DirectReader(store, files)
