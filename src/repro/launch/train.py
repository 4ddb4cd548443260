"""End-to-end training driver: object-store data -> Rolling Prefetch ->
device feed -> pjit train step -> async checkpoints -> crash-safe resume.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 100 --batch 8 --seq-len 256 --mode rolling

On this CPU container the default is the reduced config; pass --full to
train the assigned full architecture (mesh sharding engages when multiple
devices exist). Every substrate here is the production path — the same
loader, checkpoint manager, and restart logic the multi-pod job uses.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.ckpt.manager import CheckpointManager, latest_step, restore_checkpoint
from repro.configs import get_config
from repro.data import DataCursor, LoaderConfig, PrefetchingDataLoader, synth_token_shard
from repro.data.loader import DeviceFeeder
from repro.io import IOPolicy, open_store
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import mesh_host_shard
from repro.models import make_model
from repro.store import LinkModel, MemTier, SimS3Store
from repro.train import (
    AdamWConfig,
    StepConfig,
    TrainState,
    build_train_step,
    init_train_state,
)
from repro.utils import get_logger

log = get_logger("launch.train")


def build_data_store(n_shards: int, tokens_per_shard: int, vocab: int,
                     latency_s: float, bandwidth_Bps: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    store = SimS3Store(
        link=LinkModel(latency_s=latency_s, bandwidth_Bps=bandwidth_Bps)
    )
    for i in range(n_shards):
        store.backing.put(
            f"data/tok{i:04d}.bin", synth_token_shard(rng, tokens_per_shard, vocab)
        )
    return store


@dataclass
class TrainRun:
    """What one `main` call did, for callers that drive it in-process."""

    state: TrainState            # the live state after the last step
    start_step: int              # 0, or the step a checkpoint resumed from
    losses: dict[int, float]     # logged step -> loss
    restore_s: float | None      # checkpoint restore, None when not resumed
    first_step_s: float | None   # first step including its compile
    wall_s: float


def main(argv: list[str] | None = None) -> TrainRun:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--mode", default="rolling",
                    choices=["rolling", "sequential"])
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument("--blocksize", type=int, default=256 << 10)
    ap.add_argument("--ckpt-interval", type=int, default=20)
    ap.add_argument("--ckpt-store", default=None,
                    help="checkpoint store URI (mem://, local:///path, "
                         "sims3://bucket?latency_ms=...); default builds a "
                         "sims3:// URI from --s3-latency/--s3-bandwidth")
    ap.add_argument("--write-depth", type=int, default=2,
                    help="concurrent write-behind part uploads for saves")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--s3-latency", type=float, default=0.01)
    ap.add_argument("--s3-bandwidth", type=float, default=50e6)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = make_model(cfg)
    dev = jax.devices()[0]
    log.warning("arch=%s params=%.2fM devices=%d x %s (%s)", cfg.name,
                model.param_count() / 1e6, jax.device_count(),
                dev.device_kind, dev.platform)

    # --- data ---------------------------------------------------------------
    data_store = build_data_store(
        n_shards=8,
        tokens_per_shard=max(200_000, args.batch * (args.seq_len + 1) * 16),
        vocab=cfg.vocab_size,
        latency_s=args.s3_latency,
        bandwidth_Bps=args.s3_bandwidth,
    )
    # Checkpoints address their store by URI through the registry; any
    # registered backend works without touching this driver.
    ckpt_uri = args.ckpt_store or (
        f"sims3://ckpt?latency_ms={args.s3_latency * 1e3:g}"
        f"&bw_mbps={args.s3_bandwidth / 1e6:g}"
    )
    ckpt_store = open_store(ckpt_uri)
    write_policy = IOPolicy(write_depth=args.write_depth,
                            blocksize=args.blocksize)

    # --- resume or init ------------------------------------------------------
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 20))
    step_cfg = StepConfig(
        microbatches=args.microbatches,
        q_chunk=min(512, args.seq_len),
        loss_chunk=min(512, args.seq_len),
    )
    train_step = jax.jit(build_train_step(model, opt_cfg, step_cfg),
                         donate_argnums=(0,))

    state = init_train_state(model, jax.random.key(0))
    start_step, cursor, restore_s = 0, DataCursor(), None
    resume = latest_step(ckpt_store, "ckpt")
    if resume is not None:
        t0 = time.perf_counter()
        # Multi-process mesh: each host prefetch-warms only its
        # rendezvous-owned slice of the checkpoint stream (a peer://
        # ckpt store serves the rest over the LAN). Single process:
        # shard=None, the plain full restore.
        host_id, num_hosts = mesh_host_shard()
        state, manifest = restore_checkpoint(
            ckpt_store, "ckpt", state,
            shard=(host_id, num_hosts) if num_hosts > 1 else None,
        )
        jax.block_until_ready(state)
        restore_s = time.perf_counter() - t0
        start_step = manifest["step"]
        cursor = DataCursor.from_dict(manifest["extra"].get("cursor", cursor.to_dict()))
        log.warning("resumed from step %d", start_step)

    loader = PrefetchingDataLoader(
        data_store,
        data_store.list_objects("data/"),
        [MemTier(8 << 20)],
        LoaderConfig(
            seq_len=args.seq_len,
            batch_size=args.batch,
            policy=IOPolicy(
                engine=args.mode,
                blocksize=args.blocksize,
                depth=args.prefetch_depth,
                eviction_interval_s=0.2,
                autotune=True,
            ),
        ),
        cursor=cursor,
    )
    ckpt = CheckpointManager(ckpt_store, "ckpt",
                             interval_steps=args.ckpt_interval,
                             policy=write_policy)

    # --- loop ----------------------------------------------------------------
    feeder = DeviceFeeder(loader.batches(), depth=2)
    it = iter(feeder)
    t0 = time.time()
    tokens = 0
    losses: dict[int, float] = {}
    first_step_s = None
    for step in range(start_step, args.steps):
        inputs, labels = next(it)
        t_step = time.perf_counter()
        state, metrics = train_step(state, {"inputs": inputs, "labels": labels})
        if first_step_s is None:
            jax.block_until_ready(metrics["loss"])
            first_step_s = time.perf_counter() - t_step
        tokens += inputs.shape[0] * inputs.shape[1]
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            losses[step + 1] = float(metrics["loss"])
            dt = time.time() - t0
            print(
                f"step={step + 1} loss={losses[step + 1]:.4f} "
                f"grad_norm={float(metrics['grad_norm']):.3f} "
                f"lr={float(metrics['lr']):.2e} tok/s={tokens / dt:.0f}"
            )
        ckpt.maybe_save(step + 1, state,
                        extra={"cursor": loader.cursor.to_dict()})
    if args.steps > start_step and args.steps % args.ckpt_interval:
        # The final step, unless the loop has just saved it.
        ckpt.maybe_save(args.steps, state, force=True,
                        extra={"cursor": loader.cursor.to_dict()})
    ckpt.wait()
    feeder.close()
    loader.close()
    wall_s = time.time() - t0
    print("loader fs stats:", loader.fs_stats().snapshot())
    print(f"done: {args.steps} steps, {tokens} tokens, {wall_s:.1f}s wall")
    return TrainRun(state=state, start_step=start_step, losses=losses,
                    restore_s=restore_s, first_step_s=first_step_s,
                    wall_s=wall_s)


if __name__ == "__main__":
    main()
