"""Smoke run of the system's main path on a TPU, through the entry points
a user calls, at smollm-135m full width with random weights from a seed.

  python chip_smoke.py             # one chip: train, restore, resume, serve
  python chip_smoke.py --chips 4   # four chips: the elastic reshard path only

One chip, four phases, all in this one process:

  train    `repro.launch.train.main` from token shards on a simulated S3
           link (rolling engine), write-behind checkpoints every 10 steps;
  restore  `restore_checkpoint` of the last checkpoint, bitwise against the
           live final state;
  resume   `train.main` again on the same checkpoint store, 10 more steps;
  serve    `repro.launch.serve.main`: cold-start weight restore from the
           store, prefill, decode.

Four chips: a full-width train state saved sharded on a (data 4 x model 1)
mesh is restored onto (data 2 x model 2) by `restore_resharded`, compared
leaf by leaf, and every leaf the new layout shards must sit on several
devices.

Each phase prints one JSON line of its numbers. The last line is
``{"ok": true, "device": {...}}``. Without a TPU the script exits non-zero
and prints no result; a failed check raises.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
# Simulated object-store link for data, checkpoints and weights: fast
# enough that a 1.6 GB train state moves in a few seconds a transfer.
LINK_LATENCY_MS = 10
LINK_BW_MBPS = 400
CKPT_INTERVAL = 10
TRAIN_STEPS = 20
RESUME_STEPS = 10

FULL_TRAIN = ["--full", "--batch", "8", "--seq-len", "2048"]
FULL_SERVE = ["--full", "--batch", "8", "--prompt-len", "512", "--gen", "32"]


def emit(phase: str, **numbers) -> None:
    print(json.dumps({"phase": phase, **numbers}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _link_uri(bucket: str) -> str:
    return f"sims3://{bucket}?latency_ms={LINK_LATENCY_MS}&bw_mbps={LINK_BW_MBPS}"


def _train_argv(sizes: list[str], ckpt_uri: str, steps: int) -> list[str]:
    return [
        "--arch", ARCH, *sizes, "--steps", str(steps), "--mode", "rolling",
        "--ckpt-interval", str(CKPT_INTERVAL), "--log-every", "5",
        "--ckpt-store", ckpt_uri, "--write-depth", "8",
        "--s3-latency", str(LINK_LATENCY_MS / 1e3),
        "--s3-bandwidth", str(LINK_BW_MBPS * 1e6),
    ]


def _check_losses(run, phase: str) -> None:
    check(bool(run.losses), f"{phase}: no loss was logged")
    bad = {s: v for s, v in run.losses.items() if not math.isfinite(v)}
    check(not bad, f"{phase}: non-finite losses {bad}")


def _same_bytes(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def run_one_chip(train_sizes: list[str], serve_sizes: list[str]) -> None:
    """Phases train, restore, resume and serve at the given sizes."""
    import jax
    import numpy as np

    from repro.ckpt import restore_checkpoint
    from repro.configs import get_config
    from repro.launch import serve, train

    emit("link", latency_ms=LINK_LATENCY_MS, bw_mbps=LINK_BW_MBPS)
    ckpt_uri = _link_uri("smoke-ckpt")

    run = train.main(_train_argv(train_sizes, ckpt_uri, TRAIN_STEPS))
    check(run.start_step == 0, f"train: started at step {run.start_step}")
    _check_losses(run, "train")
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(run.state))
    emit("train", steps=TRAIN_STEPS, losses=run.losses,
         first_step_s=run.first_step_s, wall_s=run.wall_s,
         state_bytes=state_bytes)

    t0 = time.perf_counter()
    restored, manifest = restore_checkpoint(ckpt_uri, "ckpt", run.state)
    jax.block_until_ready(restored)
    restore_s = time.perf_counter() - t0
    live, back = jax.tree.leaves(run.state), jax.tree.leaves(restored)
    check(manifest["step"] == TRAIN_STEPS,
          f"restore: newest checkpoint is step {manifest['step']}")
    check(len(live) == len(back), "restore: leaf count differs")
    differ = [i for i, (a, b) in enumerate(zip(live, back)) if not _same_bytes(a, b)]
    check(not differ, f"restore: leaves {differ[:8]} differ from the live state")
    emit("restore", step=manifest["step"], leaves=len(live), bitwise_equal=True,
         restore_s=restore_s)
    del run, restored, live, back

    resumed = train.main(
        _train_argv(train_sizes, ckpt_uri, TRAIN_STEPS + RESUME_STEPS))
    check(resumed.start_step == TRAIN_STEPS,
          f"resume: resumed from step {resumed.start_step}")
    _check_losses(resumed, "resume")
    emit("resume", resumed_from=resumed.start_step,
         steps=TRAIN_STEPS + RESUME_STEPS, losses=resumed.losses,
         restore_s=resumed.restore_s, first_step_s=resumed.first_step_s,
         wall_s=resumed.wall_s)
    del resumed

    out = serve.main(["--arch", ARCH, *serve_sizes,
                      "--store", _link_uri("smoke-weights")])
    vocab = get_config(ARCH).vocab_size
    check(bool(((out.ids >= 0) & (out.ids < vocab)).all()),
          "serve: decoded ids outside the vocabulary")
    check(bool(np.isfinite(out.logits).all()), "serve: non-finite logits")
    emit("serve", batch=int(out.ids.shape[0]), gen=int(out.ids.shape[1]),
         restore_s=out.restore_s, prefill_s=out.prefill_s,
         decode_s=out.decode_s, sample_ids=out.ids[0, :8].tolist())


def run_elastic(reduced: bool = False) -> None:
    """Save a train state sharded on (data 4 x model 1), restore it onto
    (data 2 x model 2), and check values and layout. Needs four devices."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.ckpt import save_checkpoint
    from repro.configs import get_config
    from repro.ft import reshard_tree
    from repro.ft.elastic import restore_resharded
    from repro.io import IOPolicy
    from repro.launch.mesh import mesh_host_shard
    from repro.models import make_model
    from repro.models.spec import param_shardings
    from repro.sharding.rules import TRAIN_RULES, ShardingRules
    from repro.train import TrainState, init_train_state
    from repro.train.optimizer import AdamWState

    check(len(jax.devices()) >= 4, f"elastic: needs 4 devices, found {len(jax.devices())}")
    cfg = get_config(ARCH)
    model = make_model(cfg.reduced() if reduced else cfg)

    def layout(data: int, model_axis: int):
        mesh = jax.make_mesh((data, model_axis), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        p = param_shardings(model.spec(), ShardingRules(mesh, dict(TRAIN_RULES)))
        return TrainState(params=p, opt=AdamWState(
            step=NamedSharding(mesh, P()), m=p, v=p))

    uri = _link_uri("smoke-elastic")
    saved = reshard_tree(init_train_state(model, jax.random.key(0)), layout(4, 1))
    t0 = time.perf_counter()
    save_checkpoint(uri, "elastic", 0, saved, policy=IOPolicy(write_depth=4))
    save_s = time.perf_counter() - t0

    target = layout(2, 2)
    template = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        saved, target)
    host_id, num_hosts = mesh_host_shard()
    t0 = time.perf_counter()
    restored, _ = restore_resharded(
        uri, "elastic", template, host_id=host_id, num_hosts=num_hosts)
    jax.block_until_ready(restored)
    restore_s = time.perf_counter() - t0

    leaves = zip(jax.tree.leaves(saved), jax.tree.leaves(restored),
                 jax.tree.leaves(target))
    n_sharded = n_leaves = 0
    for i, (a, b, s) in enumerate(leaves):
        n_leaves += 1
        check(_same_bytes(a, b), f"elastic: leaf {i} differs after the reshard")
        check(b.sharding.is_equivalent_to(s, b.ndim),
              f"elastic: leaf {i} has sharding {b.sharding}, wanted {s}")
        if any(axis is not None for axis in s.spec):
            n_sharded += 1
            devices = {shard.device for shard in b.addressable_shards}
            check(len(devices) > 1 and b.addressable_shards[0].data.shape != b.shape,
                  f"elastic: leaf {i} ({s.spec}) is not split across devices")
    check(n_sharded > 0, "elastic: the target layout shards no leaf")
    emit("elastic", saved_on="data4 x model1", restored_on="data2 x model2",
         leaves=n_leaves, sharded_leaves=n_sharded, bitwise_equal=True,
         save_s=save_s, restore_s=restore_s)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4 runs only the elastic reshard path across four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if args.chips == 4:
        run_elastic()
    else:
        run_one_chip(FULL_TRAIN, FULL_SERVE)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
