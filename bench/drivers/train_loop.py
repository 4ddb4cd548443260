"""Training fed from the store, for a dense or a hybrid model, with or without
checkpoint saves: the loop of `drivers/train.py` (`launch/train.py`'s
components in the same order, one step in flight, the same spans).

The configuration file names its model's benchmark modules: with
`"bench_modules": "hybrid"` the model, weights and FLOPs come from
`bench/hybrid.py` and the reference from `bench/reference_hybrid.py`;
without it, from the dense `program.py`, `weights.py`, `flops.py` and
`reference.py`.

A workload file that sets `save_every` saves the whole train state (params
and AdamW moments) with `CheckpointManager.maybe_save` every `save_every`
steps counted from the window's first step, into the same store the data
comes from, inside a harness span `save` that opens once the saving step
has finished on the device. At each saving step per-leaf
checksums of the state are taken on the device, with no host copy. After
the window the last committed save is restored from the store and its
leaves compared with those checksums: `saved_leaves_differing`, limit 0.
"""

from __future__ import annotations

import math
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Cell, Harness, Outcome, load_module, memory_peak_bytes
from bench.store import store_at

train = load_module(Path(__file__).with_name("train.py"))
CKPT_PREFIX = "ckpt"


def modules(cfg: dict) -> SimpleNamespace:
    """The configuration's model, weights, FLOPs and reference modules."""
    if cfg.get("bench_modules", "dense") == "hybrid":
        from bench import hybrid, reference_hybrid

        return SimpleNamespace(program=hybrid, weights=hybrid, flops=hybrid,
                               reference=reference_hybrid)
    from bench import flops, program, reference, weights

    return SimpleNamespace(program=program, weights=weights, flops=flops, reference=reference)


def reference_numbers(cell: Cell, stream: np.ndarray, precision: str = "float32",
                      rows: int | None = None, make_w=None, cfg: dict | None = None) -> dict:
    """The reference's numbers for the checked steps. A planted fault keeps
    only the first `rows` of each batch, starts from other weights
    (`make_w`) or computes another configuration (`cfg`)."""
    wl = cell.workload
    mods = modules(cell.config)
    batches = []
    for i in range(wl["checked_steps"]):
        ids, labels = train.expected_batch(stream, i, wl["batch"], wl["seq_len"])
        batches.append((jnp.asarray(ids[:rows]), jnp.asarray(labels[:rows])))
    make_w = make_w or (lambda: mods.weights.make_jitted(cell.config, cell.seed))
    return mods.reference.train(make_w, cfg or cell.config, wl["optimizer"], batches, precision)


@jax.jit
def checksums(tree) -> list:
    """Two 32-bit sums of each leaf's bits, plain and position-weighted."""
    out = []
    for x in jax.tree.leaves(tree):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
        i = jnp.arange(u.size, dtype=jnp.uint32)
        out.append(jnp.stack([jnp.sum(u), jnp.sum(u * (i * jnp.uint32(2654435761) + 1))]))
    return out


def saved_leaves_differing(store, template, want: dict) -> float:
    """Leaves of the last committed save whose restored bits differ from
    the checksums taken at its step (NaN when nothing was committed)."""
    from repro.ckpt.manager import latest_step, restore_checkpoint

    step = latest_step(store.backing, CKPT_PREFIX)
    if step is None or step not in want:
        return math.nan
    restored, _ = restore_checkpoint(store.backing, CKPT_PREFIX, template, step=step)
    got = checksums(restored)
    return float(sum(bool(np.any(np.asarray(a) != np.asarray(b)))
                     for a, b in zip(got, want[step])))


def run(cell: Cell, seconds: float, h: Harness) -> Outcome:
    from repro.ckpt.manager import CheckpointManager
    from repro.data import LoaderConfig, PrefetchingDataLoader, write_token_shard
    from repro.data.loader import DeviceFeeder
    from repro.io import IOPolicy
    from repro.store import MemTier
    from repro.train import AdamWConfig, StepConfig, TrainState, build_train_step
    from repro.train.optimizer import AdamWState

    cfg, wl = cell.config, cell.workload
    mods = modules(cfg)
    batch, seq_len = wl["batch"], wl["seq_len"]
    model = mods.program.model(cfg)
    save_every = wl.get("save_every")

    store = store_at(wl["link"])
    stream = train.token_shards(cell)
    n = wl["tokens_per_shard"]
    for i in range(wl["shards"]):
        store.backing.put(f"data/tok{i:04d}.bin", write_token_shard(stream[i * n:(i + 1) * n]))

    def init(key):
        params = mods.weights.make(cfg, key)
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
        return TrainState(params, AdamWState(jnp.zeros((), jnp.int32), zeros(), zeros()))

    key = mods.weights.seed_key(cell.seed)
    state = jax.jit(init)(key)
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    opt = wl["optimizer"]
    step = jax.jit(build_train_step(model, AdamWConfig(**opt), StepConfig(**wl["step"])),
                   donate_argnums=(0,))
    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(tree)])
    change = jax.jit(lambda p, k: [jnp.sqrt(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(p), jax.tree.leaves(mods.weights.make(cfg, k)))])
    manager = (CheckpointManager(store, CKPT_PREFIX, interval_steps=save_every)
               if save_every else None)

    lw = wl["loader"]
    loader = PrefetchingDataLoader(
        store, store.list_objects("data/"), [MemTier(lw["cache_bytes"])],
        LoaderConfig(seq_len=seq_len, batch_size=batch, policy=IOPolicy(
            engine=lw["engine"], blocksize=lw["blocksize"], depth=lw["depth"],
            eviction_interval_s=lw["eviction_interval_s"], autotune=lw["autotune"])))
    feeder = DeviceFeeder(loader.batches(), depth=wl["feeder_depth"])
    it = iter(feeder)
    fed, saved = [], {}
    try:
        # Set-up: the compiled step's first steps, which the reference follows.
        losses, grad_norms = [], None
        for i in range(wl["checked_steps"]):
            inputs, labels = next(it)
            fed.append((inputs, labels))
            state, metrics = step(state, {"inputs": inputs, "labels": labels})
            losses.append(metrics["loss"])
            if i == 0:
                grad_norms = norms(state.opt.m)
        changes = change(state.params, key)
        prog = {"losses": [float(x) for x in losses],
                "grad_norms": [float(x) / (1 - opt["b1"]) for x in grad_norms],
                "change_norms": [float(x) for x in changes]}
        if manager is not None:
            jax.block_until_ready(checksums(state))
        h.setup_done()

        window_losses = []
        with h.window():
            t0 = time.perf_counter()
            pending = None
            while True:
                with h.span("loader_wait"):
                    inputs, labels = next(it)
                fed.append((inputs, labels))
                with h.span("step_dispatch"):
                    state, metrics = step(state, {"inputs": inputs, "labels": labels})
                k = len(window_losses) + 1
                if manager is not None and k % save_every == 0:
                    saved[k] = checksums(state)
                    # The steps finish outside `save`, which then holds only
                    # the wait for the previous upload and the host copy.
                    with h.span("step_wait"):
                        jax.block_until_ready((state, saved[k]))
                    with h.span("save"):
                        manager.maybe_save(k, state)
                if pending is not None:
                    with h.span("step_wait"):
                        pending.block_until_ready()
                pending = metrics["loss"]
                window_losses.append(pending)
                if time.perf_counter() - t0 >= seconds:
                    break
            with h.span("step_wait"):
                pending.block_until_ready()
            window_s = time.perf_counter() - t0
        peak = memory_peak_bytes()
    finally:
        feeder.close()
        loader.close()
        if manager is not None:
            manager.wait()
    del state, metrics, pending

    steps = len(window_losses)
    tokens_per_s = steps * batch * seq_len / window_s
    wrong_rows = 0
    for i, (inputs, labels) in enumerate(fed):
        want_in, want_lab = train.expected_batch(stream, i, batch, seq_len)
        wrong_rows += int(np.sum(np.any((np.asarray(inputs) != want_in)
                                        | (np.asarray(labels) != want_lab), axis=1)))
    del fed
    found = {}
    if manager is not None:
        found["saved_leaves_differing"] = saved_leaves_differing(store, template, saved)
    del saved
    found.update(train.gaps(prog, reference_numbers(cell, stream)))
    found["data_rows_wrong"] = float(wrong_rows)
    limits = wl["limits"]
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens_per_s},
        attempted=steps,
        failed=sum(not math.isfinite(float(x)) for x in window_losses),
        checks={k: (v, float(limits[k])) for k, v in found.items()},
        memory_peak_bytes=peak,
        facts={"tokens_per_s": tokens_per_s,
               "flops_per_token": mods.flops.train_flops_per_token(cfg, seq_len)},
    )


def faults(cell: Cell) -> dict:
    """Faults planted in the reference put in the program's place, as
    `reference_numbers` arguments: half of each batch for a dense model; for
    a hybrid, the D skip left out of its first Mamba-2 layer, and attention
    scaled by 1/sqrt(head size) in place of its multiplier."""
    cfg = cell.config
    if cfg.get("bench_modules", "dense") != "hybrid":
        return {"half_batch": {"rows": cell.workload["batch"] // 2}}
    mods = modules(cfg)
    block = f"block{cfg['layer_types'].index('mamba')}"

    def no_d_skip():
        w = mods.weights.make_jitted(cfg, cell.seed)
        mamba = w["layers"][block]["mamba"]
        mamba["d_skip"] = mamba["d_skip"].at[0].set(0.0)
        return w

    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"no_d_skip": {"make_w": no_d_skip},
            "attention_scale": {"cfg": {**cfg, "attention_multiplier": head ** -0.5}}}


def readings(cell: Cell) -> dict:
    """The control's and each planted fault's compared numbers for one
    seed, each against the float32 reference as a run compares the program."""
    stream = train.token_shards(cell)
    ref = reference_numbers(cell, stream)
    found = {"reference": ref,
             "control": train.gaps(reference_numbers(cell, stream, "fp8"), ref)}
    for name, planted in faults(cell).items():
        found[name] = train.gaps(reference_numbers(cell, stream, **planted), ref)
    return found
