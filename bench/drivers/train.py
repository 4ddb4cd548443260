"""Training fed from the store: the loop of `launch/train.py`, driven step by
step with the same components in the same order.

Token shards in the emulated store -> `PrefetchingDataLoader` -> `DeviceFeeder`
-> the jitted, donating train step. Set-up makes the state on the device from
the seed, writes the shards into the store and drives the compiled step
through its first `checked_steps` steps, which the reference follows. The
window continues the same loop. Each step's completion is taken by blocking
on the previous step's loss after dispatching the next, so one step stays in
flight; the window ends when the last dispatched step has finished.
"""

from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, program, reference, weights
from bench.harness import Cell, Harness, Outcome, memory_peak_bytes
from bench.store import store_at


# A leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam: its change is not compared.
STILL_LEAF = 1e-3


def worst_leaf_gap(got: list[float], want: list[float],
                   compared: list[bool] | None = None) -> float:
    """The largest gap between a leaf's norm in the program (`got`) and in
    the reference (`want`), over the larger of that leaf's reference norm
    and the median leaf's."""
    median = statistics.median(want)
    compared = compared or [True] * len(want)
    return max(abs(p - r) / max(r, median) for p, r, c in zip(got, want, compared) if c)


def gaps(prog: dict, ref: dict) -> dict:
    """Each compared number of the checked steps: the worst step's loss, and
    the worst leaf's first gradient and change after the checked steps."""
    median = statistics.median(ref["grad_norms"])
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])),
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                                     [g >= STILL_LEAF * median for g in ref["grad_norms"]]),
    }


def expected_batch(stream: np.ndarray, step: int, batch: int, seq_len: int):
    """(inputs, labels) of step `step` (0-based). The loader cuts the token
    stream into consecutive windows of `seq_len + 1` ids, drops the tail that
    does not fill one, and starts again at the next epoch."""
    w = seq_len + 1
    per_epoch = len(stream) // w
    rows = np.stack([stream[(k % per_epoch) * w:(k % per_epoch + 1) * w]
                     for k in range(step * batch, (step + 1) * batch)]).astype(np.int32)
    return rows[:, :-1], rows[:, 1:]


def reference_numbers(cell: Cell, stream: np.ndarray, precision: str = "float32",
                      rows: int | None = None) -> dict:
    """The reference's numbers for the checked steps; `rows` keeps only the
    first rows of each batch (a planted fault)."""
    cfg, wl = cell.config, cell.workload
    batches = []
    for i in range(wl["checked_steps"]):
        ids, labels = expected_batch(stream, i, wl["batch"], wl["seq_len"])
        batches.append((jnp.asarray(ids[:rows]), jnp.asarray(labels[:rows])))
    return reference.train(lambda: weights.make_jitted(cfg, cell.seed), cfg,
                           wl["optimizer"], batches, precision)


def token_shards(cell: Cell) -> np.ndarray:
    wl = cell.workload
    return weights.token_stream(cell.seed, wl["shards"] * wl["tokens_per_shard"],
                                cell.config["vocab_size"])


def run(cell: Cell, seconds: float, h: Harness) -> Outcome:
    from repro.data import LoaderConfig, PrefetchingDataLoader, write_token_shard
    from repro.data.loader import DeviceFeeder
    from repro.io import IOPolicy
    from repro.store import MemTier
    from repro.train import AdamWConfig, StepConfig, TrainState, build_train_step
    from repro.train.optimizer import AdamWState

    cfg, wl = cell.config, cell.workload
    batch, seq_len = wl["batch"], wl["seq_len"]
    model = program.model(cfg)

    store = store_at(wl["link"])
    stream = token_shards(cell)
    n = wl["tokens_per_shard"]
    for i in range(wl["shards"]):
        store.backing.put(f"data/tok{i:04d}.bin", write_token_shard(stream[i * n:(i + 1) * n]))

    def init(key):
        params = weights.make(cfg, key)
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
        return TrainState(params, AdamWState(jnp.zeros((), jnp.int32), zeros(), zeros()))

    key = weights.seed_key(cell.seed)
    state = jax.jit(init)(key)
    opt = wl["optimizer"]
    step = jax.jit(build_train_step(model, AdamWConfig(**opt), StepConfig(**wl["step"])),
                   donate_argnums=(0,))
    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(tree)])
    change = jax.jit(lambda p, k: [jnp.sqrt(jnp.sum((a - b) ** 2)) for a, b in
                                   zip(jax.tree.leaves(p), jax.tree.leaves(weights.make(cfg, k)))])

    lw = wl["loader"]
    loader = PrefetchingDataLoader(
        store, store.list_objects("data/"), [MemTier(lw["cache_bytes"])],
        LoaderConfig(seq_len=seq_len, batch_size=batch, policy=IOPolicy(
            engine=lw["engine"], blocksize=lw["blocksize"], depth=lw["depth"],
            eviction_interval_s=lw["eviction_interval_s"], autotune=lw["autotune"])))
    feeder = DeviceFeeder(loader.batches(), depth=wl["feeder_depth"])
    it = iter(feeder)
    fed = []
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
    del state, metrics, pending

    steps = len(window_losses)
    tokens_per_s = steps * batch * seq_len / window_s
    wrong_rows = 0
    for i, (inputs, labels) in enumerate(fed):
        want_in, want_lab = expected_batch(stream, i, batch, seq_len)
        wrong_rows += int(np.sum(np.any((np.asarray(inputs) != want_in)
                                        | (np.asarray(labels) != want_lab), axis=1)))
    del fed
    found = gaps(prog, reference_numbers(cell, stream))
    found["data_rows_wrong"] = float(wrong_rows)
    limits = wl["limits"]
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens_per_s},
        attempted=steps,
        failed=sum(not math.isfinite(float(x)) for x in window_losses),
        checks={k: (v, float(limits[k])) for k, v in found.items()},
        memory_peak_bytes=peak,
        facts={"tokens_per_s": tokens_per_s,
               "flops_per_token": flops.train_flops_per_token(cfg, seq_len)},
    )
