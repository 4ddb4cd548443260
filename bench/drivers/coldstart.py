"""A serving replica's cold start from the store, back to back.

Set-up makes the published weights on the device from the seed, writes them
into the emulated store's backing as a checkpoint, restores them once from
the backing (no link) to warm the restore path, and warms the prefill's
shapes on the published weights. In the window each cold start restores the weights
through the link into a fresh `ServeEngine.from_store` (its default policy),
submits one wave of prompts drawn from the seed and runs it to its first
tokens on the host. Cold starts run whole until `--seconds` has passed.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, reference, weights
from bench.harness import Cell, Harness, Outcome, memory_peak_bytes
from bench.store import store_at

PREFIX = "weights"


@jax.jit
def leaves_differing(a, b):
    """How many leaves of `a` differ from `b` in any bit."""
    bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
    return sum(jnp.any(bits(x) != bits(y)).astype(jnp.int32)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def logit_gaps(cell: Cell, prompts: np.ndarray, tokens: np.ndarray,
               precision: str = "float32") -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best, for every prompt of `prompts` (waves, batch, length) and `tokens`
    (waves, batch). With `precision` below float32, `tokens` is ignored and
    the tokens are those that precision puts first (the control)."""
    cfg = cell.config
    w = weights.make_jitted(cfg, cell.seed)
    static = reference.static(cfg)
    out = np.empty(tokens.shape, np.float64)
    for i, wave in enumerate(prompts):
        ref = np.asarray(reference.prompt_logits(w, static, jnp.asarray(wave), "float32"))
        picked = tokens[i]
        if precision != "float32":
            low = reference.prompt_logits(w, static, jnp.asarray(wave), precision)
            picked = np.asarray(jnp.argmax(low, axis=-1))
        out[i] = ref.max(axis=-1) - ref[np.arange(len(picked)), picked]
    return out


def prompt_pool(cell: Cell) -> np.ndarray:
    wl = cell.workload
    return weights.prompts(cell.seed, wl["waves"], wl["requests"], wl["prompt_len"],
                           cell.config["vocab_size"])


def run(cell: Cell, seconds: float, h: Harness) -> Outcome:
    from repro.ckpt import save_checkpoint
    from repro.io import IOPolicy
    from repro.serve.engine import Request, ServeEngine

    cfg, wl = cell.config, cell.workload
    model = program.model(cfg)
    store = store_at(wl["link"])
    published = weights.make_jitted(cfg, cell.seed)
    save_checkpoint(store.backing, PREFIX, 0, published, policy=IOPolicy(write_depth=4))
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), published)
    pool = prompt_pool(cell)
    n_req, budget = wl["requests"], wl["max_new_tokens"]

    def wave(engine, prompts):
        for rid, p in enumerate(prompts):
            engine.submit(Request(rid=rid, prompt=p, max_new_tokens=budget))
        return engine.run()

    # Warm the restore path once from the unlinked backing (no link time):
    # a process's first restore paid 1-4 s more in some runs. Then the
    # prefill's shapes, on the published weights.
    warm = ServeEngine.from_store(model, store.backing, PREFIX, template, max_batch=n_req)
    leaves_differing(warm.params, published).block_until_ready()
    del warm
    wave(ServeEngine(model, published, max_batch=n_req), np.zeros_like(pool[0]))
    h.setup_done()

    served, differing, floors = [], [], []
    with h.window():
        t0 = time.perf_counter()
        while not served or time.perf_counter() - t0 < seconds:
            if len(served) == len(pool):
                raise RuntimeError(f"{cell.name}: the prompt pool ran out; raise `waves`")
            with h.span("cold_start"):
                before = store.link.counters()["bytes"]
                with h.span("restore"):
                    engine = ServeEngine.from_store(model, store, PREFIX, template,
                                                    max_batch=n_req)
                    jax.block_until_ready(engine.params)
                floors.append(store.link.floor_s(store.link.counters()["bytes"] - before))
                with h.span("prefill"):
                    results = wave(engine, pool[len(served)])
            differing.append(leaves_differing(engine.params, published))
            served.append(results)
        window_s = time.perf_counter() - t0
    differing = [int(x) for x in differing]
    peak = memory_peak_bytes()
    del engine, published

    n = len(served)
    tokens = np.full((n, n_req), -1, np.int64)
    for i, results in enumerate(served):
        for r in results:
            if 0 <= r.rid < n_req and len(r.tokens):
                tokens[i, r.rid] = int(r.tokens[0])
    ok = (tokens >= 0) & (tokens < cfg["vocab_size"])
    missing = int((~ok).sum())
    gaps = logit_gaps(cell, pool[:n], np.where(ok, tokens, 0))[ok]
    limits = wl["limits"]
    found = {"logit_gap": float(gaps.max()) if gaps.size else float("inf"),
             "requests_missing": float(missing),
             "restored_leaves_differing": float(sum(differing))}
    return Outcome(
        end_to_end={"cold_start_s": window_s / n},
        attempted=n * n_req,
        failed=missing,
        checks={k: (v, float(limits[k])) for k, v in found.items()},
        memory_peak_bytes=peak,
        facts={"restore_floor_s": floors},
    )
