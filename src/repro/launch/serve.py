"""Serving driver: batched prefill + decode with KV caches; weights
restored from the object store through Rolling Prefetch (cold-start
latency is a first-order cost at serving scale, and checkpoint restore is
exactly the sequential multi-object stream the paper optimizes).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import restore_breakdown, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.io import IOPolicy, open_store
from repro.launch.compile_cache import use_compile_cache
from repro.models import make_model
from repro.utils import get_logger, trace

log = get_logger("launch.serve")


@dataclass
class ServeRun:
    """What one `main` call did, for callers that drive it in-process."""

    ids: np.ndarray          # (batch, gen) generated token ids
    logits: np.ndarray       # (batch, vocab) logits of the last decode step
    restore_s: float         # cold-start weight restore from the store
    prefill_s: float | None  # first prefill including its compile
    decode_s: float          # all decode steps, the first one's compile too


def main(argv: list[str] | None = None) -> ServeRun:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--restore-mode", default="rolling",
                    choices=["rolling", "sequential"])
    ap.add_argument("--autotune", action="store_true",
                    help="adaptive restore: coalesced range GETs + AIMD "
                         "stream depth + closed-loop blocksize tuning")
    ap.add_argument("--store", default="sims3://weights?latency_ms=10&bw_mbps=80",
                    help="weight store URI (any registered scheme)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent weight-block cache directory: restores "
                         "cache into a journaled DirTier there, so a "
                         "restarted replica cold-starts warm (zero store "
                         "GETs for blocks that survived on local disk)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant", choices=["int8"], default=None,
                    help="weight-only int8 serving (TP-only layout)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = make_model(cfg)

    # --- publish + cold-start restore through the object store ----------------
    store = open_store(args.store)
    params = model.init(jax.random.key(0))
    save_checkpoint(store, "weights", 0, params,
                    policy=IOPolicy(write_depth=4))
    with trace.recording() as rec:
        t0 = time.perf_counter()
        params, _ = restore_checkpoint(
            store, "weights", params,
            policy=IOPolicy(engine=args.restore_mode, depth=2,
                            max_depth=8 if args.autotune else None,
                            autotune=args.autotune,
                            eviction_interval_s=0.2),
            cache_dir=args.cache_dir,
        )
        jax.block_until_ready(params)
        restore_s = time.perf_counter() - t0
    parts = restore_breakdown(rec)
    print(f"weight restore ({args.restore_mode}): {restore_s:.2f}s"
          + (f" [cache: {args.cache_dir}]" if args.cache_dir else ""))
    print(f"  meta {parts['meta_s']:.2f}s, wait {parts['wait_s']:.2f}s, "
          f"host {parts['host_s']:.2f}s, {parts['requests']:.0f} store requests")
    if args.quant == "int8":
        from repro.models.quant import quantize_params

        params, n_q = quantize_params(params)
        print(f"quantized {n_q} weight tensors to int8 (weight-only)")

    # --- batched prefill -------------------------------------------------------
    b, s = args.batch, args.prompt_len
    rng = np.random.default_rng(0)
    if cfg.embed_inputs and not cfg.is_encdec:
        batch = {"inputs": jnp.asarray(
            rng.normal(size=(b, s, cfg.d_model)).astype(np.float32),
            jnp.bfloat16)}
    elif cfg.is_encdec:
        batch = {
            "enc_inputs": jnp.asarray(
                rng.normal(size=(b, s, cfg.d_model)).astype(np.float32),
                jnp.bfloat16),
            "dec_prompt": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b, 8)), jnp.int32),
        }
    else:
        batch = {"inputs": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}

    # Decode needs cache headroom for generated tokens.
    prompt_tokens = 8 if cfg.is_encdec else s
    max_len = prompt_tokens + args.gen
    prefill_s = None
    if cfg.is_encdec:
        from repro.models import encdec as ED

        enc_h = ED.encode(params, cfg, batch["enc_inputs"], q_chunk=min(512, s))
        cross = ED.build_cross_caches(params, cfg, enc_h)
        caches = ED.make_decode_caches(cfg, b, max_len, cross_len=s, length=0)
        caches = ED._merge_cross(caches, cross)
        from repro.models import layers as L, lm as LM

        x = L.embed_tokens(params["embed"], cfg, batch["dec_prompt"])
        x = ED._add_sinusoid(x)
        positions = jnp.arange(prompt_tokens, dtype=jnp.int32)
        x, caches, _ = LM.stack_fwd(
            params["layers"], cfg, x, positions=positions, caches=caches,
            update_cache=True, causal=True, q_chunk=min(512, prompt_tokens),
        )
        h = L.apply_norm(params["final_norm"], cfg, x)
        logits = LM.logits_from_hidden(params, cfg, h[:, -1:, :])[:, 0]
    else:
        from repro.models import lm as LM

        t0 = time.perf_counter()
        logits, caches = LM.lm_prefill(params, cfg, batch["inputs"],
                                       max_len=max_len, q_chunk=min(512, s))
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - t0
        print(f"prefill: {prefill_s:.2f}s ({b * s / prefill_s:.0f} tok/s)")

    # --- decode loop -----------------------------------------------------------
    decode = jax.jit(
        lambda p, ids, c, pos: model.decode_step(p, ids, c, pos)
    )
    key = jax.random.key(1)
    tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None]
    generated = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        pos = prompt_tokens + i
        if cfg.embed_inputs and not cfg.is_encdec:
            # VLM decode consumes token embeddings from the text table.
            emb = jnp.take(params["embed"]["table"], tok[:, 0], axis=0)
            step_in = emb[:, None, :].astype(jnp.bfloat16)
        else:
            step_in = tok
        logits, caches = decode(params, step_in, caches, pos)
        logits = logits[:, : cfg.vocab_size]
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / args.temperature)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None]
        generated.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    out = np.asarray(jnp.concatenate(generated, axis=1))
    print(f"decoded {args.gen} tokens x {b} seqs in {dt:.2f}s "
          f"({b * args.gen / dt:.1f} tok/s)")
    print("sample token ids:", out[0, :16])
    return ServeRun(ids=out, logits=np.asarray(logits[:, : cfg.vocab_size]),
                    restore_s=restore_s, prefill_s=prefill_s, decode_s=dt)


if __name__ == "__main__":
    main()
