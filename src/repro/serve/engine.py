"""Batched serving engine: request queue -> padded-batch prefill -> masked
decode waves with early retirement.

Wave-based batching (vLLM-style slot-level continuous batching needs
per-slot position vectors; the assigned decode cells are uniform-position,
so the engine batches requests into waves): each wave admits up to
`max_batch` queued requests of the SAME prompt length (length-bucketed —
padding would let real tokens attend to garbage), prefills them together,
then decodes step-by-step. Finished sequences (EOS or their own token
budget) are masked out; the wave retires when every member finishes, and
the queue refills the next wave. Weight restore streams through Rolling
Prefetch (see launch/serve.py) — serving cold-start is the paper's
sequential-object-stream case.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.utils import get_logger, trace

log = get_logger("serve.engine")


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int
    eos_id: int | None = None
    submitted_s: float | None = None   # set by `ServeEngine.submit`


@dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray          # generated ids (<= max_new_tokens)
    prompt_len: int
    first_token_s: float        # from submit to the first token on the host


@dataclass
class ServeStats:
    waves: int = 0
    requests: int = 0
    generated_tokens: int = 0
    decode_steps: int = 0
    wall_s: float = 0.0

    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s else 0.0


class ServeEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 pad_id: int = 0) -> None:
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.pad_id = pad_id
        self.queue: list[Request] = []
        self.stats = ServeStats()
        self._decode = jax.jit(
            lambda p, ids, caches, pos: model.decode_step(p, ids, caches, pos)
        )

    @classmethod
    def from_store(cls, model: Model, store, prefix: str, template, *,
                   policy=None, step: int | None = None, max_batch: int = 8,
                   pad_id: int = 0) -> "ServeEngine":
        """Cold-start an engine from checkpointed weights in an object
        store, streamed through the `PrefetchFS` facade with the restore's
        own default policy (`RESTORE_POLICY`) unless ``policy`` is given:
        leaf fetches overlap `device_put` (serving cold-start is the
        paper's sequential multi-object stream)."""
        from repro.ckpt.manager import RESTORE_POLICY, restore_checkpoint

        # Serving cold-start is the latency-critical restore class: under
        # an HSM hierarchy its blocks admit into (and are protected in)
        # the top tier, so a concurrent bulk scan cannot flush the weights
        # a replica re-reads on every restart. An explicit class wins.
        policy = policy if policy is not None else RESTORE_POLICY
        if policy.io_class == "default":
            policy = policy.replace(io_class="serve")
        params, _ = restore_checkpoint(store, prefix, template, step=step,
                                       policy=policy)
        return cls(model, params, max_batch=max_batch, pad_id=pad_id)

    def submit(self, req: Request) -> None:
        self.queue.append(replace(req, submitted_s=time.perf_counter()))

    # ------------------------------------------------------------------ #
    def _admit_wave(self) -> list[Request]:
        """Length-bucketed admission: the oldest request sets the wave's
        prompt length; other same-length requests join up to max_batch."""
        if not self.queue:
            return []
        want = len(self.queue[0].prompt)
        wave, rest = [], []
        for r in self.queue:
            if len(r.prompt) == want and len(wave) < self.max_batch:
                wave.append(r)
            else:
                rest.append(r)
        self.queue = rest
        return wave

    def _stack_prompts(self, wave: list[Request]) -> tuple[np.ndarray, np.ndarray]:
        batch = np.stack([r.prompt for r in wave]).astype(np.int32)
        lens = np.array([len(r.prompt) for r in wave], np.int32)
        return batch, lens

    def run(self, max_waves: int | None = None) -> list[RequestResult]:
        """Drain the queue; returns per-request results."""
        results: list[RequestResult] = []
        t_start = time.perf_counter()
        while self.queue and (max_waves is None or self.stats.waves < max_waves):
            wave = self._admit_wave()
            batch_ids, lens = self._stack_prompts(wave)
            b, s = batch_ids.shape
            budget = max(r.max_new_tokens for r in wave)
            cfg = self.model.cfg

            # Prefill with decode headroom.
            from repro.models import lm as LM

            with trace.span("serve.prefill"):
                caches = LM.make_stack_cache(cfg, b, s + budget)
                h, caches, _ = LM.lm_hidden(
                    self.params, cfg, jnp.asarray(batch_ids),
                    caches=caches, update_cache=True,
                    q_chunk=min(512, s),
                )
                logits = LM.logits_from_hidden(self.params, cfg, h[:, -1:, :])[:, 0]

            generated = np.full((b, budget), -1, np.int64)
            done = np.zeros(b, bool)
            with trace.span("serve.first_tokens"):
                tok = np.asarray(
                    jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)
                )
            t_first = time.perf_counter()
            for i, r in enumerate(wave):
                generated[i, 0] = tok[i]
                if (r.eos_id is not None and tok[i] == r.eos_id) or \
                        r.max_new_tokens <= 1:
                    done[i] = True

            step = 1
            with trace.span("serve.decode"):
                while not done.all() and step < budget:
                    logits_t, caches = self._decode(
                        self.params, jnp.asarray(tok[:, None], jnp.int32),
                        caches, s + step - 1,
                    )
                    self.stats.decode_steps += 1
                    tok = np.asarray(
                        jnp.argmax(logits_t[:, : cfg.vocab_size], axis=-1)
                    )
                    for i, r in enumerate(wave):
                        if done[i]:
                            continue
                        generated[i, step] = tok[i]
                        if (r.eos_id is not None and tok[i] == r.eos_id) or \
                                step + 1 >= r.max_new_tokens:
                            done[i] = True
                    step += 1

            for i, r in enumerate(wave):
                toks = generated[i][generated[i] >= 0]
                results.append(RequestResult(
                    rid=r.rid,
                    tokens=toks.astype(np.int64),
                    prompt_len=int(lens[i]),
                    first_token_s=t_first - r.submitted_s,
                ))
                self.stats.generated_tokens += len(toks)
            self.stats.waves += 1
            self.stats.requests += len(wave)
        self.stats.wall_s = time.perf_counter() - t_start
        return results
