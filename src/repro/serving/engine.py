"""Slot-based serving engine with continuous batching.

vLLM-style scheduling mapped to jax-native constructs: a fixed device
batch of `slots`, each slot holding one request's KV state inside ONE
batched cache pytree (so the decode step is a single jit'd call — no
per-request dispatch).  Continuous batching = admit new requests into
free slots between decode steps; finished requests free their slot
immediately.

  * prefill: per-request prefill produces a length-S cache which is
    scattered into the slot's rows of the batched ring cache;
  * decode: one `serve_step` advances every active slot by one token;
    inactive slots decode garbage that is masked out (the standard
    padded-batch trick — wasted FLOPs bounded by occupancy).
  * greedy or temperature sampling, EOS/max-token termination.

On a real pod the same engine runs with the decode step pjit-sharded
(batch over `data`, KV-seq over `model`); the scheduler is host-side and
identical.  Part of the LM template: the clustering service does not
import this module.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M

from .batcher import HostBatcher


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (len,) int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    temperature: float = 0.0
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg, params, *, slots: int = 4, cache_len: int = 256, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.model = M.build_model(cfg)
        self.serve_step = jax.jit(M.make_serve_step(cfg))
        self._prefill = jax.jit(self._prefill_one)
        self.caches = self.model.init_cache(slots, cache_len)  # owner: serve thread
        self.slot_req: list[Request | None] = [None] * slots  # owner: serve thread
        self.slot_pos = np.zeros(slots, dtype=np.int64)  # owner: serve thread
        self.queue = HostBatcher(max_block=slots)
        self.rng = np.random.default_rng(seed)
        self.steps = 0  # owner: serve thread
        self.tokens_out = 0  # owner: serve thread

    # -- internals ----------------------------------------------------------

    def _prefill_one(self, params, tokens):
        """(1, S) prompt -> (last_logits, cache-of-length-cache_len)."""
        cfg = self.cfg
        toks = tokens
        if cfg.family == "vlm":
            media = jnp.zeros((1, cfg.n_media_tokens, cfg.d_model), jnp.bfloat16)
            logits, cache = self.model.prefill(params, toks, media)
        elif cfg.family == "audio":
            enc = jnp.zeros((1, cfg.n_frames, cfg.d_model), jnp.bfloat16)
            logits, cache = self.model.prefill(params, toks, enc)
        else:
            logits, cache = self.model.prefill(params, toks)
        return logits, cache

    def _write_slot_cache(self, slot: int, cache, prompt_len: int):
        """Scatter a freshly prefilled cache into the batched slot cache.
        Prefill caches have seq length == prompt_len; the slot cache is a
        cache_len ring.  Batch dim position differs per cache family; we
        match on the dim equal to `slots` that aligns with the prefill
        cache's size-1 dim."""

        def put(slot_arr, new_arr):
            if not hasattr(slot_arr, "ndim") or slot_arr.ndim == 0:
                return slot_arr
            # find batch dim: axis where slot cache has self.slots and the
            # prefill cache has 1
            bdim = None
            for ax in range(slot_arr.ndim):
                if (
                    ax < new_arr.ndim
                    and slot_arr.shape[ax] == self.slots
                    and new_arr.shape[ax] == 1
                ):
                    bdim = ax
                    break
            if bdim is None:
                return slot_arr  # per-layer pos counters handled below
            # seq dim: the axis right after batch where lengths differ
            idx = [slice(None)] * slot_arr.ndim
            idx[bdim] = slice(slot, slot + 1)
            sdim = None
            for ax in range(slot_arr.ndim):
                if ax != bdim and ax < new_arr.ndim and new_arr.shape[ax] != slot_arr.shape[ax]:
                    sdim = ax
                    break
            if sdim is not None:
                take = min(new_arr.shape[sdim], slot_arr.shape[sdim])
                nidx = [slice(None)] * new_arr.ndim
                nidx[sdim] = slice(0, take)
                new_arr = new_arr[tuple(nidx)]
                idx[sdim] = slice(0, take)
            return slot_arr.at[tuple(idx)].set(new_arr.astype(slot_arr.dtype))

        self.caches = jax.tree.map(put, self.caches, cache)

    # -- public API -----------------------------------------------------------

    def submit(self, req: Request):
        self.queue.push(req, kind="req")

    def _admit(self):
        for slot in range(self.slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop_one()
                toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
                logits, cache = self._prefill(self.params, toks)
                self._write_slot_cache(slot, cache, len(req.prompt))
                tok = self._sample(np.asarray(logits[0, -1]), req)
                req.generated.append(int(tok))
                self.tokens_out += 1
                # the prefill-produced token can itself terminate
                if (req.eos_id is not None and tok == req.eos_id) or len(
                    req.generated
                ) >= req.max_new_tokens:
                    req.done = True
                    continue
                self.slot_req[slot] = req
                self.slot_pos[slot] = len(req.prompt)

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        logits = logits[: self.cfg.vocab_size].astype(np.float64)
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / req.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def step(self):
        """One continuous-batching iteration: admit + decode + retire."""
        self._admit()
        active = [s for s in range(self.slots) if self.slot_req[s] is not None]
        if not active:
            return False
        last = np.zeros((self.slots, 1), dtype=np.int32)
        for s in active:
            last[s, 0] = self.slot_req[s].generated[-1]
        pos = int(max(self.slot_pos[s] for s in active))  # scalar step pos
        extras = None
        if self.cfg.family == "vlm":
            extras = {"media": jnp.zeros((self.slots, self.cfg.n_media_tokens, self.cfg.d_model), jnp.bfloat16)}
        elif self.cfg.family == "audio":
            extras = {"enc": jnp.zeros((self.slots, self.cfg.n_frames, self.cfg.d_model), jnp.bfloat16)}
        logits, self.caches = self.serve_step(
            self.params, self.caches, jnp.asarray(last), jnp.asarray(pos, jnp.int32), extras
        )
        logits = np.asarray(logits[:, -1].astype(jnp.float32))
        self.steps += 1
        for s in active:
            req = self.slot_req[s]
            tok = self._sample(logits[s], req)
            req.generated.append(tok)
            self.tokens_out += 1
            self.slot_pos[s] += 1
            if (
                (req.eos_id is not None and tok == req.eos_id)
                or len(req.generated) >= req.max_new_tokens
                or self.slot_pos[s] >= self.cache_len - 1
            ):
                req.done = True
                self.slot_req[s] = None  # free the slot for the next admit
        return True

    def run(self, max_steps: int = 10_000) -> list[Request]:
        finished: list[Request] = []
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        return finished
