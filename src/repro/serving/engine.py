"""Slab-based continuous-batching generation engine (real execution mode).

The engine owns a fixed pool of ``max_batch`` sequence slots backed by one
decode-state pytree (``lm.init_decode_state``), so a decode step is a single
jitted call over the whole slab — the vLLM-style step() the wavefront
scheduler drives.  Sequences join via per-sequence prefill (bucketed padding
to bound recompilation) whose state is scattered into a free slot, and leave
when EOS/max-token hits, freeing the slot for the next request: continuous
batching.

This engine is what RealBackend binds to (launch/serve.py).  Its jitted
programs are module-level and keyed on the config, so engines built for the
same model share compiled code, and ``warmup`` compiles every program a
serving pass can reach before the pass is timed.

With its recorder's wall channel on (``trace``, see ``repro.obs.trace``) a
prefill records ``engine.prefill`` (tokens kept, padded width) with its
dispatch, cache insert, first-token pull and last-token update as
children, and a decode step ``engine.decode`` (live sequences, slots,
total context) with its dispatch, the pull that waits for the new tokens,
and the host bookkeeping.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.obs.trace import NOSPAN, TraceRecorder
from repro.serving.sampler import SamplerConfig, sample


@dataclasses.dataclass
class Sequence:
    seq_id: int
    slot: int
    prompt_len: int
    max_new: int
    tokens: list  # generated tokens
    done: bool = False


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


def _decode_impl(params, state, tokens, key, active, *, cfg, sampler):
    logits, state = lm.decode_step(params, cfg, tokens, state)
    with jax.named_scope("sample"):
        nxt = sample(logits, key, sampler)
    # frozen slots keep emitting pad; their cache_len must not grow
    state["cache_len"] = jnp.where(active, state["cache_len"],
                                   state["cache_len"] - 1)
    return nxt, state


def _insert_impl(slab_state, one_state, slot):
    def ins(slab, one):
        if slab.ndim == 1:  # cache_len (B,)
            return slab.at[slot].set(one[0])
        # (L, B, ...) vs (L, 1, ...)
        return jax.lax.dynamic_update_slice_in_dim(slab, one.astype(slab.dtype), slot, axis=1)

    with jax.named_scope("kv_write"):
        return jax.tree.map(ins, slab_state, one_state)


# (params, cfg, tokens (B, S), *, max_len) -> (last-token logits (B, V), state)
jit_prefill = jax.jit(lm.prefill, static_argnums=(1,), static_argnames=("max_len",))
_decode = jax.jit(_decode_impl, static_argnames=("cfg", "sampler"),
                  donate_argnums=(1,))
_insert = jax.jit(_insert_impl, donate_argnums=(0,))


class GenerationEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 512, eos_id: int = 0,
                 sampler: Optional[SamplerConfig] = None, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.sampler = sampler if sampler is not None else SamplerConfig()
        self.state = lm.init_decode_state(cfg, max_batch, max_len)
        self.seqs: dict[int, Sequence] = {}
        self._key = jax.random.PRNGKey(seed)
        self.trace = TraceRecorder()  # wall channel off until switched
        self._clear_slots()

    def _clear_slots(self) -> None:
        self.free_slots = list(range(self.max_batch))
        self.seqs.clear()
        self._next_id = 0
        self._last_tokens = jnp.zeros((self.max_batch,), jnp.int32)
        self._active = np.zeros((self.max_batch,), bool)

    def _prompt_room(self, max_new: int) -> int:
        """Longest prompt kept for a sequence that may decode ``max_new``."""
        # decode writes land at cache_len, so the padded prompt width plus
        # the decode cap must fit the cache or late steps clamp at max_len
        # and corrupt the last KV slot.  Reserve decode room for max_new
        # (but at most half the cache — max_new is often a loose cap).
        decode_room = min(max_new, max(self.max_len // 2, 1))
        return max(self.max_len - decode_room, 1)

    def prefill_widths(self, max_new: int) -> list[int]:
        """Every padded prompt width ``add_sequence(.., max_new)`` can use:
        one compiled prefill program each."""
        keep = self._prompt_room(max_new)
        return sorted({min(_bucket(n), keep) for n in range(1, keep + 1)})

    def warmup(self, max_new: int) -> None:
        """Compile every prefill width and the decode step, then return the
        slots to their empty state, so that a timed serving pass whose
        sequences use at most ``max_new`` compiles nothing.  The sampling key
        is restored: a warmed engine generates what a cold one would."""
        key = self._key
        for w in self.prefill_widths(max_new):
            if not self.free_slots:
                self._clear_slots()
            self.add_sequence(np.ones((w,), np.int32), max_new=max_new)
        self.step()
        jax.block_until_ready(self.state)
        self._clear_slots()
        self._key = key

    # ------------------------------------------------------------------ API
    def can_admit(self) -> bool:
        return bool(self.free_slots)

    def add_sequence(self, prompt_tokens: np.ndarray, max_new: int = 64) -> int:
        """Prefill a prompt into a free slot; returns seq id."""
        if not self.free_slots:
            raise RuntimeError("no free slots")
        tr = self.trace
        with (tr.span("engine.prefill") if tr.wall else NOSPAN) as span:
            slot = self.free_slots.pop()
            # keep the prompt suffix (left-pad semantics), and shrink the
            # effective max_new to the headroom left after padding
            keep = self._prompt_room(max_new)
            prompt_tokens = np.asarray(prompt_tokens)
            if len(prompt_tokens) > keep:
                prompt_tokens = prompt_tokens[-keep:]
            n = len(prompt_tokens)
            pad_to = min(_bucket(n), keep)
            max_new = min(max_new, self.max_len - pad_to)
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, pad_to - n:] = prompt_tokens  # left-pad (simplest causal-safe)
            with (tr.span("engine.prefill.dispatch") if tr.wall else NOSPAN):
                logits, st1 = jit_prefill(self.params, self.cfg,
                                          jnp.asarray(toks),
                                          max_len=self.max_len)
            with (tr.span("engine.insert") if tr.wall else NOSPAN):
                self.state = _insert(self.state, st1, slot)
            # note: left-padding slightly pollutes the prefix; acceptable for
            # the toy-model integration path (real deployment uses paged
            # prefill)
            with (tr.span("engine.first_token") if tr.wall else NOSPAN):
                first = int(jnp.argmax(logits[0]))
            sid = self._next_id
            self._next_id += 1
            self.seqs[sid] = Sequence(sid, slot, n, max_new, [first])
            self._active[slot] = True
            with (tr.span("engine.last_tokens") if tr.wall else NOSPAN):
                lt = np.array(self._last_tokens)
                lt[slot] = first
                self._last_tokens = jnp.asarray(lt)
            if span is not NOSPAN:
                span.args.update(tokens=n, width=pad_to)
        return sid

    def step(self) -> dict[int, int]:
        """One decode step over the slab; returns {seq_id: new_token}."""
        if not self.seqs:
            return {}
        tr = self.trace
        with (tr.span("engine.decode", live=len(self.seqs),
                      slots=self.max_batch,
                      ctx=sum(s.prompt_len + len(s.tokens) - 1
                              for s in self.seqs.values()))
              if tr.wall else NOSPAN):
            with (tr.span("engine.decode.dispatch") if tr.wall else NOSPAN):
                self._key, sub = jax.random.split(self._key)
                active = jnp.asarray(self._active)
                nxt, self.state = _decode(self.params, self.state,
                                          self._last_tokens, sub, active,
                                          cfg=self.cfg, sampler=self.sampler)
                self._last_tokens = nxt
            with (tr.span("engine.decode.pull") if tr.wall else NOSPAN):
                nxt_np = np.asarray(nxt)
            with (tr.span("engine.decode.book") if tr.wall else NOSPAN):
                out: dict[int, int] = {}
                for sid, seq in list(self.seqs.items()):
                    if seq.done:
                        continue
                    tok = int(nxt_np[seq.slot])
                    seq.tokens.append(tok)
                    out[sid] = tok
                    if tok == self.eos_id or len(seq.tokens) >= seq.max_new:
                        seq.done = True
                        self._active[seq.slot] = False
                        self.free_slots.append(seq.slot)
                        del self.seqs[sid]
        return out

    def step_batch(self, n_steps: int) -> None:
        for _ in range(n_steps):
            if not self.seqs:
                return
            self.step()

    @property
    def batch_size(self) -> int:
        return len(self.seqs)
