"""Generation load adapter: the engine does the work the scheduler charges.

The wavefront scheduler charges a generation sub-stage through
``backend.gen_duration(n_prefill_tokens, batch, n_steps)``, and
``RealBackend.gen_duration`` only steps whatever the engine already holds.
This adapter takes its place.  For each call it makes the
``GenerationEngine`` prefill the prompts of the batch's requests that are
new to it (``n_prefill_tokens`` in all), hold the batch's sequences live,
and run ``n_steps`` decode steps, then returns the measured time.

The call's arguments do not say which requests form the batch, so the
adapter reads it from the scheduler without changing it: the slack order
``_assemble_gen`` computed just before the call, cut to ``max_gen_batch``.
Each engine sequence belongs to one generation stage of one request (its
``GenProgress``); it is released when that stage ends or is replaced, and
a sequence evicted for room is prefilled again, with what it had generated,
when its request is next in a batch (counted apart, as work not charged).
Prompt token ids come from the run's seed.  The engine's own truncation of
long prompts is counted as the engine did it, and the token array the
engine hands its prefill program is recorded as it was, so the reference
scores the very input the engine served from.

The engine has no public call that drops a sequence, so ``_free`` edits its
sequence table and free-slot list; ``PERF.md`` lists every private name the
benchmark depends on.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import seeds


@dataclasses.dataclass
class Live:
    progress: object
    request_id: int
    node: int
    prompt: np.ndarray  # the ids the engine kept, after its truncation
    seq: object  # the engine's Sequence: its tokens list grows as it decodes
    sid: int
    model_in: object = None  # the (1, width) token array the prefill program got
    target: int = 0  # decode tokens the stage asks for
    evicted: bool = False
    complete: bool = False
    last_call: int = 0


class GenLoad:
    def __init__(self, sched, engine, workload, spans, *, vocab: int, seed: int,
                 max_new: int):
        self.sched, self.engine, self.workload, self.spans = (
            sched, engine, workload, spans)
        self.vocab, self.seed, self.max_new = vocab, seed, max_new
        self.live: dict[int, Live] = {}  # id(GenProgress) -> sequence
        self.done: list[Live] = []  # released sequences, oldest first
        self.counts = {k: 0 for k in (
            "calls", "mismatched_calls", "prefill_charged", "prefill_executed",
            "prefill_truncated", "reprefill_tokens", "evictions",
            "steps_charged", "steps_executed", "tokens_charged",
            "tokens_executed", "tokens_outside_batch")}
        self._last_order: list = []
        self._order = sched._slack_order
        sched._slack_order = self._observe_order
        self._prefill_input = None

    def _record_prefill(self, orig):
        def prefill(params, cfg, tokens, **kw):
            self._prefill_input = tokens
            return orig(params, cfg, tokens, **kw)
        return prefill

    def _observe_order(self, reqs, now):
        out = self._order(reqs, now)
        self._last_order = out
        return out

    def install(self, backend) -> None:
        """Take the backend's generation entry, and record each input the
        engine's prefill program gets; ``restore`` undoes the latter."""
        import repro.serving.engine as eng

        backend.gen_duration = self.gen_duration
        self._eng_mod, self._orig_prefill = eng, eng.jit_prefill
        eng.jit_prefill = self._record_prefill(eng.jit_prefill)

    def restore(self) -> None:
        self._eng_mod.jit_prefill = self._orig_prefill

    # --------------------------------------------------------------- engine
    def prompt_ids(self, request_id: int, node: int, n: int) -> np.ndarray:
        rng = seeds.rng(self.seed, seeds.PROMPTS, request_id, node)
        return rng.integers(1, self.vocab, size=n, dtype=np.int32)

    def _free(self, lv: Live) -> bool:
        """Drop ``lv``'s sequence from the engine and return its slot, if
        the engine still holds it: the engine's own ``step`` does the same
        for a sequence that reaches its token cap."""
        eng = self.engine
        if eng.seqs.get(lv.sid) is not lv.seq:
            return False
        del eng.seqs[lv.sid]
        eng.free_slots.append(lv.seq.slot)
        return True

    def _release(self, key: int) -> None:
        lv = self.live.pop(key)
        if not lv.complete:
            self._free(lv)
        self.done.append(lv)

    def _release_stale(self) -> None:
        current = {id(r.gen): r.gen for r in self.sched.active
                   if r.gen is not None and not r.gen.done}
        for key, lv in list(self.live.items()):
            if current.get(key) is not lv.progress:
                self._release(key)

    def _make_room(self, batch_keys: set) -> None:
        if self.engine.can_admit():
            return
        idle = [k for k in self.live if k not in batch_keys
                and self.engine.seqs.get(self.live[k].sid) is self.live[k].seq]
        key = min(idle, key=lambda k: self.live[k].last_call)
        lv = self.live[key]
        self._free(lv)
        lv.evicted = True
        self.counts["evictions"] += 1

    def _add(self, ids: np.ndarray, fresh: bool):
        """Prefill ``ids``; a fresh sequence's first token is output, a
        re-prefilled one's is dropped.  The span keeps the prompt tokens
        kept and the output tokens emitted."""
        with self.spans.span("prefill", tokens=0, emitted=0) as info:
            sid = self.engine.add_sequence(ids, max_new=self.max_new)
            seq = self.engine.seqs[sid]
            info["tokens"] = seq.prompt_len
            info["emitted"] = int(fresh)
        return sid, seq, self._prefill_input

    def _ensure(self, r, batch_keys: set) -> None:
        key = id(r.gen)
        lv = self.live.get(key)
        if lv is not None and (lv.complete or self.engine.seqs.get(lv.sid) is lv.seq):
            return
        self._make_room(batch_keys)
        if lv is None:
            node = int(r.current or 0)
            n = self.workload.prompt_tokens(r.request_id, node)
            sid, seq, model_in = self._add(self.prompt_ids(r.request_id, node, n), True)
            self.counts["prefill_charged"] += n
            self.counts["prefill_executed"] += seq.prompt_len
            self.counts["prefill_truncated"] += n - seq.prompt_len
            kept = self.prompt_ids(r.request_id, node, n)[n - seq.prompt_len:]
            self.live[key] = Live(r.gen, r.request_id, node, kept, seq, sid,
                                  model_in=model_in, target=int(r.gen.target_tokens))
            return
        # evicted earlier: prefill its prompt and what it had generated
        ctx = np.concatenate([lv.prompt, np.asarray(lv.seq.tokens[:-1], np.int32)])
        sid, seq, _ = self._add(ctx, False)
        self.counts["reprefill_tokens"] += seq.prompt_len
        seq.tokens[:0] = lv.seq.tokens[:-1]
        lv.seq, lv.sid = seq, sid

    def gen_duration(self, n_prefill_tokens: int, batch: int, n_steps: int) -> float:
        c = self.counts
        c["calls"] += 1
        reqs = list(self._last_order[: self.sched.cfg.max_gen_batch])
        new = [r for r in reqs if not r.gen.prefilled]
        charged = sum(self.workload.prompt_tokens(r.request_id, int(r.current or 0))
                      for r in new)
        if len(reqs) != batch or charged != n_prefill_tokens:
            c["mismatched_calls"] += 1
        t0 = time.perf_counter()
        self._release_stale()
        keys = {id(r.gen) for r in reqs}
        for r in reqs:
            self._ensure(r, keys)
            self.live[id(r.gen)].last_call = c["calls"]
        in_batch = {self.live[k].sid for k in keys}
        c["steps_charged"] += n_steps
        c["tokens_charged"] += sum(
            min(n_steps, r.gen.target_tokens - r.gen.generated) for r in reqs)
        for _ in range(n_steps):
            if not self.engine.seqs:
                break
            ctx = sum(s.prompt_len + len(s.tokens) - 1
                      for s in self.engine.seqs.values())
            with self.spans.span("decode", live=len(self.engine.seqs), ctx=ctx,
                                 emitted=0) as info:
                out = self.engine.step()
                info["emitted"] = len(out)
            c["steps_executed"] += 1
            hit = sum(1 for sid in out if sid in in_batch)
            c["tokens_executed"] += hit
            c["tokens_outside_batch"] += len(out) - hit
            for k in keys:
                self._retire_if_complete(k)
        return (time.perf_counter() - t0) * 1e6

    def _retire_if_complete(self, key: int) -> None:
        """A sequence that has decoded what its stage asks for leaves the
        engine's sequence table, so later steps of the call, which decode
        every slot anyway, add no token to it."""
        lv = self.live[key]
        if lv.complete or len(lv.seq.tokens) - 1 < lv.target:
            return
        self._free(lv)
        lv.complete = True

    def finished_sequences(self) -> list[Live]:
        """Every sequence released so far plus those still live."""
        return self.done + list(self.live.values())
