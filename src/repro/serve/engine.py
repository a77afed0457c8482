"""Continuous-batching serving engine.

Fixed B decode slots over a static-shaped KV cache (TPU-friendly: one
compiled decode step, no re-compilation as requests come and go):
  * new requests are prefilled one at a time, at their own prompt length
    (one prefill program is compiled per length), and their cache
    scattered into a free slot,
  * every engine tick reads the batched step the previous tick launched,
    frees the slots that ended (EOS, budget or max_len), refills them
    from the queue, and launches the next step over every active slot
    before it returns, so the chip decodes while the host hands the
    tokens back and the next tick call comes in.

`SlotScheduler` holds that loop -- queue, slots, admission, completion,
the `engine.*` spans -- once; `ServeEngine` (a model) and `StubEngine` (a
pure function of the prompt) supply only its hooks.

On a pod this engine is one long-lived Syndeo actor per model replica; the
Syndeo scheduler routes request batches to replicas (placement groups pin
them to pod slices). Each engine is pinned to one device: its params,
cache and step inputs are committed there, so one process can host one
replica per chip.
"""
from __future__ import annotations

import collections
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import SPANS
from repro.models.registry import Model

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@functools.cache
def _record_compiles() -> None:
    """Once per process: record each backend compile as an
    `engine.compile` span, [now - its duration, now], under the span
    open on the compiling thread (a compile inside a serving tick lands
    under that `engine.tick`)."""
    def on(event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            now = time.perf_counter()
            SPANS.record("engine.compile", now - secs, now)
    jax.monitoring.register_event_duration_secs_listener(on)


@dataclass
class Request:
    id: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: int = -1          # -1: never
    output: List[int] = field(default_factory=list)
    done: bool = False


class SlotScheduler:
    """B decode slots fed from a FIFO queue. Each tick reads the tokens of
    the step the previous tick launched, frees a slot whose request ended
    (on its EOS, on its budget of `max_new_tokens`, or when prompt and
    output fill `max_len`), admits queued requests into empty slots, and
    launches the next step over every active slot without waiting for
    it."""

    def __init__(self, batch_slots: int, max_len: int):
        self.B = batch_slots
        self.max_len = max_len
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.queue: "collections.deque[Request]" = collections.deque()
        self._queued_at: Dict[int, float] = {}    # id(req) -> add_request
        self._completed: List[Request] = []
        self._stepping: List[int] = []    # the slots of the step in flight
        self.stats = {"ticks": 0, "prefills": 0, "decoded_tokens": 0,
                      "completed": 0}

    # -- the engine's hooks ----------------------------------------------------

    def _prefill(self, req: Request, slot: int) -> int:
        """Prefill `req` into the empty `slot`; return its first token."""
        raise NotImplementedError

    def _launch(self) -> None:
        """Start one step over the active slots; do not wait for it."""
        raise NotImplementedError

    def _collect(self) -> Sequence[Optional[int]]:
        """The next token of each slot, from the step `_launch` started."""
        raise NotImplementedError

    def _free(self, slot: int) -> None:
        """Release what the engine holds for the ended request in `slot`."""

    # -- request management ------------------------------------------------------

    def add_request(self, req: Request):
        self._queued_at[id(req)] = time.perf_counter()
        self.queue.append(req)

    @property
    def free_slots(self) -> int:
        """Decode slots with no active request (prefill capacity), net of
        queued requests that will claim one at the next tick."""
        empty = sum(1 for r in self.slot_req if r is None)
        return max(0, empty - len(self.queue))

    @property
    def queue_len(self) -> int:
        return len(self.queue)

    @property
    def outstanding_tokens(self) -> int:
        """Tokens still owed to admitted requests -- the router's
        least-outstanding-tokens tiebreak reads this, so it counts queued
        requests (full budget) plus active slots (budget minus emitted)."""
        owed = sum(r.max_new_tokens for r in self.queue)
        owed += sum(r.max_new_tokens - len(r.output)
                    for r in self.slot_req if r is not None)
        return owed

    def _fill_free_slots(self):
        """Admit queued requests into empty slots. An admission's `held` is
        the number of slots already answering (those admitted earlier in
        this tick among them): each gets no token until it ends."""
        for slot in range(self.B):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            now = time.perf_counter()
            SPANS.record("engine.queue", self._queued_at.pop(id(req), now),
                         now, req=req.id)
            held = sum(r is not None for r in self.slot_req)
            with SPANS.span("engine.admit", req=req.id,
                            prompt=len(req.prompt), syncs=0, held=held):
                req.output.append(self._prefill(req, slot))
                self.slot_req[slot] = req
                self.stats["prefills"] += 1

    # -- the decode tick -----------------------------------------------------------

    def tick(self) -> int:
        """One engine iteration; returns the number of slots it answered:
        those of the step it read and those of the step it launched.
        `ahead` is 1 where it read a step the previous tick launched."""
        with SPANS.span("engine.tick", active=0, syncs=0,
                        ahead=int(bool(self._stepping))) as span:
            span.attrs["active"] = n = self._tick()
        return n

    def _tick(self) -> int:
        stepped = self._stepping
        if stepped:
            with SPANS.span("engine.step_wait"):
                next_tokens = self._collect()
            for s in stepped:
                req = self.slot_req[s]
                tok = int(next_tokens[s])
                req.output.append(tok)
                self.stats["decoded_tokens"] += 1
                if (tok == req.eos_id or len(req.output) >= req.max_new_tokens
                        or len(req.prompt) + len(req.output) >= self.max_len):
                    req.done = True
                    self.slot_req[s] = None
                    self._free(s)
                    self._completed.append(req)
                    self.stats["completed"] += 1
        self._fill_free_slots()
        self._stepping = [s for s in range(self.B)
                          if self.slot_req[s] is not None]
        if self._stepping:
            self._launch()
        answered = len(set(stepped) | set(self._stepping))
        if answered:
            self.stats["ticks"] += 1
        return answered

    def pop_completed(self) -> List[Request]:
        """Requests finished since the last pop (the router's per-tick
        harvest)."""
        out, self._completed = self._completed, []
        return out

    def run_until_drained(self, max_ticks: int = 10000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            self.tick()
        return self.pop_completed()


class ServeEngine(SlotScheduler):
    def __init__(self, model: Model, params, batch_slots: int, max_len: int,
                 device: Optional[jax.Device] = None):
        """`device` (default: the first device) holds the replica; params
        living elsewhere are copied to it."""
        super().__init__(batch_slots, max_len)
        self.model = model
        self.device = device or jax.devices()[0]
        self.params = self._put(params)
        with jax.default_device(self.device):   # built in place, no hop
            self.cache = self._put(model.init_cache(batch_slots, max_len))
            self.positions = self._put(jnp.zeros((batch_slots,), jnp.int32))
            # 1 for an answering slot: only those advance, so an empty slot
            # stays at position 0, where the decode step reads none of its
            # cache
            self.live = self._put(jnp.zeros((batch_slots,), jnp.int32))
            self.tokens = self._put(jnp.zeros((batch_slots, 1), jnp.int32))
        self._decode = jax.jit(self._decode_step, donate_argnums=(1,))
        self._prefill_one = jax.jit(self._prefill_impl)
        _record_compiles()

    def _decode_step(self, params, cache, batch):
        """The batched decode step, jitted under this name (a profile
        names the program `jit__decode_step`). It returns the next step's
        inputs, so they never leave the device: each slot's greedy token,
        and its position advanced where it is `live`."""
        logits, cache = self.model.decode_step(
            params, cache,
            {"tokens": batch["tokens"], "positions": batch["positions"]})
        tokens = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return (tokens[:, None], batch["positions"] + batch["live"]), cache

    def _prefill_impl(self, params, tokens):
        return self.model.prefill(params, {"tokens": tokens})

    @staticmethod
    def _read(x) -> np.ndarray:
        """Every device-to-host read of the engine goes through here,
        counted as one host sync of the open `engine.tick` and
        `engine.admit` spans."""
        SPANS.add("syncs")
        return np.asarray(x)

    def _put(self, x):
        """Commit a host value (or pytree) to this engine's device."""
        return jax.device_put(x, self.device)

    # -- the scheduler's hooks ---------------------------------------------------

    def _prefill(self, req: Request, slot: int) -> int:
        prompt = self._put(np.asarray(req.prompt, np.int32)[None, :])
        with SPANS.span("engine.prefill"):
            logits, pcache = self._prefill_one(self.params, prompt)
        next_tok = int(self._read(jnp.argmax(logits[0, -1])))
        self._scatter_cache(pcache, slot, len(req.prompt))
        self.positions = self.positions.at[slot].set(len(req.prompt))
        self.live = self.live.at[slot].set(1)
        self.tokens = self.tokens.at[slot, 0].set(next_tok)
        return next_tok

    def _scatter_cache(self, pcache, slot: int, plen: int):
        """Copy a 1-seq prefill cache into batch slot `slot`."""
        def per_leaf(big, small):
            if big.ndim < 2 or big.shape[1] != self.B:
                return big
            pad_width = [(0, 0) if i == 1 else (0, b - s)   # up to max_len
                         for i, (b, s) in enumerate(zip(big.shape,
                                                        small.shape))]
            small_p = jnp.pad(small, pad_width)
            return big.at[:, slot].set(small_p[:, 0].astype(big.dtype))
        self.cache = jax.tree.map(per_leaf, self.cache, pcache)

    def _launch(self) -> None:
        batch = {"tokens": self.tokens, "positions": self.positions,
                 "live": self.live}
        with SPANS.span("engine.decode"):
            (self.tokens, self.positions), self.cache = self._decode(
                self.params, self.cache, batch)

    def _collect(self) -> np.ndarray:
        return self._read(self.tokens)[:, 0]

    def _free(self, slot: int) -> None:
        self.positions = self.positions.at[slot].set(0)
        self.live = self.live.at[slot].set(0)


class StubEngine(SlotScheduler):
    """Model-free engine on the shared slot scheduler, for the router, the
    sim cost model, and CI hosts without an accelerator: queue, slots,
    admission, completion and the `engine.*` spans are ServeEngine's own
    by construction; only the tokens differ.

    Deterministic: a request's output is a pure function of its prompt
    (`stub_output`), so a routed K-replica execution must be
    token-identical to one local engine -- the completion-equivalence
    property in tests/test_serve_plane.py."""

    def __init__(self, batch_slots: int, max_len: int = 1 << 30):
        super().__init__(batch_slots, max_len)

    @staticmethod
    def stub_output(prompt: List[int], n: int) -> List[int]:
        """The deterministic "model": token i is a rolling digest of the
        prompt -- replica-independent, so routing never changes outputs."""
        acc = 1469598103  # FNV-ish seed
        for t in prompt:
            acc = (acc * 16777619 + int(t)) & 0x7FFFFFFF
        out = []
        for _ in range(n):
            acc = (acc * 16777619 + 13) & 0x7FFFFFFF
            out.append(acc % 50_000)
        return out

    def _prefill(self, req: Request, slot: int) -> int:
        return self.stub_output(req.prompt, len(req.output) + 1)[-1]

    def _launch(self) -> None:
        self._next = [None if r is None
                      else self.stub_output(r.prompt, len(r.output) + 1)[-1]
                      for r in self.slot_req]

    def _collect(self) -> List[Optional[int]]:
        return self._next
