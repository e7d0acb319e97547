"""ServeEngine: continuous batching over one shared slot-decode cache.

Lifecycle of a request:

  submit -> queue (FIFO) -> admit: allocate slot, jitted prefill
  (``prefill_with_cache``), insert the request cache into the slot row,
  first token from the prefill logits -> decode: ONE jitted step advances
  every live slot under an active mask -> finish (EOS / max tokens):
  free the slot; the next queued request reuses it.

Compile behaviour (the whole point of the design):

  * the decode step is traced ONCE per engine shape — the active mask and
    per-slot positions are traced operands, so slots finishing, joining,
    or wrapping never retrace; heterogeneous ``NumericsPolicy`` configs
    (per-layer searched policies, docs/numerics.md#policy-files) resolve
    per call site AT TRACE TIME inside that single step, so they add no
    compiles either (gated: tests/test_policy.py asserts
    ``_cache_size() == 1`` under a per-layer policy);
  * prefill compiles once per distinct prompt *length* (documented cost;
    callers pad/bucket prompts if they care);
  * the slot insert is one trace total (the slot index is a traced scalar).

Correctness invariant (gated by benchmarks/serve_bench.py in CI): for the
integer AMR modes — and exact, and even ``amr_noise`` thanks to per-slot
position PRNG folding — the token AND logit streams of a request decoded
in a busy engine are bit-identical to the same request served alone.

Fault wiring: an optional ``Heartbeat`` (runtime.fault) publishes
queue/slot/step progress for external watchdogs, and a
``StragglerMonitor`` flags decode steps slower than the running median —
a host-side stall (e.g. a paging device or a preempting neighbour) shows
up as flagged steps rather than silent p99 inflation.

Counters and spans: ``engine.counters`` (:class:`EngineCounters`) is the
one record of the engine's work — decode steps, seconds and tokens,
admissions with their host seconds, prefill tokens and compiles, queue
wait; ``stats()`` and the heartbeat payload are built from it, and
``counters.reset()`` zeroes it (after a warm-up).  Each phase is a host
span (``runtime.spans.span``) that a running JAX profiler records on the
device ops' clock:

  * ``serve.admit`` (``uid``, ``slot``, ``prompt_len``), one per request,
    holding ``serve.prefill`` (prompt upload and prefill dispatch;
    ``compiled=1`` when the prefill compiled), ``serve.insert`` and
    ``serve.first_token`` (the last logits' ``device_get`` and argmax);
  * ``serve.decode`` (``step``, ``active``), one per decode step, holding
    ``serve.decode.launch`` (building the step's inputs, dispatch),
    ``serve.decode.sync`` (the blocking ``device_get``) and
    ``serve.decode.emit`` (the per-slot bookkeeping, finishing, heartbeat).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_cache, prefill_with_cache
from repro.runtime.fault import Heartbeat, StragglerMonitor
from repro.runtime.spans import span
from repro.train.steps import make_serve_step

from .request import Completion, Request, RequestQueue
from .slots import SlotAllocator


def _insert_request(engine_cache, request_cache, slot):
    """Write a batch-1 prefill cache into slot row ``slot`` of the engine
    cache. Leaves are stacked (n_repeat, B, ...); scalar-position length
    leaves arrive as (n_repeat,) and gain the batch axis here."""

    def one(e, r):
        if r.ndim == e.ndim - 1:
            r = jnp.expand_dims(r, 1)
        return jax.lax.dynamic_update_slice_in_dim(e, r.astype(e.dtype), slot, axis=1)

    with jax.named_scope("kv.write"):
        return jax.tree.map(one, engine_cache, request_cache)


@dataclasses.dataclass
class EngineCounters:
    """Running totals of the engine's work since creation or ``reset()``.

    Seconds are host clock over work that ends in a ``device_get``, so they
    include the device time the host waited for."""

    decode_steps: int = 0
    decode_seconds: float = 0.0    # masked decode steps, dispatch to fetch
    decode_tokens: int = 0         # tokens produced by decode steps (not prefill)
    admitted: int = 0
    admit_seconds: float = 0.0     # admissions (``serve.admit``), to the first token
    prefill_tokens: int = 0
    prefill_compiles: int = 0
    queue_wait_seconds: float = 0.0  # sum over admissions of t_admit - t_submit

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, f.default)


class ServeEngine:
    """Continuous-batching greedy decoder with ``n_slots`` fixed slots."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int,
        capacity: int,
        record_logits: bool = False,
        heartbeat: Heartbeat | None = None,
        straggler: StragglerMonitor | None = None,
        log: Callable[[str], None] | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.capacity = capacity
        self.record_logits = record_logits
        self.queue = RequestQueue()
        self.slots = SlotAllocator(n_slots)
        self.heartbeat = heartbeat
        self.straggler = straggler if straggler is not None else StragglerMonitor()
        self._log = log or (lambda msg: None)

        self.cache = init_cache(cfg, n_slots, capacity, per_slot=True)
        self._active = np.zeros(n_slots, bool)
        self._next_tok = np.zeros(n_slots, np.int32)
        self._slot_req: list[Request | None] = [None] * n_slots
        self._slot_toks: list[list[int]] = [[] for _ in range(n_slots)]
        self._slot_logits: list[list] = [[] for _ in range(n_slots)]
        self.completions: list[Completion] = []
        self.counters = EngineCounters()

        self._prefill = jax.jit(
            partial(prefill_with_cache, cfg, capacity=capacity))
        self._decode = jax.jit(make_serve_step(cfg, with_logits=record_logits),
                               donate_argnums=(1,))
        self._insert = jax.jit(_insert_request, donate_argnums=(0,))

    @property
    def decode_seconds(self) -> float:
        return self.counters.decode_seconds

    @property
    def decode_tokens(self) -> int:
        return self.counters.decode_tokens

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> int:
        """Queue a request; returns its uid. Rejects requests that cannot
        fit the slot cache (prompt + generation exceeds capacity)."""
        need = len(req.prompt) + req.max_new_tokens
        if need > self.capacity:
            raise ValueError(
                f"request needs {need} cache positions "
                f"(prompt {len(req.prompt)} + max_new_tokens "
                f"{req.max_new_tokens}) but slot capacity is {self.capacity}")
        req.t_submit = time.monotonic()
        return self.queue.submit(req)

    # ---------------------------------------------------------- scheduler
    def run(self, max_steps: int | None = None) -> list[Completion]:
        """Drive admit/decode until the queue and all slots drain (or
        ``max_steps`` decode steps ran). Returns completions in uid order."""
        if self.heartbeat is not None:
            self.heartbeat.start()
        try:
            steps = 0
            while self.queue or self._active.any():
                self._admit()
                if self._active.any():
                    self._decode_once()
                    steps += 1
                    if max_steps is not None and steps >= max_steps:
                        break
        finally:
            if self.heartbeat is not None:
                self._beat()
                self.heartbeat.stop()
        return sorted(self.completions, key=lambda c: c.uid)

    def _beat(self) -> None:
        if self.heartbeat is None:
            return
        self.heartbeat.payload = {
            "step": self.counters.decode_steps,
            "admitted": self.counters.admitted,
            "active_slots": int(self._active.sum()),
            "queued": len(self.queue),
            "completed": len(self.completions),
        }
        # Flush immediately: the timer thread only re-writes the last
        # payload, so liveness on disk tracks scheduler progress, not the
        # heartbeat interval.
        self.heartbeat.beat()

    def _admit(self) -> None:
        """Admit queued requests into free slots, FIFO order."""
        c = self.counters
        while self.queue and self.slots.n_free:
            req = self.queue.pop()
            slot = self.slots.allocate()
            assert slot is not None
            req.t_admit = time.monotonic()
            with span("serve.admit", uid=req.uid, slot=slot, prompt_len=len(req.prompt)):
                with span("serve.prefill") as sp:
                    n_compiled = self._prefill._cache_size()
                    toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
                    logits, rcache = self._prefill(self.params, toks)
                    if self._prefill._cache_size() > n_compiled:
                        c.prefill_compiles += 1
                        sp.set_metadata(compiled=1)
                with span("serve.insert"):
                    self.cache = self._insert(self.cache, rcache, jnp.int32(slot))
                with span("serve.first_token"):
                    last = jax.device_get(logits[:, -1].astype(jnp.float32))[0]
                    first = int(np.argmax(last))
                req.t_first_token = time.monotonic()
                self._slot_req[slot] = req
                self._slot_toks[slot] = [first]
                self._slot_logits[slot] = [last] if self.record_logits else []
                self._active[slot] = True
                self._next_tok[slot] = first
                self._maybe_finish(slot)
                self._beat()
            c.admitted += 1
            c.admit_seconds += time.monotonic() - req.t_admit
            c.prefill_tokens += len(req.prompt)
            c.queue_wait_seconds += req.t_admit - req.t_submit

    def _decode_once(self) -> None:
        """One masked decode step for every live slot."""
        c = self.counters
        n_active = int(self._active.sum())
        with span("serve.decode", step=c.decode_steps + 1, active=n_active):
            with span("serve.decode.launch"):
                batch = {
                    "token": jnp.asarray(self._next_tok)[:, None],
                    "active": jnp.asarray(self._active),
                }
                t0 = time.monotonic()
                out = self._decode(self.params, self.cache, batch)
            with span("serve.decode.sync"):
                if self.record_logits:
                    next_tok, last_logits, self.cache = out
                    logits_host = jax.device_get(last_logits)
                else:
                    next_tok, self.cache = out
                    logits_host = None
                tok_host = jax.device_get(next_tok)  # blocks: true step time
                dt = time.monotonic() - t0
            with span("serve.decode.emit"):
                c.decode_steps += 1
                c.decode_seconds += dt
                c.decode_tokens += n_active
                if self.straggler.observe(c.decode_steps, dt):
                    self._log(f"[serve] step {c.decode_steps}: straggler "
                              f"({dt * 1e3:.1f}ms vs median "
                              f"{self.straggler.median() * 1e3:.1f}ms)")
                for slot in np.flatnonzero(self._active):
                    self._slot_toks[slot].append(int(tok_host[slot]))
                    if logits_host is not None:
                        self._slot_logits[slot].append(np.asarray(logits_host[slot]))
                    self._next_tok[slot] = int(tok_host[slot])
                    self._maybe_finish(slot)
                self._beat()

    # ------------------------------------------------------------ finish
    def _maybe_finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        toks = self._slot_toks[slot]
        reason = None
        if req.eos_id is not None and toks and toks[-1] == req.eos_id:
            reason = "eos"
        elif len(toks) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        self.completions.append(Completion(
            uid=req.uid, prompt=req.prompt, tokens=tuple(toks),
            finish_reason=reason, t_submit=req.t_submit, t_admit=req.t_admit,
            t_first_token=req.t_first_token, t_done=time.monotonic(),
            logits=self._slot_logits[slot] if self.record_logits else None))
        self._active[slot] = False
        self._next_tok[slot] = 0
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self._slot_logits[slot] = []
        self.slots.free(slot)

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {
            "completed": len(self.completions),
            "active_slots": int(self._active.sum()),
            "queued": len(self.queue),
            "stragglers": len(self.straggler.flagged),
            **dataclasses.asdict(self.counters),
        }
