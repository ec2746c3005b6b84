"""Continuous-batching request scheduler over the block-paged KV cache —
designed robustness-first: every overload and straggler scenario has a
defined, tested, NON-CRASHING outcome.

The request state machine
-------------------------
Every :class:`Request` is in exactly one state::

                 submit()
                    │  (queue full / cannot ever fit → REJECTED)
                    ▼
    QUEUED ──(admitted: pages + token budget + watermark)──▶ PREFILL
      │                                                        │
      │ (TTL expired)                           (one-shot prefill via
      │                                          build_prefill_step, one
      ▼                                          (plan, version) snapshot)
    TIMED_OUT                                          │
                                  (prefill crashed > retry budget →
                                   REJECTED; else back to QUEUED)
                                                       ▼
                        ┌───────────────────────── DECODING ◀─┐
                        │                             │       │
              (TTL expired: pages freed)    (page-pool exhausted:
                        │                    YOUNGEST sequence is
                        ▼                    PREEMPTED — pages freed,
                   TIMED_OUT                 requeued at the queue head
                                             with prompt + generated so
                        ┌─────────────────┐  far — and re-prefills later)
                        ▼                 │
                      DONE (max_new reached / EOS)

Terminal states are exactly ``DONE | REJECTED | TIMED_OUT`` — an admitted
request is NEVER silently lost, and the decode path NEVER raises: overload
is always returned to the caller as a typed result on the request
(``state`` + ``finish_reason``).  The chaos soak in
tests/test_serve_batching.py arms ``serve.page_exhausted``,
``serve.request_hang`` and ``serve.prefill_crash`` in random order and
asserts exactly this invariant.

The overload policy
-------------------
* **Bounded queue** — ``submit`` beyond ``max_queue`` returns the request
  already REJECTED (``finish_reason="queue_full"``); a request whose
  prompt + budget can never fit the pool is REJECTED up front
  (``"too_long"``).  Preempted requests re-enter at the queue HEAD and do
  not count against the bound (they were already admitted once — dropping
  them would lose an admitted request).
* **Admission gate** — a queued request is admitted only when (1) a slot
  is free, (2) its prompt fits the per-tick ``prefill_token_budget``
  (the first admission of a tick is always allowed, so an oversized
  prompt cannot starve), and (3) allocating its prompt pages keeps the
  pool's free fraction at or above ``admit_free_frac`` while other
  sequences are running — headroom that lets RUNNING sequences grow
  instead of thrashing through preemption.
* **Preemption** — when a decoding sequence crosses a page boundary and
  the pool is exhausted, the YOUNGEST (most recently admitted) sequence
  is preempted: pages released, requeued at the head with its prompt
  extended by everything it already generated, so a later re-prefill
  resumes it losslessly.  The oldest active sequence therefore always
  makes progress — the scheduler degrades, it never livelocks.
* **Deadlines** — every request carries a TTL (``ttl_s``); expiry in any
  non-terminal state yields TIMED_OUT (pages freed, slot recycled).  A
  wedged request (``serve.request_hang``) stops advancing but keeps its
  slot only until its deadline.

Consistency with the publication protocol
-----------------------------------------
Prefill runs ONE-SHOT through ``serve.engine.build_prefill_step`` against
a single ``Engine._snapshot()`` — the same locked (params, plan, slots)
view a decode step takes — so a prefill that straddles a live publication
reads one consistent (plan, version) pair, never new params with old plan
tables.  Each decode tick takes its own snapshot, runs the engine's step
boundary, and batches ALL active sequences into one fixed-shape paged
decode step (``build_paged_serve_step``) that issues ZERO SparseAllGather
collectives with a fresh slot cache (jaxpr-asserted).

Backpressure out
----------------
The scheduler installs a load probe on its engine
(``Engine.attach_load_probe``), surfacing ``queue_depth`` and
``kv_used_frac`` through ``EngineHealth`` — ``PublicationBus.route()``
sorts healthy replicas by exactly this signal, so fleet routing places
new requests on the least-loaded replica.

Counters ``requests_rejected`` / ``requests_preempted`` /
``requests_timed_out`` mirror into ``RobustnessCounters``
(:meth:`RequestScheduler.robustness`).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import faults
from repro.serve.engine import (build_paged_serve_step, build_prefill_step,
                                _sample)
from repro.serve.kv_pool import KVPagePool, PageTable
from repro.models import model as mdl
from repro.train import metrics as metrics_lib

QUEUED = "QUEUED"
PREFILL = "PREFILL"
DECODING = "DECODING"
DONE = "DONE"
PREEMPTED = "PREEMPTED"
REJECTED = "REJECTED"
TIMED_OUT = "TIMED_OUT"

TERMINAL = frozenset({DONE, REJECTED, TIMED_OUT})


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle state.

    ``prompt`` is the CURRENT prompt (grows across preemptions so a
    re-prefill resumes losslessly); ``orig_prompt`` is what the caller
    submitted.  ``generated`` accumulates every sampled token across
    preemptions; ``output()`` is the caller-facing trace."""
    rid: int
    orig_prompt: np.ndarray
    max_new_tokens: int
    deadline: float
    prompt: np.ndarray = None
    state: str = QUEUED
    generated: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    preemptions: int = 0
    prefill_failures: int = 0
    admitted_seq: int = -1              # admission order (youngest = max)

    def __post_init__(self):
        if self.prompt is None:
            self.prompt = self.orig_prompt

    @property
    def done(self) -> bool:
        return self.state in TERMINAL

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.generated)

    def output(self) -> np.ndarray:
        """Prompt + everything generated, as one int32 trace."""
        return np.concatenate([self.orig_prompt,
                               np.asarray(self.generated, np.int32)])


class RequestScheduler:
    """Admit / prefill / batch-decode / evict individual sequences against
    one :class:`~repro.serve.engine.Engine` (see the module docstring for
    the state machine and overload policy).

    ``max_slots`` concurrent sequences share a ``num_pages``-page KV pool
    (page 0 reserved as the trash page idle slots park on).  ``max_kv``
    bounds any sequence's total length (prompt + generated) and fixes the
    decode step's shape; it defaults to the engine's ``max_len`` rounded
    up to a page multiple.
    """

    def __init__(self, engine, *, max_slots: int = 4, num_pages: int = 32,
                 page_size: int = 8, max_kv: Optional[int] = None,
                 max_queue: int = 16, default_ttl_s: float = 30.0,
                 prefill_token_budget: int = 2048,
                 admit_free_frac: float = 0.0, temperature: float = 0.0,
                 seed: int = 0, eos_id: Optional[int] = None,
                 max_prefill_retries: int = 1,
                 clock: Callable[[], float] = time.monotonic):
        self.engine = engine
        self.cfg, self.rt = engine.cfg, engine.rt
        assert not self.cfg.is_encoder_decoder, (
            "continuous batching does not support encoder-decoder models")
        self.pool = KVPagePool(num_pages, page_size)
        ps = page_size
        mk = max_kv if max_kv is not None else engine.max_len
        self.max_kv = -(-mk // ps) * ps             # page-aligned width
        self.max_slots = max_slots
        self.max_queue = max_queue
        self.default_ttl_s = default_ttl_s
        self.prefill_token_budget = prefill_token_budget
        self.admit_free_frac = admit_free_frac
        self.temperature = temperature
        self.eos_id = eos_id
        self.max_prefill_retries = max_prefill_retries
        self.clock = clock
        self._key0 = jax.random.PRNGKey(seed)
        # prompt padding buckets share compiled prefills; a recurrent
        # (mamba) layer consumes padding tokens into its state, so hybrid
        # archs prefill at exact length instead (one compile per length)
        self._pad_prompts = "mamba" not in self.cfg.layer_pattern

        # the jitted fns live on the ENGINE so their compile caches
        # survive scheduler churn — serving sessions come and go on a
        # long-lived engine, and a re-attach must not recompile.  The
        # paged step closes over page_size (static: the Pallas kernel's
        # KV tile is one pool page), so only a re-attach with a DIFFERENT
        # pool geometry rebuilds it.
        if (not hasattr(engine, "_paged_step_fn")
                or getattr(engine, "_paged_step_ps", None) != page_size):
            engine._paged_step_fn = jax.jit(
                build_paged_serve_step(self.cfg, self.rt,
                                       page_size=page_size))
            engine._paged_step_ps = page_size
        if not hasattr(engine, "_sched_prefill_fn"):
            engine._sched_prefill_fn = jax.jit(
                build_prefill_step(self.cfg, self.rt))
        self._step_fn = engine._paged_step_fn
        self._prefill_fn = engine._sched_prefill_fn
        self.cache = mdl.init_paged_cache(self.cfg, max_slots,
                                          self.pool.num_rows)

        self._queue: Deque[Request] = deque()
        self._slots: List[Optional[Request]] = [None] * max_slots
        self._tables: List[Optional[PageTable]] = [None] * max_slots
        self._positions = np.zeros(max_slots, np.int32)
        self._last_tok = np.zeros(max_slots, np.int32)
        self._row_idx = np.zeros((max_slots, self.max_kv), np.int32)
        self._next_rid = 0
        self._admit_seq = 0
        self._closed = False
        # overload counters (mirrored into RobustnessCounters)
        self.requests_rejected = 0
        self.requests_preempted = 0
        self.requests_timed_out = 0
        self.requests_completed = 0
        self.prefill_crashes = 0
        self.decode_ticks = 0
        engine.attach_load_probe(self._load)

    # ---- observability --------------------------------------------------
    def _load(self):
        """The EngineHealth load probe: (queue depth, KV occupancy)."""
        return len(self._queue), self.pool.used_frac

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def active(self) -> List[Request]:
        return [r for r in self._slots if r is not None]

    def robustness(self) -> metrics_lib.RobustnessCounters:
        """The scheduler's overload outcomes as RobustnessCounters."""
        return metrics_lib.RobustnessCounters(
            requests_rejected=self.requests_rejected,
            requests_preempted=self.requests_preempted,
            requests_timed_out=self.requests_timed_out)

    # ---- submission -----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               ttl_s: Optional[float] = None) -> Request:
        """Enqueue one request.  NEVER raises on overload: a full queue or
        an impossible-to-fit request comes back already REJECTED (typed
        result), everything else QUEUED."""
        if self._closed:
            raise RuntimeError("RequestScheduler is closed")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1, "empty prompt"
        req = Request(rid=self._next_rid, orig_prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      deadline=self.clock() + (ttl_s if ttl_s is not None
                                               else self.default_ttl_s))
        self._next_rid += 1
        total = prompt.size + max_new_tokens
        if (total > self.max_kv
                or self.pool.pages_for(total) > self.pool.usable_pages):
            self._reject(req, "too_long")
        elif len(self._queue) >= self.max_queue:
            self._reject(req, "queue_full")
        else:
            self._queue.append(req)
        return req

    def _reject(self, req: Request, reason: str) -> None:
        req.state = REJECTED
        req.finish_reason = reason
        self.requests_rejected += 1

    # ---- the scheduling tick -------------------------------------------
    def step(self) -> int:
        """One scheduler tick: reap deadlines, admit + prefill arrivals,
        run ONE batched paged decode step for every active sequence.
        Returns the number of sequences that advanced.  Never raises for
        any overload/fault condition — failures become typed request
        outcomes."""
        if self._closed:
            raise RuntimeError("RequestScheduler is closed")
        now = self.clock()
        self._reap(now)
        self._admit(now)
        return self._decode_tick()

    def run(self, max_ticks: Optional[int] = None) -> None:
        """Drive ticks until every submitted request is terminal (or
        ``max_ticks`` elapse).  Progress is guaranteed: the oldest active
        sequence always advances, and anything wedged is bounded by its
        TTL."""
        ticks = 0
        while max_ticks is None or ticks < max_ticks:
            pending = (self._queue or any(s is not None
                                          for s in self._slots))
            if not pending:
                return
            self.step()
            ticks += 1

    # ---- deadlines ------------------------------------------------------
    def _reap(self, now: float) -> None:
        for req in list(self._queue):
            if now > req.deadline:
                self._queue.remove(req)
                req.state = TIMED_OUT
                req.finish_reason = "ttl"
                self.requests_timed_out += 1
        for b, req in enumerate(self._slots):
            if req is not None and now > req.deadline:
                self._release_slot(b)
                req.state = TIMED_OUT
                req.finish_reason = "ttl"
                self.requests_timed_out += 1

    # ---- admission ------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for b, r in enumerate(self._slots):
            if r is None:
                return b
        return None

    def _alloc(self, n: int):
        """Pool allocation behind the ``serve.page_exhausted`` chaos site:
        an armed fault forces the exhausted outcome (None) — the policy
        reaction (wait / preempt) is exactly the real-exhaustion one, and
        nothing raises out of the scheduling path."""
        try:
            faults.fire("serve.page_exhausted")
        except Exception:
            return None
        return self.pool.alloc(n)

    def _admit(self, now: float) -> None:
        budget = self.prefill_token_budget
        admitted = 0
        while self._queue:
            b = self._free_slot()
            if b is None:
                return
            req = self._queue[0]
            p_len = int(req.prompt.size)
            if admitted and p_len > budget:
                return                  # token budget: next tick
            need = self.pool.pages_for(p_len + 1)   # +1: first decode write
            if (self.active() and self.pool.usable_pages
                    and (self.pool.free_pages - need) / self.pool.usable_pages
                    < self.admit_free_frac):
                return                  # watermark: leave growth headroom
            pages = self._alloc(need)
            if pages is None:
                return                  # exhausted: arrivals wait
            self._queue.popleft()
            budget -= p_len
            admitted += 1
            if not self._prefill(req, b, pages):
                continue                # crash path already re-queued it

    # ---- prefill --------------------------------------------------------
    def _bucket(self, n: int) -> int:
        if not self._pad_prompts:
            return n
        b = 8
        while b < n:
            b *= 2
        return b

    def _prefill(self, req: Request, slot: int, pages) -> bool:
        """One-shot prefill through one (plan, version) snapshot; scatter
        the prompt's K/V rows into the request's pages.  A crash
        (``serve.prefill_crash``) frees the pages and re-queues (bounded
        retries, then REJECTED) — it never propagates."""
        req.state = PREFILL
        p_len = int(req.prompt.size)
        try:
            faults.fire("serve.prefill_crash", req.rid)
            # ONE consistent (params, plan, slots) view — a prefill that
            # straddles a publication reads one (plan, version) pair
            params, pa, _ = self.engine._snapshot()
            pad = self._bucket(p_len)
            toks = np.zeros((1, pad), np.int32)
            toks[0, :p_len] = req.prompt
            batch = {"tokens": jnp.asarray(toks),
                     "last_pos": jnp.asarray([p_len - 1], np.int32)}
            logits, pcache = self._prefill_fn(params, batch, pa)
        except Exception:
            self.pool.free(pages)
            self.prefill_crashes += 1
            req.prefill_failures += 1
            if req.prefill_failures > self.max_prefill_retries:
                self._reject(req, "prefill_crash")
            else:
                req.state = QUEUED
                self._queue.appendleft(req)
            return False
        table = PageTable(self.pool.page_size, self.max_kv, pages)
        self._slots[slot] = req
        self._tables[slot] = table
        self._row_idx[slot] = table.row_idx()
        self._positions[slot] = p_len
        req.state = DECODING
        req.admitted_seq = self._admit_seq
        self._admit_seq += 1
        self._write_prompt_kv(slot, pcache, p_len)
        tok = self._sample(req, np.asarray(logits)[0, -1])
        self._last_tok[slot] = tok
        self._append(req, slot, tok)
        return True

    def _write_prompt_kv(self, slot: int, pcache, p_len: int) -> None:
        rows = jnp.asarray(self._row_idx[slot][:p_len])
        for j, kind in enumerate(self.cfg.layer_pattern):
            dst, src = self.cache[f"l{j}"], pcache[f"l{j}"]
            if kind == "mamba":     # O(1) state: dense per slot
                self.cache[f"l{j}"] = {
                    k: dst[k].at[:, slot].set(src[k][:, 0])
                    for k in dst}
            else:
                # prefill K/V (n_sb, 1, S, nkv, hd) -> the head-major
                # pool (n_sb, nkv, num_rows, hd)
                self.cache[f"l{j}"] = {
                    k: dst[k].at[:, :, rows].set(
                        src[k][:, 0, :p_len].swapaxes(1, 2))
                    for k in ("k", "v")}

    # ---- decode ---------------------------------------------------------
    def _sample(self, req: Request, logits_row) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        key = jax.random.fold_in(
            jax.random.fold_in(self._key0, req.rid), len(req.generated))
        return int(_sample(jnp.asarray(logits_row)[None],
                           self.temperature, key)[0])

    def _append(self, req: Request, slot: int, tok: int) -> None:
        req.generated.append(int(tok))
        if (req.remaining <= 0
                or (self.eos_id is not None and tok == self.eos_id)):
            self._release_slot(slot)
            req.state = DONE
            req.finish_reason = ("eos" if self.eos_id is not None
                                 and tok == self.eos_id else "length")
            self.requests_completed += 1

    def _release_slot(self, b: int) -> None:
        if self._tables[b] is not None:
            self.pool.free(self._tables[b].pages)
        self._slots[b] = None
        self._tables[b] = None
        self._positions[b] = 0
        self._last_tok[b] = 0
        self._row_idx[b] = 0            # park on the trash page

    def _youngest(self) -> Optional[int]:
        best, seq = None, -1
        for b, r in enumerate(self._slots):
            if r is not None and r.admitted_seq > seq:
                best, seq = b, r.admitted_seq
        return best

    def _preempt(self, b: int) -> None:
        """Release slot b's pages and requeue it at the head with its
        prompt extended by everything generated — lossless resume via a
        later re-prefill."""
        req = self._slots[b]
        self._release_slot(b)
        req.state = PREEMPTED
        req.preemptions += 1
        self.requests_preempted += 1
        req.prompt = np.concatenate(
            [req.orig_prompt, np.asarray(req.generated, np.int32)])
        req.state = QUEUED
        self._queue.appendleft(req)     # head: oldest-work-first

    def _ensure_pages(self) -> None:
        """Every active sequence's next write position must be paged.
        Pool exhausted → preempt the YOUNGEST sequence until the write
        fits (possibly preempting the writer itself — it requeues and
        resumes later)."""
        for b in range(self.max_slots):
            req = self._slots[b]
            if req is None:
                continue
            table = self._tables[b]
            while int(self._positions[b]) >= table.capacity:
                got = self._alloc(1)
                if got is not None:
                    table.pages.extend(got)
                    self._row_idx[b] = table.row_idx()
                    continue
                victim = self._youngest()
                self._preempt(victim)
                if victim == b:
                    break               # the writer itself was youngest

    def _decode_tick(self) -> int:
        self._ensure_pages()
        live = [b for b in range(self.max_slots)
                if self._slots[b] is not None]
        if not live:
            return 0
        # wedged requests (chaos site): an armed hang means "this request
        # makes no progress this tick" — it stays in its slot, recomputes
        # an idempotent KV write, and is eventually reaped by its TTL
        hung = set()
        for b in live:
            try:
                faults.fire("serve.request_hang", self._slots[b].rid)
            except Exception:
                hung.add(b)
        params, pa, premat = self.engine._snapshot()
        logits, self.cache = self._step_fn(
            params, self.cache, jnp.asarray(self._last_tok[:, None]),
            jnp.asarray(self._positions), jnp.asarray(self._row_idx),
            pa, premat)
        self.decode_ticks += 1
        lg = np.asarray(logits)
        advanced = 0
        for b in live:
            req = self._slots[b]
            if req is None or b in hung:
                continue
            self._positions[b] += 1
            tok = self._sample(req, lg[b, -1])
            self._last_tok[b] = tok
            self._append(req, b, tok)
            advanced += 1
        return advanced

    # ---- lifecycle ------------------------------------------------------
    def close(self) -> None:
        """Detach from the engine.  Queued/active requests stay in their
        current (non-terminal) states — the caller owns the decision to
        drain first."""
        if self._closed:
            return
        self._closed = True
        try:
            self.engine.attach_load_probe(None)
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
