"""Hecate training driver: the FSSDP control loop.

Per iteration (paper Fig. 5):
  1. predictor estimates next-iteration expert loads (sliding window, w=5);
  2. Algorithm 1 emits the materialization plan (runtime tables — no
     recompile);
  3. the jitted train step runs: spAG materializes the placement, tokens are
     dispatched to replicas, spRS (AD transpose) reduces gradients onto the
     owning shards, AdamW updates shard-resident optimizer state;
  4. observed per-layer expert counts feed back into the predictor;
  5. every ``resharding.interval`` steps Algorithm 2 re-shards the unified
     chunk buffer (cross-layer heterogeneous sharding) — the only data
     movement on the critical path, amortized (paper §4.3).

In-run elastic recovery (``repro.train.supervisor``): with a
``TrainSupervisor`` attached, device failure is a typed in-process event,
not a dead run.  The supervisor's per-step probe runs the heartbeat /
watchdog / straggler checks and drives this state machine::

    RUNNING --(heartbeat miss / straggler seen)--> DEGRADED
    DEGRADED --(beats return, stragglers clear)--> RUNNING
    RUNNING|DEGRADED --(loss declared)-----------> DeviceLossError
        caught by train_loop: shrink mesh to the surviving ep',
        roll back to the newest intact checkpoint
        (elastic_row_remap), rebuild the jitted step, replay the
        rolled-back batches from the in-memory replay buffer ----> SHRUNK
    SHRUNK --(fault cleared; next checkpoint boundary:
              grow back to the full ep via the inverse remap)---> RECOVERED
    RECOVERED --(next loss / straggler)----------> ... (cycle)

The shrink path reuses ``resume_train_state``'s mesh-shape-elastic
restore verbatim, so the continued trajectory is the SAME trajectory a
kill-and-restart elastic restore would produce (parity asserted in
tests/test_elastic_recovery.py).  A persistently slow device is
DE-WEIGHTED instead of declared dead: the supervisor's step-time EMA
publishes per-device speed weights that flow into
``schedule.heterogeneous_sharding(device_weights=)`` at the next reshard
(and into the calibration cost model), shrinking the straggler's expert
slot share proportionally.

Tracing: each iteration of ``train_loop`` is a ``hecate.step`` profiler
step with one ``jax.profiler.TraceAnnotation`` per phase (``hecate.batch``,
``.upload``, ``.reshard``, ``.plan``, ``.dispatch``, ``.publish``,
``.plan_ahead``, ``.readback``, ``.observe``, ``.callback``,
``.checkpoint``); ``HecateScheduler`` adds the children of ``hecate.plan``
(``.wait``, ``.alg1``, ``.tables``, ``.to_device``) and of
``hecate.observe`` (``hecate.calibrate``), and its worker thread's
``hecate.worker.alg1`` / ``.tables`` spans carry the step they plan for.
They cost nothing measurable without a profiler session.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import queue
import threading
import time
import warnings
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.common import faults
from repro.common.config import ModelConfig, TrainConfig
from repro.common.sharding import elastic_row_remap, remap_buffer_rows
from repro.core import moe as moe_core
from repro.core.placement import (MaterializationPlan, ShardingPlan,
                                  ep_materialization, homogeneous_sharding)
from repro.core.schedule import (LoadPredictor, ReshardingPolicy,
                                 sparse_materialization)
from repro.train import metrics as metrics_lib
from repro.train import step as step_lib
from repro.train.supervisor import DeviceLossError, TrainSupervisor


class TrainAbortError(RuntimeError):
    """Raised by ``train_loop`` when the consecutive-bad-step budget
    (``tc.max_bad_steps``) is exhausted.  ``state`` carries the training
    state AFTER rollback to the last intact checkpoint (or the live state
    when no checkpointing was configured), ``history`` the per-step
    records up to the abort, ``step`` the global step that aborted."""

    def __init__(self, msg: str, state=None, history=None, step: int = -1):
        super().__init__(msg)
        self.state = state
        self.history = history or []
        self.step = step


def placement_latency_safe(ctx, plan, loads, layer, device_weights=None):
    from repro.core.costs import placement_latency
    try:
        return placement_latency(ctx, plan, loads, layer,
                                 device_weights=device_weights)
    except Exception:
        return 0.0


def reshard_perm(old: ShardingPlan, new: ShardingPlan) -> np.ndarray:
    """perm[new_global_row] = old_global_row (identity on pad rows)."""
    rows = old.rows_per_device * old.num_devices
    perm = np.arange(rows, dtype=np.int32)
    perm[new.global_rows().reshape(-1)] = old.global_rows().reshape(-1)
    return perm


class _PlanWorker:
    """Single background DAEMON thread running plan-ahead jobs.

    Deliberately not a ``ThreadPoolExecutor``: its threads are non-daemon
    and ``concurrent.futures`` registers an atexit join, so a genuinely
    hung Alg-1 job would wedge interpreter shutdown even after the
    scheduler routed around it (``shutdown(wait=False)`` only makes the
    *call* non-blocking).  A daemon thread can simply be abandoned — a
    wedged job dies with the process instead of blocking its exit."""

    def __init__(self):
        self._q = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run,
                                        name="hecate-plan", daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, fn = item
            if not fut.set_running_or_notify_cancel():
                continue                # cancelled before it started
            try:
                fut.set_result(fn())
            except BaseException as e:
                fut.set_exception(e)

    def submit(self, fn) -> Future:
        fut = Future()
        self._q.put((fut, fn))
        return fut

    def stop(self) -> None:
        """Ask the thread to exit after the in-flight job (never blocks;
        a wedged job just leaves the daemon parked until process exit)."""
        self._q.put(None)


@dataclasses.dataclass
class HecateScheduler:
    """Owns the sharding plan, predictor, per-step materialization, the
    calibration stage (§4.2), and the PLAN-AHEAD thread.

    Calibration adaptation (DESIGN.md): under XLA's static graphs a plan
    cannot change mid-step (the paper re-plans after the gate, before
    dispatch).  We calibrate at the ITERATION BOUNDARY instead: when the
    freshly observed loads show the window-averaged plan would have lost
    more than ``calibration_margin`` of modeled latency vs a plan built on
    the latest loads, the next step uses the re-planned placement
    immediately (still zero recompiles — plans are runtime tables).

    Plan-ahead (``async_plan``, default on): Algorithm 1 is host-side
    numpy, so ``train_loop`` computes step i+1's plan on a background
    thread WHILE step i runs on-device — exactly the timeliness failure
    the paper pins on rearrangement systems (the plan is ready when the
    devices are, instead of serializing host planning between steps).
    ``plan_ahead()`` snapshots the predictor's current prediction and
    submits the Alg-1 greedy; ``plan()`` consumes the finished future.
    The prefetched plan is one observation stale (it cannot see the
    counts of the step still in flight) — within the paper's tolerance,
    since the predictor is a w=5 sliding-window mean and the calibration
    stage overrides the prefetch whenever the freshest loads disagree
    enough to matter.  Resharding invalidates the prefetch (the sharding
    it was planned against is gone).
    """

    cfg: ModelConfig
    ep: int
    t: int = 8                      # overlap degree (profiled in prod)
    impl: str = "ring"              # ring | a2a | dense | ep
    resharding: Optional[ReshardingPolicy] = None
    window: int = 5
    calibrate: bool = True
    calibration_margin: float = 0.05
    tokens_per_step: float = 0.0    # for the latency model; 0 = est later
    async_plan: bool = True         # plan step i+1 while step i runs
    plan_timeout_s: float = 30.0    # bound on joining a plan-ahead job

    def __post_init__(self):
        L = moe_core.num_moe_layers(self.cfg)
        E = self.cfg.moe.num_experts
        self.predictor = LoadPredictor(L, E, self.window)
        self.sharding = homogeneous_sharding(L, E, self.ep)
        self._calibrated: Optional[MaterializationPlan] = None
        self._last_plan: Optional[MaterializationPlan] = None
        self._executor = None
        self._pending = None        # (future, sharding identity)
        self._prefetched_tables = None
        self.calibration_events = 0
        self.plan_ahead_hits = 0
        # per-device speed weights from the supervisor's straggler probe
        # (None = all devices at full speed); refreshed by train_loop
        # each step, consumed at reshard and calibration time
        self.device_weights: Optional[np.ndarray] = None
        # degraded-mode accounting: background jobs that raised or hung
        # and were answered by the synchronous plan path instead
        self.plan_fallbacks = 0
        self._fallback_warned = False
        self._worker_poisoned = False   # a job hung; the worker is wedged

    # ---- plan-ahead machinery ----------------------------------------
    def _pool(self) -> _PlanWorker:
        if self._executor is None:
            self._executor = _PlanWorker()
        return self._executor

    def plan_ahead(self, step: Optional[int] = None) -> None:
        """Kick off computing the NEXT step's materialization plan — AND
        its runtime tables — on the background thread.  Call right after
        dispatching the train step: the Alg-1 greedy and the
        ``plan_tables`` build then overlap the device computation, leaving
        only the device transfer on the critical path.  The prediction is
        snapshotted on the caller's thread so the worker never races
        predictor updates.  ``step``, the global step the plan is for,
        tags the worker's ``hecate.worker.*`` trace spans."""
        if not self.async_plan or self.impl == "ep":
            return
        if self._pending is not None:       # one in flight is plenty
            return
        pred = self.predictor.predict()
        sh = self.sharding
        tag = {} if step is None else {"step": step}

        def job():
            # chaos sites (repro.common.faults): an armed exception/hang
            # here must degrade to synchronous planning, never kill the
            # training loop
            faults.fire("scheduler.plan_job")
            faults.fire("scheduler.plan_job_hang")
            with jax.profiler.TraceAnnotation("hecate.worker.alg1", **tag):
                plan = sparse_materialization(
                    sh, pred, t=self.t, m=self.cfg.moe.slots_per_device,
                    impl=self.impl)
            with jax.profiler.TraceAnnotation("hecate.worker.tables", **tag):
                return plan, moe_core.plan_tables(plan)

        self._pending = (self._pool().submit(job), sh)

    def _warn_fallback_once(self, msg: str) -> None:
        if not self._fallback_warned:
            self._fallback_warned = True
            warnings.warn(f"HecateScheduler: {msg}", RuntimeWarning,
                          stacklevel=3)

    def _take_pending(self):
        """Returns (plan, numpy tables) or None.

        DEGRADED MODE: a background job that raised is swallowed here
        (logged once, ``plan_fallbacks`` counted) and the caller falls
        back to the synchronous plan path — a planner bug costs one
        on-path Alg-1 run, never the training run.  The join is bounded
        by ``plan_timeout_s``: a HUNG job additionally poisons the
        single-thread worker (a running thread cannot be cancelled), so
        plan-ahead is disabled for the rest of this scheduler's life and
        every later plan is computed synchronously; ``close()`` will not
        block on the wedged job."""
        if self._pending is None:
            return None
        fut, sh = self._pending
        self._pending = None
        if sh is not self.sharding:         # resharded since — stale plan
            fut.cancel()
            return None
        try:
            return fut.result(timeout=self.plan_timeout_s)
        except _FutTimeout:
            self._worker_poisoned = True
            self.async_plan = False         # degrade: sync planning only
            self.plan_fallbacks += 1
            self._warn_fallback_once(
                f"plan-ahead job hung (> {self.plan_timeout_s:.1f}s); "
                "disabling plan-ahead and falling back to synchronous "
                "planning")
            return None
        except Exception as e:
            self.plan_fallbacks += 1
            self._warn_fallback_once(
                f"plan-ahead job failed ({e!r}); falling back to "
                "synchronous planning")
            return None

    def _drop_pending(self) -> None:
        """Discard a prefetched plan WITHOUT joining it — the worker may
        still be running (calibration overriding a large in-flight plan)
        and blocking on its result would put Alg 1 back on the critical
        path just to throw the answer away."""
        if self._pending is not None:
            self._pending[0].cancel()
            self._pending = None

    def close(self) -> None:
        """Release the plan-ahead worker (tests / clean shutdown).  Never
        blocks: the worker is a DAEMON thread (see ``_PlanWorker``), so a
        poisoned worker (hung job) is abandoned — it can wedge neither
        this call nor interpreter shutdown."""
        self._drop_pending()
        if self._executor is not None:
            self._executor.stop()
            self._executor = None

    # ---- planning ----------------------------------------------------
    def plan(self) -> MaterializationPlan:
        self._prefetched_tables = None
        if self.impl == "ep":
            # plan_ahead never submits for ep — nothing pending to drop
            plan = ep_materialization(self.sharding)
        elif self._calibrated is not None:
            # calibration saw the freshest loads — it beats the prefetch
            plan, self._calibrated = self._calibrated, None
            self._drop_pending()
        else:
            got = None
            if self._pending is not None:
                with jax.profiler.TraceAnnotation("hecate.plan.wait"):
                    got = self._take_pending()
            if got is not None:
                plan, self._prefetched_tables = got
                self.plan_ahead_hits += 1
            else:
                with jax.profiler.TraceAnnotation("hecate.plan.alg1"):
                    plan = sparse_materialization(
                        self.sharding, self.predictor.predict(), t=self.t,
                        m=self.cfg.moe.slots_per_device, impl=self.impl)
        self._last_plan = plan
        return plan

    def _plan_source(self) -> str:
        """Where the next ``plan()`` comes from, as known before it runs.
        A prefetch that turns out stale or failed still falls back to a
        synchronous Alg 1, which its ``hecate.plan.alg1`` span shows."""
        if self.impl != "ep" and self._calibrated is not None:
            return "calibrated"
        if self.impl != "ep" and self._pending is not None:
            return "prefetch"
        return "sync"

    def plan_arrays(self) -> moe_core.PlanArrays:
        """Device tables for the next step — from the plan-ahead thread's
        prefetched numpy tables when available (only the host->device
        transfer remains on the critical path)."""
        with jax.profiler.TraceAnnotation("hecate.plan",
                                          source=self._plan_source()):
            plan = self.plan()
            tables, self._prefetched_tables = self._prefetched_tables, None
            if tables is None:
                with jax.profiler.TraceAnnotation("hecate.plan.tables"):
                    tables = moe_core.plan_tables(plan)
            with jax.profiler.TraceAnnotation("hecate.plan.to_device"):
                return moe_core.tables_to_device(tables)

    def observe(self, counts: np.ndarray) -> None:
        counts = np.asarray(counts, np.float64)
        self.predictor.observe(counts)
        if (self.calibrate and self.impl in ("ring", "a2a")
                and self._last_plan is not None):
            with jax.profiler.TraceAnnotation("hecate.calibrate"):
                self._maybe_calibrate(counts)

    def _maybe_calibrate(self, real_loads: np.ndarray) -> None:
        from repro.core.costs import CostContext, calibration_gain
        tokens = self.tokens_per_step or float(real_loads[0].sum()
                                               / max(self.cfg.moe.experts_per_token, 1))
        ctx = CostContext(self.cfg, tokens_per_step=tokens)
        cand = sparse_materialization(
            self.sharding, real_loads, t=self.t,
            m=self.cfg.moe.slots_per_device, impl=self.impl)
        # evaluate on the most imbalanced layer (cheap, representative);
        # a layer whose tokens were ALL dropped has mean 0 — its
        # imbalance ratio is meaningless, not infinite, so rank it last
        # instead of dividing by zero
        means = real_loads.mean(1)
        ratio = np.where(means > 0,
                         real_loads.max(1) / np.maximum(means, 1e-12), 0.0)
        layer = int(np.argmax(ratio))
        base = placement_latency_safe(ctx, self._last_plan, real_loads,
                                      layer, self.device_weights)
        gain = calibration_gain(ctx, self._last_plan, cand, real_loads,
                                layer, device_weights=self.device_weights)
        if base > 0 and gain / base > self.calibration_margin:
            self._calibrated = cand
            self.calibration_events += 1

    def maybe_reshard(self, step: int):
        """Returns perm (np.ndarray) to apply to buffer rows, or None."""
        if self.resharding is None or self.impl in ("ep", "dense"):
            return None
        # hand the straggler weights to the policy (plain attribute set —
        # harmless on duck-typed test policies); drop weights whose length
        # no longer matches the mesh (stale across an elastic shrink)
        w = self.device_weights
        if w is not None and np.asarray(w).reshape(-1).shape[0] \
                != self.sharding.num_devices:
            w = None
        self.resharding.device_weights = w
        new, changed = self.resharding.maybe_reshard(
            step, self.sharding, self.predictor)
        if not changed:
            return None
        perm = reshard_perm(self.sharding, new)
        self.sharding = new                 # _take_pending sees the swap
        return perm


def apply_reshard(state: step_lib.TrainState, perm: np.ndarray
                  ) -> step_lib.TrainState:
    """Physically move chunk rows (params + optimizer moments) to their new
    owners.  jitted gather over the global row dim — GSPMD emits the
    required point-to-point collectives."""
    perm = jnp.asarray(perm)

    @jax.jit
    def go(params, opt):
        def move(tree):
            new = dict(tree)
            new["moe_buffer"] = jnp.take(tree["moe_buffer"], perm, axis=0)
            return new
        return move(params), opt._replace(mu=move(opt.mu), nu=move(opt.nu))

    new_params, new_opt = go(state.params, state.opt)
    return step_lib.TrainState(new_params, new_opt, state.step)


def _state_tree(state: step_lib.TrainState) -> Dict[str, Any]:
    """The checkpointed pytree: params + FULL optimizer state + step —
    everything an exact-resume needs (kill-and-resume parity ≤ 1e-5 is
    asserted in tests/test_fault_tolerance.py)."""
    return {"params": state.params, "opt": state.opt, "step": state.step}


def _sharding_tree(sh: ShardingPlan) -> Dict[str, np.ndarray]:
    """The persisted form of a ShardingPlan (see ``_sharding_from_tree``)."""
    return {"owner_dev": np.asarray(sh.owner_dev, np.int32),
            "owner_row": np.asarray(sh.owner_row, np.int32),
            "num_devices": np.int64(sh.num_devices),
            "rows_per_device": np.int64(sh.rows_per_device),
            "k_local": np.int64(sh.k_local)}


def _sharding_from_tree(shard: Dict[str, np.ndarray]) -> ShardingPlan:
    od = np.asarray(shard["owner_dev"], np.int32)
    plan = ShardingPlan(
        num_layers=od.shape[0], num_experts=od.shape[1],
        num_devices=int(shard["num_devices"]),
        rows_per_device=int(shard["rows_per_device"]),
        owner_dev=od, owner_row=np.asarray(shard["owner_row"], np.int32),
        k_local=int(shard["k_local"]))
    plan.validate()
    return plan


def save_train_state(tc: TrainConfig, gstep: int,
                     state: step_lib.TrainState,
                     scheduler: Optional[HecateScheduler] = None) -> None:
    """One crash-safe checkpoint: train state (atomic, checksummed) plus
    — when a scheduler is live and has planned — its predictor history,
    current plan tables AND current ShardingPlan via the serving-state
    path, then keep-last retention + orphaned-tmp GC for both.

    The ShardingPlan is load-bearing, not advisory: ``apply_reshard``
    physically permutes the checkpointed ``moe_buffer`` rows, so a resume
    that re-plans under a fresh homogeneous sharding would silently map
    experts to the wrong rows.  ``resume_train_state`` restores it (and
    refuses to resume a resharding-enabled run without it)."""
    store.save(tc.checkpoint_dir, gstep, _state_tree(state))
    if scheduler is not None and scheduler._last_plan is not None:
        calib = ({"load_history": np.stack(scheduler.predictor.history)}
                 if scheduler.predictor.history else None)
        store.save_serving_state(
            tc.checkpoint_dir, gstep,
            moe_core.plan_tables(scheduler._last_plan),
            version=gstep, calibration=calib,
            sharding=_sharding_tree(scheduler.sharding))
    if tc.keep_checkpoints > 0:
        store.gc(tc.checkpoint_dir, keep_last=tc.keep_checkpoints)
        store.gc(os.path.join(tc.checkpoint_dir, "serving"),
                 keep_last=tc.keep_checkpoints)


def _elastic_remap(cfg: ModelConfig, old_plan: ShardingPlan, ep: int):
    """Build the ``store.restore(remap=...)`` transform + the new
    ShardingPlan for a checkpoint saved under a different EP size.  The
    saved arrays are full host copies (the gather-to-host already
    happened at save time), so the re-layout is a pure numpy row gather
    on the CPU mirror; the device put inside ``store.restore`` is the
    reshard onto the new mesh."""
    new_plan = homogeneous_sharding(old_plan.num_layers,
                                    old_plan.num_experts, ep)
    rows = moe_core.buffer_rows(cfg, ep)
    src, valid = elastic_row_remap(old_plan, new_plan, out_rows=rows)
    remap = {"moe_buffer": lambda a: remap_buffer_rows(a, src, valid)}
    return remap, new_plan


def resume_train_state(cfg: ModelConfig, tc: TrainConfig,
                       scheduler: Optional[HecateScheduler] = None,
                       ep: int = 1,
                       counters: Optional[metrics_lib.RobustnessCounters]
                       = None):
    """Restore (TrainState, global_step) from the newest RESTORABLE
    checkpoint in ``tc.checkpoint_dir``.  The walk goes newest-first and
    skips (a) corrupt/truncated checkpoints — torn writes, bit rot, a
    crash mid-save — via the per-array checksum verification, and (b)
    checkpoints that verify but cannot restore today's tree (e.g. an
    old-format ``{params, opt_count}`` save from before full-state
    checkpointing), warning and falling back to the next-newest.

    MESH-SHAPE-ELASTIC: when the candidate's saved ShardingPlan was built
    for a different EP size than this process runs (``num_devices != ep``
    — detected from the plan record, never from array shapes, which can
    coincide across EP sizes with different row layouts), the chunk
    buffer AND its AdamW moments are re-laid-out row-by-row onto this
    run's homogeneous sharding before the restore
    (``common.sharding.elastic_row_remap``), so a trainer that lost
    devices resumes smaller — trajectory parity vs an unresized run is
    asserted in tests/test_serve_fleet.py.  ``counters`` (when given)
    records the event in ``elastic_restores``; a failed elastic re-layout
    (fault site ``restore.mesh_mismatch``) degrades to fresh init with a
    warning, never a crash.

    Also rehydrates the scheduler from the serving-state saved alongside:
    the load-predictor history (so the resumed run re-plans from the same
    window the killed run saw) and the ShardingPlan that was live at save
    time — or, after an elastic restore, the NEW plan the rows were
    re-laid-out onto.  The plan restore is a correctness requirement, not
    an optimization — a reshard physically permuted the checkpointed
    buffer rows, and a fresh scheduler's homogeneous sharding would
    silently train with the wrong expert-to-row mapping.  When resharding
    is enabled but the checkpoint carries no sharding record, resume is
    REFUSED (fresh init with a warning) rather than guessed.

    Returns (None, 0) when no restorable checkpoint exists."""
    if not os.path.isdir(tc.checkpoint_dir):
        return None, 0
    target = step_lib.init_state(cfg, jax.random.PRNGKey(tc.seed), ep)
    state = gstep = ss = elastic_plan = None
    for cand in reversed(store.list_steps(tc.checkpoint_dir)):
        if not store.verify_step(tc.checkpoint_dir, cand):
            continue                    # torn / bit-rotted — skip
        # the sharding record saved WITH this candidate defines its
        # buffer row layout — read it BEFORE restoring the arrays
        try:
            ss = store.restore_serving_state(tc.checkpoint_dir, step=cand)
        except store.CheckpointCorruptError:
            ss = None                   # params intact, serving state torn
        old_plan = remap = elastic_plan = None
        shard = (ss or {}).get("sharding") or {}
        if shard:
            try:
                old_plan = _sharding_from_tree(shard)
            except Exception:
                old_plan = None         # unreadable record: treat as none
        if old_plan is not None and old_plan.num_devices != ep:
            try:
                faults.fire("restore.mesh_mismatch",
                            (old_plan.num_devices, ep))
                remap, elastic_plan = _elastic_remap(cfg, old_plan, ep)
            except Exception as e:
                warnings.warn(
                    f"resume: mesh-shape-elastic restore of step {cand} "
                    f"(saved ep={old_plan.num_devices}, running ep={ep}) "
                    f"failed ({e!r}); starting fresh", RuntimeWarning)
                return None, 0
        try:
            data = store.restore(tc.checkpoint_dir, cand,
                                 _state_tree(target), remap=remap)
        except store.CheckpointCorruptError as e:
            warnings.warn(
                f"resume: checkpoint step {cand} is intact but not "
                f"restorable into the current train state ({e}); trying "
                f"an older one", RuntimeWarning)
            continue
        state = step_lib.TrainState(data["params"], data["opt"],
                                    data["step"])
        gstep = cand
        break
    if state is None:
        return None, 0
    if elastic_plan is not None:
        warnings.warn(
            f"resume: checkpoint step {gstep} was saved on ep="
            f"{int(old_plan.num_devices)}; chunk buffer + AdamW moments "
            f"re-laid-out onto ep={ep}", RuntimeWarning)
        if counters is not None:
            counters.elastic_restores += 1
    if scheduler is not None:
        shard = (ss or {}).get("sharding") or {}
        if elastic_plan is not None or shard:
            scheduler._drop_pending()   # planned against the old sharding
            scheduler.sharding = (elastic_plan if elastic_plan is not None
                                  else _sharding_from_tree(shard))
            scheduler._calibrated = None
            scheduler._last_plan = None
            scheduler._prefetched_tables = None
        elif (scheduler.resharding is not None
              and scheduler.impl not in ("ep", "dense")):
            warnings.warn(
                f"resume: checkpoint step {gstep} carries no sharding "
                f"plan but resharding is enabled — its buffer rows may "
                f"have been permuted by a reshard this process cannot "
                f"reconstruct; refusing to resume (fresh init)",
                RuntimeWarning)
            return None, 0
        hist = (ss or {}).get("calibration", {}).get("load_history")
        if hist is not None:
            scheduler.predictor.history = [np.asarray(h) for h in hist]
    return state, int(state.step)


def jit_train_step(cfg: ModelConfig, rt, tc: TrainConfig):
    """The jitted train step ``train_loop`` runs.  On a mesh the new state
    keeps the layout of ``step_lib.state_shardings``, so every step sees
    the input shardings the first one was compiled for."""
    fn = step_lib.build_train_step(cfg, rt, tc)
    mesh = getattr(rt, "mesh", None)
    if mesh is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=(step_lib.state_shardings(cfg, mesh),
                                      None))


def train_loop(cfg: ModelConfig, rt, tc: TrainConfig,
               stream: Iterable[Dict[str, np.ndarray]],
               *, scheduler: Optional[HecateScheduler] = None,
               train_step_fn: Optional[Callable] = None,
               state: Optional[step_lib.TrainState] = None,
               num_steps: Optional[int] = None,
               log_every: int = 10,
               callback: Optional[Callable] = None,
               metric_logger=None,
               publish_engine=None, publish_every: int = 0,
               supervisor: Optional[TrainSupervisor] = None):
    """Single-host training driver (used by examples + e2e tests).

    Planning runs OFF the critical path: the jitted step is dispatched
    asynchronously, and while the devices execute it the scheduler's
    background thread computes step i+1's materialization plan
    (``HecateScheduler.plan_ahead``) — the loop only blocks when it reads
    the step's metrics back.  ``plan_arrays()`` at the top of the next
    iteration then consumes the finished plan instead of serializing an
    Alg-1 run between steps (measured in benchmarks/planner_microbench.py).

    Training-while-serving: with ``publish_engine`` (a live
    ``repro.serve.engine.Engine`` — or a ``repro.serve.bus.
    PublicationBus`` fanning the same publications out to N replicas; the
    bus duck-types the engine surface, stages without blocking, and its
    per-replica failures are evictions counted here as fleet counters,
    never exceptions on this path) and ``publish_every = k``, the loop
    PUBLISHES the optimizer-updated parameter tree into the engine every k
    steps, versioned by the step index — right after dispatching the step,
    so the engine's background thread builds the new version's compute
    slots (the stacked SparseAllGather) while the devices are still
    executing and the engine swaps at its next decode-step boundary.
    Publication is entirely off this loop's critical path: the call only
    stages (it never builds slots or blocks on the engine).

    Fault tolerance (all knobs on ``tc``; counters in every history
    record — see ``repro.train.metrics.RobustnessCounters``):

    * **Skip policy** (``tc.step_guard``): a step whose loss or grad
      global norm is non-finite does NOT update params/optimizer state
      (bit-identical skip, fused into the jitted step — zero extra device
      syncs); the loop counts it (``skipped_steps``) and continues.
      After ``tc.max_bad_steps`` CONSECUTIVE bad steps the loop aborts
      with :class:`TrainAbortError`, first rolling ``.state`` back to the
      newest intact checkpoint when checkpointing is on (``rollbacks``).
    * **Crash-safe resume**: with ``tc.checkpoint_dir`` +
      ``tc.checkpoint_every``, the loop checkpoints params + full
      optimizer state + step atomically with per-array checksums, applies
      keep-last retention and orphaned-tmp GC (``store.gc``), and — when
      started without an explicit ``state`` and ``tc.auto_resume`` —
      resumes from the newest INTACT checkpoint: corrupt checkpoints are
      skipped, the stream is fast-forwarded by the restored step count so
      the data order matches an uninterrupted run, and the scheduler's
      predictor window AND ShardingPlan are rehydrated via the
      serving-state path (``resumes``) — the sharding restore keeps the
      physically-permuted (resharded) buffer rows consistent with future
      plans; a resharding-enabled run whose checkpoint lacks a sharding
      record starts fresh instead of guessing.  ``num_steps`` is the
      TOTAL step target: a run resumed at step k executes steps
      k..num_steps.
    * **Degraded modes**: a plan-ahead job that raises or hangs falls
      back to synchronous planning (``plan_fallbacks``; a hang also
      disables further plan-ahead — see ``HecateScheduler``); a closed or
      failing ``publish_engine`` never kills training — the failed
      publication is counted (``publish_drops``), a closed engine stops
      further publications, and the engine itself drops failed slot
      builds at its boundary without ever raising on the decode path.
    * **In-run elastic recovery** (``supervisor``, a
      ``repro.train.supervisor.TrainSupervisor``): the supervisor's probe
      runs after every step readback; on ``DeviceLossError`` the loop
      shrinks IN-PROCESS to the surviving ep' — new runtime from
      ``supervisor.runtime_factory``, state rolled back through the same
      ``resume_train_state`` mesh-shape-elastic path a kill-and-restart
      would take (trajectory parity by construction), jitted step
      rebuilt, and the rolled-back batches replayed from an in-memory
      replay buffer so the data order matches an uninterrupted run
      (``device_losses`` / ``elastic_shrinks``).  When the lost device
      rejoins (its fault site cleared), the loop GROWS BACK to the full
      ep at the next checkpoint boundary via the inverse row remap
      (``grow_backs``).  Publication versions are guarded monotone across
      rollbacks, so a live engine/bus never sees its version regress.
      The supervisor's straggler weights flow into the scheduler each
      step (``stragglers_deweighted``).  A loss below ``min_ep`` — or
      without a checkpoint to roll back from — aborts with
      :class:`TrainAbortError`.
    """
    num_steps = num_steps or tc.total_steps
    counters = metrics_lib.RobustnessCounters()
    start = 0
    if state is None and tc.checkpoint_dir and tc.auto_resume:
        state, start = resume_train_state(cfg, tc, scheduler,
                                          scheduler.ep if scheduler else 1,
                                          counters=counters)
        if state is not None:
            counters.resumes += 1
    if state is None:
        state = step_lib.init_state(cfg, jax.random.PRNGKey(tc.seed),
                                    scheduler.ep if scheduler else 1,
                                    mesh=getattr(rt, "mesh", None))
    if train_step_fn is None:
        train_step_fn = jit_train_step(cfg, rt, tc)
    history = []
    it = iter(stream)
    for _ in range(start):          # align data order with the killed run
        next(it)
    pending_replan = False          # reshard since the last publication?
    # publications are versioned by the GLOBAL training step (monotone
    # across resumed runs — a restored engine must never see its version
    # counter regress), not this loop's local index
    step_base = int(state.step)
    bad_streak = 0
    publish_warned = False
    loop_pub_failures = 0
    # engine/scheduler-side counters are read as deltas from here, so a
    # pre-used engine's or scheduler's history (e.g. a restart after
    # TrainAbortError) does not leak into this run's counters
    eng_drops0 = getattr(publish_engine, "publish_drops", 0) or 0
    eng_drops = 0
    # fleet counters exist when publish_engine is a PublicationBus; on a
    # bare Engine the getattr defaults keep every delta at 0
    _FLEET = ("replica_evictions", "replica_rejoins", "dedup_hits")
    fleet0 = {k: getattr(publish_engine, k, 0) or 0 for k in _FLEET}
    plan_fb0 = scheduler.plan_fallbacks if scheduler is not None else 0
    sup_dw0 = supervisor.deweight_events if supervisor is not None else 0
    # elastic recovery: keep the raw batches consumed since (a bit before)
    # the last checkpoint so a rollback can REPLAY them in order instead
    # of restarting the stream; `pending` holds batches queued for replay
    replay = deque(maxlen=max(2 * (tc.checkpoint_every or 1), 8)) \
        if supervisor is not None else None
    pending = deque()
    last_pub_version = 0            # monotone guard across rollbacks
    # one hecate.step trace span per iteration, from its start to the
    # next one's (or the loop's end); each phase has a span of its own
    step_span = contextlib.ExitStack()
    try:
        i = start
        while i < num_steps:
            gstep = step_base + (i - start) + 1     # global step AFTER i
            step_span.close()
            step_span.enter_context(jax.profiler.StepTraceAnnotation(
                "hecate.step", step_num=gstep))
            with jax.profiler.TraceAnnotation("hecate.batch"):
                raw = pending.popleft() if pending else next(it)
            if replay is not None:
                replay.append((i, raw))
            with jax.profiler.TraceAnnotation("hecate.upload"):
                batch = {k: jnp.asarray(v) for k, v in raw.items()}
            # chaos site: tests arm this with faults.poison_grads to make
            # THIS step's gradients NaN (see repro.common.faults)
            batch = faults.fire("train.nan_grads", batch)
            pa = None
            if scheduler is not None and cfg.moe.enabled:
                with jax.profiler.TraceAnnotation("hecate.reshard"):
                    if supervisor is not None:
                        scheduler.device_weights = \
                            supervisor.device_weights()
                    perm = scheduler.maybe_reshard(i)
                    if perm is not None:
                        state = apply_reshard(state, perm)
                        pending_replan = True
                pa = scheduler.plan_arrays()        # span: hecate.plan
            t0 = time.perf_counter()
            # async dispatch: the call returns with the step in flight
            with jax.profiler.TraceAnnotation("hecate.dispatch"):
                state, metrics = train_step_fn(state, batch, pa)
            if (publish_engine is not None and publish_every
                    and (i + 1) % publish_every == 0
                    # after an elastic rollback the replayed steps revisit
                    # old gsteps — never hand the engine a version it has
                    # already seen (its version counter must not regress)
                    and gstep > last_pub_version):
                # training-while-serving: stage the updated params into
                # the live engine, versioned by step.  The updated arrays
                # are still in flight — the engine's background build
                # dispatches against them asynchronously, and the swap
                # happens at the engine's next decode-step boundary.
                # After a reshard the engine's plan tables describe the
                # OLD row ownership — publish the fresh plan WITH the
                # params so they swap as one atomic pair.  A failing or
                # closed engine must not kill training: the publication
                # is dropped (counted), and a closed engine disables
                # further publications for this run.
                try:
                    with jax.profiler.TraceAnnotation("hecate.publish"):
                        if pending_replan and pa is not None:
                            publish_engine.publish_params(
                                state.params, version=gstep, pa=pa)
                            pending_replan = False
                        else:
                            publish_engine.publish_params(
                                state.params, version=gstep)
                    last_pub_version = gstep
                except Exception as e:
                    loop_pub_failures += 1
                    if not publish_warned:
                        publish_warned = True
                        warnings.warn(
                            f"train_loop: parameter publication failed "
                            f"({e!r}); training continues unpublished",
                            RuntimeWarning)
                    if getattr(publish_engine, "_closed", False):
                        publish_engine = None
            if (scheduler is not None and cfg.moe.enabled
                    and i + 1 < num_steps):
                # plan step i+1 while step i runs on-device
                with jax.profiler.TraceAnnotation("hecate.plan_ahead"):
                    scheduler.plan_ahead(step=gstep + 1)
            with jax.profiler.TraceAnnotation("hecate.readback"):
                metrics = jax.tree.map(np.asarray, metrics)  # blocks
            dt = time.perf_counter() - t0
            if supervisor is not None:
                try:
                    supervisor.probe(i, dt)
                except DeviceLossError as e:
                    counters.device_losses += len(e.lost)
                    new_ep = supervisor.ep - len(e.lost)
                    if new_ep < max(supervisor.min_ep, 1) \
                            or not tc.checkpoint_dir:
                        reason = (f"surviving ep={new_ep} would fall "
                                  f"below min_ep={supervisor.min_ep}"
                                  if tc.checkpoint_dir else
                                  "no checkpoint_dir to roll back from")
                        raise TrainAbortError(
                            f"unrecoverable device loss at global step "
                            f"{gstep} ({e}): {reason}",
                            state=state, history=history, step=gstep)
                    warnings.warn(
                        f"train_loop: {e} at global step {gstep}; "
                        f"shrinking in-process to ep={new_ep} and rolling "
                        f"back to the newest intact checkpoint",
                        RuntimeWarning)
                    rt_new = supervisor.runtime_factory(new_ep)
                    if scheduler is not None:
                        scheduler.ep = new_ep
                    rolled, rstep = resume_train_state(
                        cfg, tc, scheduler, new_ep, counters=counters)
                    if rolled is None:
                        raise TrainAbortError(
                            f"device loss at global step {gstep} ({e}) "
                            f"but no intact checkpoint to roll back to",
                            state=state, history=history, step=gstep)
                    i_resume = start + (rstep - step_base)
                    if replay and replay[0][0] > i_resume:
                        raise TrainAbortError(
                            f"device loss at global step {gstep} ({e}): "
                            f"replay buffer no longer covers rollback "
                            f"target step {i_resume} (oldest retained: "
                            f"{replay[0][0]})",
                            state=rolled, history=history, step=gstep)
                    # re-queue the rolled-back batches (oldest first),
                    # ahead of anything already pending from a previous
                    # rollback, and prune the replay window to match
                    tail = [r for idx, r in replay if idx >= i_resume]
                    kept = [(idx, r) for idx, r in replay
                            if idx < i_resume]
                    pending.extendleft(reversed(tail))
                    replay.clear()
                    replay.extend(kept)
                    history[:] = [h for h in history
                                  if h["step"] < i_resume]
                    state = rolled
                    rt = rt_new
                    train_step_fn = jit_train_step(cfg, rt, tc)
                    counters.elastic_shrinks += 1
                    supervisor.on_shrunk(new_ep,
                                         steps_lost=i - i_resume + 1)
                    bad_streak = 0
                    pending_replan = True
                    i = i_resume
                    continue
            if scheduler is not None and "expert_counts" in metrics:
                with jax.profiler.TraceAnnotation("hecate.observe"):
                    scheduler.observe(metrics["expert_counts"])
            # ---- step-health skip policy (rides the readback above) ----
            step_ok = float(metrics.get("step_ok", 1.0)) >= 0.5
            if not step_ok:
                counters.skipped_steps += 1
                bad_streak += 1
            else:
                bad_streak = 0
            if scheduler is not None:
                counters.plan_fallbacks = (scheduler.plan_fallbacks
                                           - plan_fb0)
            if supervisor is not None:
                counters.stragglers_deweighted = (
                    supervisor.deweight_events - sup_dw0)
            if publish_engine is not None:
                eng_drops = (getattr(publish_engine, "publish_drops", 0)
                             or 0) - eng_drops0
                for k in _FLEET:
                    setattr(counters, k,
                            (getattr(publish_engine, k, 0) or 0)
                            - fleet0[k])
            counters.publish_drops = loop_pub_failures + eng_drops
            rec = {"step": i, "loss": float(metrics["loss"]),
                   "xent": float(metrics["xent"]), "time_s": dt,
                   "step_ok": float(step_ok), **counters.as_dict()}
            if "dropped_frac" in metrics:
                rec["dropped_frac"] = float(metrics["dropped_frac"])
            if "pad_frac" in metrics:
                rec["pad_frac"] = float(metrics["pad_frac"])
            if metric_logger is not None:
                rec.update(metric_logger.log(i, {**metrics, "time_s": dt}))
            history.append(rec)
            if callback:
                with jax.profiler.TraceAnnotation("hecate.callback"):
                    callback(i, state, metrics)
            if bad_streak >= tc.max_bad_steps > 0:
                # budget exhausted: roll back to the last intact
                # checkpoint (params poisoned-in-flight are abandoned)
                # and surface the abort instead of training on garbage
                if tc.checkpoint_dir:
                    rolled, rstep = resume_train_state(
                        cfg, tc, scheduler,
                        scheduler.ep if scheduler else 1,
                        counters=counters)
                    if rolled is not None:
                        state = rolled
                        counters.rollbacks += 1
                        if history:
                            history[-1].update(counters.as_dict())
                tail = ("state rolled back to last intact checkpoint"
                        if counters.rollbacks
                        else "no checkpoint to roll back to")
                raise TrainAbortError(
                    f"aborting: {bad_streak} consecutive bad steps "
                    f"(tc.max_bad_steps={tc.max_bad_steps}) at global "
                    f"step {gstep}; {tail}",
                    state=state, history=history, step=gstep)
            if (tc.checkpoint_dir and tc.checkpoint_every
                    and step_ok and gstep % tc.checkpoint_every == 0):
                with jax.profiler.TraceAnnotation("hecate.checkpoint"):
                    save_train_state(tc, gstep, state, scheduler)
                if supervisor is not None and supervisor.can_grow_back():
                    # the lost device rejoined (its fault site cleared):
                    # grow back to the full ep at this checkpoint
                    # boundary — restore the JUST-SAVED step through the
                    # inverse elastic remap, so the row layout round-trips
                    # bit-exactly (the elastic_row_remap law) and no data
                    # or history rewinds.  A failed grow-back stays SHRUNK.
                    full_ep = supervisor.full_ep
                    shrunk_ep = supervisor.ep
                    try:
                        rt_new = supervisor.runtime_factory(full_ep)
                        if scheduler is not None:
                            scheduler.ep = full_ep
                        regrown, rstep = resume_train_state(
                            cfg, tc, scheduler, full_ep, counters=counters)
                        if regrown is None or rstep != gstep:
                            raise RuntimeError(
                                f"grow-back restore yielded step {rstep}, "
                                f"expected {gstep}")
                        state = regrown
                        rt = rt_new
                        train_step_fn = jit_train_step(cfg, rt, tc)
                        counters.grow_backs += 1
                        supervisor.on_grow_back()
                        pending_replan = True
                        warnings.warn(
                            f"train_loop: grew back to ep={full_ep} at "
                            f"global step {gstep}", RuntimeWarning)
                    except Exception as ge:
                        if scheduler is not None:
                            scheduler.ep = shrunk_ep
                            # a partial restore may have rehydrated the
                            # scheduler for the full mesh — re-restore at
                            # the ep we are actually still running
                            resume_train_state(cfg, tc, scheduler,
                                               shrunk_ep)
                        warnings.warn(
                            f"train_loop: grow-back to ep={full_ep} "
                            f"failed ({ge!r}); staying on ep="
                            f"{shrunk_ep}", RuntimeWarning)
            if log_every and i % log_every == 0:
                print(f"step {i:5d}  loss {rec['loss']:.4f}  "
                      f"xent {rec['xent']:.4f}  {dt*1e3:.0f} ms")
            i += 1
    finally:
        step_span.close()
        if scheduler is not None:
            # join the plan-ahead worker; the executor is re-created
            # lazily, so a scheduler reused across train_loop calls keeps
            # working
            scheduler.close()
    return state, history
