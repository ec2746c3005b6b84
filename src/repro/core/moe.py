"""FSSDP MoE layer — sparse materialization, dispatch, compute, combine.

The compiled heart of the paper.  One flat *chunk buffer* holds every expert
of every MoE layer, fully sharded: rows (experts) over the ``model`` mesh
axis, the flattened parameter vector over the ``("pod","data")`` axes
(optimizer states share this layout — exactly one global copy, C1).

Per layer, inside a ``shard_map`` over the whole mesh:

  1. **SparseAllGather(P, P′)** materializes compute slots:
       * ``k_local`` owned slots — local buffer rows (no model-axis comm),
       * ``m`` extra slots — replicas fetched across the ``model`` axis by
         one of three interchangeable impls:
           - ``ring``  : one `ppermute` per slot over a static ring offset;
                         per-device volume = m·chunk — the paper's λS bound,
                         hit exactly (beyond-paper optimization),
           - ``a2a``   : one `all_to_all` per slot (paper-faithful
                         upper-bound schedule; robust to any ownership),
           - ``dense`` : all-gather everything (the FSDP baseline §2.4),
       followed by an all-gather of the slot chunks over ``("pod","data")``
       (the *fully sharded* half of FSSDP — FSDP-style, overlappable).
  2. Token **dispatch** to replica devices (local-first, then round-robin —
     §4.4) through a single capacity-bounded `all_to_all`.
  3. Grouped expert FFN over the K compute slots (Pallas grouped-GEMM kernel
     or XLA batched matmul).
  4. Combine back (reverse `all_to_all`), weighted by gate probabilities.

**SparseReduceScatter(P′, P) is the AD transpose of step 1** — reverse
ppermute/all_to_all + scatter-add onto the owning rows; JAX derives it, and
tests check it against the dense reference gradient.

Hot path
--------
The compiled layer body is tuned around three costs (see
``benchmarks/dispatch_microbench.py`` for measurements):

* **Sort-based dispatch.**  Per-expert arrival ranks, destinations, cell
  positions and per-slot group sizes all come from ONE stable argsort of
  the flat (T·k,) assignments (``segment_ranks`` / ``replica_dispatch``)
  — O(T·k log T·k) time, O(T·k) memory, replacing the O(T·k·E) +
  O(T·k · M·K) one-hot/cumsum tensors the naive formulation builds.  No
  second sort is needed for positions: each cell holds one expert whose
  entries arrive at a fixed destination in a strict cycle.
* **Batched sparse collectives.**  ``_materialize`` issues ONE stacked
  (M, m, chunk) all_to_all for the a2a impl (previously m sequential
  (M, chunk) calls) and a single batched row-gather + m data-independent
  single-hop ppermutes for the ring impl (a collective-permute op carries
  exactly one source→target map per offset, so ring keeps m ops — but with
  no dependence between them they overlap, and the λS = m·chunk volume is
  unchanged).  On the CPU backend batching auto-disables (XLA's host
  collective emulation degrades with message size; same wire volume).
  Materialization is issued BEFORE the gate so its collectives overlap
  with gate + dispatch arithmetic (§4.2).
* **Validity-aware compute, forward AND backward, with no compaction
  copies.**  The kept-token counts fall out of the dispatch sort for free
  and ride a tiny (M, K) int all_to_all to the receiving device.  The
  dispatch lands each source device's kept tokens in a valid *prefix* of
  its capacity stripe, so per-row validity of the (K, M·C, D) compute
  buffer is pure metadata: ``row_valid[k, r·C + i] = i < recv_cnt[r, k]``.
  That mask goes straight into the Pallas grouped GEMM
  (``repro.kernels.grouped_mlp``), whose forward, dgrad and wgrad kernels
  all skip token tiles containing no valid row (a per-tile count table
  rides the kernels' scalar-prefetch operand).  The previous formulation
  compacted valid rows into one prefix with a ``take_along_axis`` gather
  before the kernel and scattered back after it — two full (K, T, D)
  copies per layer per direction (four counting AD transposes); both are
  gone, and the backward is two Pallas kernels (dgrad + wgrad reducing
  only valid token tiles into f32 VMEM accumulators) instead of dense XLA
  einsums over the padded buffers — in training the backward is ~2x the
  forward FLOPs, so this is where most of the padding skip pays off.

Pipelined materialization (§4.2), re-materialization (§4.3), and the
overlap-complete training step
--------------------------------------------------------------------
In training, step 1 is software-pipelined ONE LAYER AHEAD of steps 2–4:
the model's superblock scan (``repro.models.model.forward``) carries the
next MoE layer's prefetched compute slots.  A warm-up
``materialize_layer`` builds layer 0's slots before the scan; each scan
step then issues layer l+1's SparseAllGather (ring/a2a over the EP axis +
the FSDP-axis all-gather) BEFORE layer l's grouped-GEMM consumer and
feeds layer l the slots prefetched one step earlier via
``moe_layer(premat=...)``.  The materialization collectives therefore
overlap the whole of the previous layer's attention + gate + dispatch +
FFN compute instead of only the thin gate in front of their own FFN.
Peak cost: TWO layers' (M, K, chunk_len) slots are live at the pipeline
boundary instead of one.

**Step-level reuse (gradient accumulation).**  Under ``tc.microbatch``
the gathers are HOISTED out of the accumulation loop entirely:
``materialize_stack`` builds all L layers' slots once at the step head
(one stacked traceable shard_map) and every microbatch's forward consumes
them through ``forward(premat=...)`` — L SparseAllGathers per accumulated
step instead of L·n, jaxpr-asserted in tests/test_step_overlap.py.  In
"save" mode the hoisted slots are ONE shared residual set instead of n
(the scan sums the per-microbatch chunk cotangents; a single
``jax.linear_transpose`` of the stacked gather — the stacked
SparseReduceScatter — lands the sum on the owning shards once per step).

What the backward does about the materialized chunks is
``cfg.moe.rematerialize``:

* ``"save"``   — each layer's chunks are kept as AD residuals (the values
  are checkpoint-named ``moe_materialized`` at their producer); the
  backward issues no materialization collectives.  Fastest backward,
  highest chunk memory (L layers of K·chunk_len per device).
* ``"gather"`` — TRUE re-materialization via a custom VJP: residuals are
  only (x, wr, buf, plan) — no chunk residuals AND no dispatch/FFN
  intermediates — and the backward re-acquires the slots from the live
  sharded buffer, re-runs the layer under ``jax.vjp``, and lands the
  buffer gradient through the SparseReduceScatter (the gather's linear
  transpose).  The forward prefetch is consumed through a
  ``stop_gradient`` so the pipeline's producer is never transposed.  With
  ``cfg.moe.bwd_prefetch`` (default) the re-gathers form an EXPLICIT
  backward pipeline (``moe_layer_regather_pipelined``), the structural
  mirror of the forward one: layer l's backward consumes slots
  re-gathered one backward step earlier and issues layer l−1's re-gather
  BEFORE its own dgrad/wgrad kernels (jaxpr-asserted ordering; the slots
  travel as the cotangent of a chunk-shaped pipe channel threaded
  through the forward), with each layer's SparseReduceScatter trailing
  its kernels off the critical path.  ``bwd_prefetch=False`` keeps the
  legacy schedule (each VJP gathers its own slots at its head and relies
  on the async scheduler to hoist them).
* ``"block"``  — the whole superblock reruns under ``nothing_saveable``.
  Minimum memory, maximum recompute; the cross-layer pipeline is forced
  OFF in this mode (a carried prefetch would be stored as a scan residual,
  defeating the point).

**Planning off the critical path.**  The tables all of this consumes are
host-side numpy (zero recompiles); ``HecateScheduler.plan_ahead`` runs
Algorithm 1 + the ``plan_tables`` build for step i+1 on a background
thread while step i executes on-device (the algorithms themselves are
vectorized — see ``repro.core.schedule`` and
benchmarks/planner_microbench.py), so ``train_loop`` blocks only on the
host→device table transfer between steps.

Decode reuse and training-while-serving
---------------------------------------
``materialize_chunks`` runs step 1 alone for every MoE layer — ONE
stacked jitted shard_map call over the layer dim — and returns the
stacked compute-slot chunks; ``moe_layer(..., premat=...)`` then skips
the SparseAllGather entirely.  Between decode steps the plan (and the
buffer) is unchanged, so the serving engine materializes once per
(plan, buffer version) pair and reuses the slots every step.  Buffer
identity is the ``VersionedBuffer`` handle: a trainer publishing updated
parameters into a live engine bumps the publication epoch, and
``materialize_chunks`` memoizes built slots under (buffer version, plan
token) so re-requesting an already-built pair issues zero collectives.
``serve.Engine`` double-buffers BOTH dimensions — ``set_plan`` stages the
next plan's slots, ``publish_params`` the next version's (built on a
background thread, overlapping in-flight decode steps) — and swaps the
whole (plan, params, version, slots) state at a decode step boundary
(see repro/serve/engine.py for the state machine).

Trace names
-----------
The layer's device work carries ``jax.named_scope`` names, which the
compiler keeps in each operation's ``op_name`` metadata: ``gate``,
``dispatch`` (sort dispatch, capacity, the scatter into slots and the
token all-to-all), ``expert_ffn``, ``combine``, and ``spag`` around every
SparseAllGather.  The gather's AD transpose, spRS, reads as
``transpose(...spag...)``; the explicit transposes of the backward
re-gather and of the step-level stacked gather are scoped ``sprs``.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.config import ModelConfig
from repro.common.params import Param
from repro.core.placement import MaterializationPlan


# ---------------------------------------------------------------------------
# Versioned buffer handle (training-while-serving)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)   # identity eq/hash: holds
class VersionedBuffer:                          # an unhashable device array
    """The sharded chunk buffer plus a monotone publication epoch.

    FSSDP keeps the sharded buffer as the single source of truth for every
    MoE parameter, which is exactly what lets a decode engine serve from
    the same buffer a trainer is updating — provided consumers can tell
    WHICH buffer state their derived artifacts (the materialized compute
    slots) came from.  Object identity is not enough: a donated/updated
    buffer may reuse storage, and a restored buffer is a fresh object with
    old contents.  The epoch counter is that identity: the trainer bumps
    it on every publication, ``materialize_chunks`` keys its slot-result
    memo on it, and ``serve.Engine`` swaps (plan, version) pairs at decode
    step boundaries.

    Every ``materialize_*`` entry point accepts either a raw array or a
    handle; wrapping costs nothing on the training path.
    """
    array: Any
    version: int = 0

    def bump(self, new_array) -> "VersionedBuffer":
        """Next publication: new contents, epoch + 1."""
        return VersionedBuffer(new_array, self.version + 1)


def unwrap_buffer(buf) -> Tuple[Any, Optional[int]]:
    """(array, version) — version is None for raw (unversioned) arrays."""
    if isinstance(buf, VersionedBuffer):
        return buf.array, buf.version
    return buf, None


# ---------------------------------------------------------------------------
# Parameter layout
# ---------------------------------------------------------------------------
def n_mats(cfg: ModelConfig) -> int:
    return 3 if cfg.act.endswith("_glu") else 2


def chunk_len(cfg: ModelConfig) -> int:
    return n_mats(cfg) * cfg.d_model * cfg.moe.d_ff


def num_moe_layers(cfg: ModelConfig) -> int:
    return sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))


def buffer_rows(cfg: ModelConfig, ep: int) -> int:
    """Global rows (padded so every device owns the same count)."""
    per_dev = -(-num_moe_layers(cfg) * cfg.moe.num_experts // ep)
    return per_dev * ep


def moe_buffer_param(cfg: ModelConfig, ep: int) -> Param:
    return Param((buffer_rows(cfg, ep), chunk_len(cfg)),
                 ("expert", "expert_ff"), init="normal")


def router_param(cfg: ModelConfig) -> Param:
    # stacked over MoE layers; REPLICATED — it is tiny (d×E) and sharding
    # its d_model dim makes GSPMD all-gather the full token tensor for the
    # gate einsum (seen in dry-run HLO: 8.6 GB f32 gathers).
    return Param((num_moe_layers(cfg), cfg.d_model, cfg.moe.num_experts),
                 ("layers", None, None), init="scaled")


def unpack_chunks(cfg: ModelConfig, chunks: jnp.ndarray):
    """chunks: (K, chunk_len) -> (wi, wg|None, wo) with shapes
    (K,d,f), (K,d,f), (K,f,d)."""
    d, f = cfg.d_model, cfg.moe.d_ff
    k = chunks.shape[0]
    if n_mats(cfg) == 3:
        wi = chunks[:, :d * f].reshape(k, d, f)
        wg = chunks[:, d * f:2 * d * f].reshape(k, d, f)
        wo = chunks[:, 2 * d * f:].reshape(k, f, d)
        return wi, wg, wo
    wi = chunks[:, :d * f].reshape(k, d, f)
    wo = chunks[:, d * f:].reshape(k, f, d)
    return wi, None, wo


def pack_expert(cfg: ModelConfig, wi, wg, wo) -> jnp.ndarray:
    parts = [wi.reshape(-1)] + ([wg.reshape(-1)] if wg is not None else []) \
        + [wo.reshape(-1)]
    return jnp.concatenate(parts)


# ---------------------------------------------------------------------------
# Plan -> device arrays
# ---------------------------------------------------------------------------
class PlanArrays(NamedTuple):
    """Per-MoE-layer tables fed to the jitted step (leading dim = L_moe)."""
    local_rows: jnp.ndarray      # (L, M, k_local) int32
    local_experts: jnp.ndarray   # (L, M, k_local) int32 (-1 pad)
    extra_experts: jnp.ndarray   # (L, M, m) int32 (-1 pad)
    ring_send_rows: jnp.ndarray  # (L, M, m) int32
    expert_slot: jnp.ndarray     # (L, M, E) int32 (-1 = absent)
    replicas: jnp.ndarray        # (L, E, r_max) int32
    n_replicas: jnp.ndarray      # (L, E) int32
    owner_dev: jnp.ndarray       # (L, E) int32
    owner_row: jnp.ndarray       # (L, E) int32


def plan_tables(plan: MaterializationPlan, r_max: int = 0) -> PlanArrays:
    """The host-side (numpy) half of ``plan_to_arrays``: derive every
    runtime table from the plan.  Split out so the scheduler's plan-ahead
    thread can build the tables off the critical path — only the device
    transfer is left for the consuming step."""
    sh = plan.sharding
    r_max = r_max or max(1, plan.m + 1)
    slot_expert, expert_slot = plan.slot_tables()
    replicas, n_rep = plan.replica_tables(r_max, slot_expert)
    return PlanArrays(
        local_rows=plan.local_rows, local_experts=plan.local_experts,
        extra_experts=plan.extra_experts,
        ring_send_rows=plan.ring_send_rows, expert_slot=expert_slot,
        replicas=replicas, n_replicas=n_rep,
        owner_dev=sh.owner_dev, owner_row=sh.owner_row)


def plan_to_arrays(plan: MaterializationPlan, r_max: int = 0) -> PlanArrays:
    return tables_to_device(plan_tables(plan, r_max))


def tables_to_device(tables: PlanArrays) -> PlanArrays:
    return PlanArrays(*[jnp.asarray(a, jnp.int32) for a in tables])


def plan_arrays_specs(mesh: Mesh, ep_axis: str = "model") -> PlanArrays:
    """shard_map in_specs for a single layer's slice of PlanArrays."""
    s = P(ep_axis)          # tables indexed by device on dim 0
    r = P()                 # replicated
    return PlanArrays(local_rows=s, local_experts=s, extra_experts=r,
                      ring_send_rows=s, expert_slot=r, replicas=r,
                      n_replicas=r, owner_dev=r, owner_row=r)


def abstract_plan_arrays(cfg: ModelConfig, ep: int, m: int, k_local: int,
                         r_max: int = 0) -> PlanArrays:
    L, E = num_moe_layers(cfg), cfg.moe.num_experts
    r_max = r_max or max(1, m + 1)
    sds = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    return PlanArrays(
        local_rows=sds((L, ep, k_local)), local_experts=sds((L, ep, k_local)),
        extra_experts=sds((L, ep, m)), ring_send_rows=sds((L, ep, m)),
        expert_slot=sds((L, ep, E)), replicas=sds((L, E, r_max)),
        n_replicas=sds((L, E)), owner_dev=sds((L, E)), owner_row=sds((L, E)))


class MoEAux(NamedTuple):
    counts: jnp.ndarray          # (E,) f32 global token counts this layer
    aux_loss: jnp.ndarray        # scalar load-balance loss
    z_loss: jnp.ndarray          # scalar router z-loss
    dropped_frac: jnp.ndarray    # scalar fraction of (token,k) dropped
    device_loads: jnp.ndarray    # (M,) real tokens processed per EP device
                                 # (the straggler observable, §1)
    pad_frac: jnp.ndarray        # scalar fraction of expert-compute rows
                                 # that are padding (what group_sizes lets
                                 # the grouped GEMM skip)


# ---------------------------------------------------------------------------
# Gate (GShard top-k) — runs under GSPMD, outside the shard_map region
# ---------------------------------------------------------------------------
def gate(cfg: ModelConfig, wr: jnp.ndarray, x: jnp.ndarray,
         valid: jnp.ndarray, psum_axes=None):
    """x: (T, D); valid: (T,) bool.  Returns (idx:(T,k), vals:(T,k) f32,
    counts:(E,), aux_loss, z_loss).  With ``psum_axes`` (inside shard_map)
    the statistics are globalized with a single (E,)+scalars psum."""
    with jax.named_scope("gate"):
        k = cfg.moe.experts_per_token
        e = cfg.moe.num_experts
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            wr.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, k)
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
        vals = vals * valid[:, None]
        # per-expert token counts by scatter-add — the same trick the
        # dispatch sort uses: the one-hot formulation materialized an
        # O(T·k·E) tensor (the last one on the hot path); invalid entries
        # land in an overflow bucket that is sliced off
        cell = jnp.where(valid[:, None], idx, e).reshape(-1)
        counts = jnp.zeros((e + 1,), jnp.float32).at[cell].add(1.0)[:e]
        prob_sum = (probs * valid[:, None]).sum(0)                # (E,)
        # the scalar statistics stay RANK-1 through the psum and
        # divisions: shard_map's linearize-time partial eval on this jax
        # version assigns residuals a leading device-axis spec that a
        # rank-0 value cannot carry, breaking the AD transpose of the
        # layer whenever the gate stats are differentiated (aux/z-loss in
        # the training objective)
        n_valid = valid.sum(keepdims=True).astype(jnp.float32)    # (1,)
        z_sum = jnp.sum((jax.nn.logsumexp(logits, axis=-1) ** 2) * valid,
                        keepdims=True)                            # (1,)
        if psum_axes is not None:
            counts, prob_sum, n_valid, z_sum = jax.lax.psum(
                (counts, prob_sum, n_valid, z_sum), psum_axes)
        n_valid = jnp.maximum(n_valid, 1.0)
        # GShard aux: E * sum_e frac_e * mean_prob_e
        frac = counts / jnp.maximum(counts.sum(), 1.0)
        mean_prob = prob_sum / n_valid
        aux = e * jnp.sum(jax.lax.stop_gradient(frac) * mean_prob[None, :],
                          keepdims=True).reshape(1)
        z = z_sum / n_valid
        return idx, vals, counts, aux[0], z[0]


# ---------------------------------------------------------------------------
# SparseAllGather inside shard_map
# ---------------------------------------------------------------------------
def _materialize(cfg: ModelConfig, buf, pa: PlanArrays, impl: str,
                 ep_axis: str, fsdp_axes, m: int, batch: bool = True):
    """buf: (rows_local, chunk_loc).  Returns (K, chunk_len) full chunks.

    pa fields here are the PER-LAYER slices with the shard_map-local shapes:
    local_rows (1,k_local), ring_send_rows (1,m), extra_experts (M,m), ...

    With ``batch`` (the accelerator default) the collectives are BATCHED:
    the a2a impl issues one stacked (M, m, chunk) all_to_all instead of m
    sequential (M, chunk) calls; the ring impl gathers all m outgoing rows
    with a single take and issues m data-independent single-hop ppermutes
    (one collective-permute op can carry only one source→target map, so
    the m distinct ring offsets cannot fuse further — but with no
    dependence between them they overlap, and the per-device λS = m·chunk
    volume is unchanged).  ``batch=False`` keeps the m-round sequential
    schedule: XLA's CPU host-collective emulation degrades sharply with
    message size (measured 2–7x in benchmarks/dispatch_microbench.py), so
    the CPU backend prefers it; wire volume is identical either way.
    """
    me = jax.lax.axis_index(ep_axis)
    M = jax.lax.axis_size(ep_axis)
    local_rows = pa.local_rows[0]                 # (k_local,)
    owned = jnp.take(buf, local_rows, axis=0)     # (k_local, chunk_loc)
    owned = owned * (pa.local_experts[0][:, None] >= 0).astype(buf.dtype)
    slots = [owned]
    if impl == "ring" and m > 0:
        if batch:
            send = jnp.take(buf, pa.ring_send_rows[0], axis=0)  # (m, chunk)
        else:
            send = None
        got = []
        for j in range(m):
            chunk = send[j:j + 1] if batch else jax.lax.dynamic_slice_in_dim(
                buf, pa.ring_send_rows[0, j], 1, axis=0)
            got.append(jax.lax.ppermute(
                chunk, ep_axis, [(s, (s - j - 1) % M) for s in range(M)]))
        extra = jnp.concatenate(got, axis=0)                 # (m, chunk_loc)
        slots.append(extra * (pa.extra_experts[me][:, None] >= 0
                              ).astype(buf.dtype))
    elif impl == "a2a" and m > 0:
        wanted = pa.extra_experts                            # (M, m)
        wanted_c = jnp.maximum(wanted, 0)
        is_mine = (jnp.take(pa.owner_dev, wanted_c) == me) & (wanted >= 0)
        rows = jnp.take(pa.owner_row, wanted_c)              # (M, m)
        my_e = pa.extra_experts[me]                          # (m,)
        src = jnp.take(pa.owner_dev, jnp.maximum(my_e, 0))
        if batch:
            send = jnp.take(buf, rows.reshape(-1), axis=0) \
                .reshape(M, m, buf.shape[1])
            send = send * is_mine[..., None].astype(buf.dtype)
            recv = jax.lax.all_to_all(send, ep_axis, 0, 0,
                                      tiled=False)           # (M, m, chunk)
            got = recv[src, jnp.arange(m)]                   # (m, chunk_loc)
        else:
            per = []
            for j in range(m):
                sj = jnp.take(buf, rows[:, j], axis=0) \
                    * is_mine[:, j, None].astype(buf.dtype)
                rj = jax.lax.all_to_all(sj, ep_axis, 0, 0, tiled=False)
                per.append(jnp.take(rj, src[j][None], axis=0))
            got = jnp.concatenate(per, axis=0)               # (m, chunk_loc)
        slots.append(got * (my_e[:, None] >= 0).astype(buf.dtype))
    elif impl == "dense":
        # FSDP baseline: everything everywhere (K == k_local + (E - k_local))
        allbuf = jax.lax.all_gather(buf, ep_axis, tiled=True)     # (rows, chunk_loc)
        e_ids = pa.extra_experts[me]                              # (m=E-ish,)
        grow = (jnp.take(pa.owner_dev, jnp.maximum(e_ids, 0)) * buf.shape[0]
                + jnp.take(pa.owner_row, jnp.maximum(e_ids, 0)))
        got = jnp.take(allbuf, grow, axis=0)
        got = got * (e_ids >= 0).astype(buf.dtype)[:, None]
        slots.append(got)
    chunks = jnp.concatenate(slots, axis=0)                       # (K, chunk_loc)
    # FSDP half: gather the sharded parameter vector (overlappable)
    if fsdp_axes:
        chunks = jax.lax.all_gather(chunks, fsdp_axes, axis=1, tiled=True)
    return chunks


# ---------------------------------------------------------------------------
# Sort-based dispatch primitives (the hot path; see module docstring)
# ---------------------------------------------------------------------------
def segment_ranks(keys: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = |{j < i : keys[j] == keys[i]}| — O(N log N) / O(N) memory.

    Replaces the one-hot + cumsum rank computation (an O(N·B) tensor for B
    buckets): stable-argsort the keys, subtract a running maximum over
    equal-key segment starts from iota, scatter back to flat order.
    """
    n = keys.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(keys, stable=True)
    sk = jnp.take(keys, order)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, iota, 0))
    return jnp.zeros((n,), jnp.int32).at[order].set(iota - seg_start)


def replica_dispatch(e_safe: jnp.ndarray, valid: jnp.ndarray,
                     expert_slot: jnp.ndarray, replicas: jnp.ndarray,
                     n_replicas: jnp.ndarray, me, K: int, capacity,
                     local_first: bool):
    """Sort-based §4.4 dispatch: destinations, cell positions, keep mask and
    per-cell group sizes from ONE stable argsort of the flat assignments.

    The one-hot formulation this replaces materialized an O(N·E) rank
    tensor plus an O(N·M·K) position tensor.  Here a single argsort yields
    per-expert arrival ranks; positions need NO second sort because every
    (device, slot) cell holds exactly one expert, and one expert's entries
    land on a fixed destination in a strict cycle — every entry for a
    local-first (or dense) cell, every ``n_rep``-th entry under
    round-robin — so the in-cell arrival position is ``rank // cycle``,
    with first-come-first-kept semantics identical to the cumsum.

    e_safe: (N,) int32 expert per flat (token, k) entry (clamped >= 0).
    valid: (N,) bool gate mask.  Invalid entries consume NO positions (the
      rank sort shunts them to an overflow key), so the kept entries of
      every cell occupy exactly the position prefix [0, counts) — the
      invariant the group-size masking and the post-a2a compaction rely
      on.  Over-capacity entries still follow first-come-first-kept.
    expert_slot: (M, E); replicas: (E, r_max); n_replicas: (E,).
    me: this device's EP index (traced).

    Returns (dest, slot, pos, keep, counts) with counts (M, K) int32 —
    KEPT entries per destination cell (= the grouped-GEMM group sizes,
    emitted as a byproduct of the dispatch sort).
    """
    M = expert_slot.shape[0]
    E = expert_slot.shape[1]
    my_slot = jnp.take(expert_slot[me], e_safe)
    rank = segment_ranks(jnp.where(valid, e_safe, E))
    # clamp to the replica table width so the cycle invariant (each dest
    # gets every cycle-th arrival) holds even for inconsistent inputs
    n_rep = jnp.clip(jnp.take(n_replicas, e_safe), 1, replicas.shape[-1])
    rr = (rank + me) % n_rep
    dest_rr = replicas[e_safe, rr]
    if local_first:
        # paper §4.4: a local replica absorbs all local tokens.  Best for
        # network volume; with static per-pair capacity the local cell must
        # then be sized for the device's own hot load.
        dest = jnp.where(my_slot >= 0, me, dest_rr)
        cycle = jnp.where(my_slot >= 0, 1, n_rep)
    else:
        # round-robin over ALL replicas: spreads hot-expert tokens evenly
        # across cells — the static-buffer-friendly adaptation
        dest, cycle = dest_rr, n_rep
    slot = expert_slot[dest, e_safe]
    pos = rank // cycle
    keep = valid & (pos < capacity) & (slot >= 0)
    cell = jnp.where(keep, dest * K + slot, M * K)    # overflow bucket
    counts = jnp.zeros((M * K + 1,), jnp.int32).at[cell].add(1)[:M * K]
    return dest, slot, pos, keep, counts.reshape(M, K)


# ---------------------------------------------------------------------------
# Expert compute over K slots
# ---------------------------------------------------------------------------
def _expert_ffn(cfg: ModelConfig, chunks, xr, use_pallas: bool,
                group_sizes=None, row_valid=None):
    """chunks: (K, chunk_len); xr: (K, T, D). Returns (K, T, D).

    Validity is either group_sizes (K,) — the valid-row PREFIX of each
    slot — or row_valid (K, T) bool for arbitrary rows (the fused dispatch
    layout): the Pallas kernels skip whole token tiles with no valid row
    (MegaBlocks-style), forward and backward; the XLA path masks input AND
    output rows so both values and gradients match the kernels' custom
    VJP exactly.
    """
    with jax.named_scope("expert_ffn"):
        wi, wg, wo = unpack_chunks(cfg, chunks)
        dt = xr.dtype
        if use_pallas:
            from repro.kernels import ops as kops
            return kops.grouped_mlp(xr, wi.astype(dt),
                                    None if wg is None else wg.astype(dt),
                                    wo.astype(dt), group_sizes, row_valid,
                                    act=cfg.act)
        from repro.kernels.ref import grouped_mlp_ref
        return grouped_mlp_ref(xr, wi.astype(dt),
                               None if wg is None else wg.astype(dt),
                               wo.astype(dt), act=cfg.act,
                               group_sizes=group_sizes, row_valid=row_valid)


# ---------------------------------------------------------------------------
# The full FSSDP MoE layer body (inside shard_map)
# ---------------------------------------------------------------------------
def _moe_body(cfg: ModelConfig, impl: str, ep_axis: str, fsdp_axes,
              m: int, capacity: int, use_pallas: bool, local_first: bool,
              batch_coll: bool,
              x, valid, wr, buf, pa: PlanArrays, premat=None):
    """x: (T_loc, D) local tokens; valid: (T_loc,) padding mask.
    buf: (rows_local, chunk_loc).
    Returns (y, counts, aux, z, dropped, dev_loads, pad_frac).

    The gate lives INSIDE the shard_map: top_k is row-local, so keeping it
    here avoids GSPMD's full (T, E) gather (seen in dry-run HLO: 268 MB per
    layer per device).  Global gate statistics come from one (E,) psum.

    premat: optional (1, K, chunk_len) pre-materialized compute slots (the
    decode path, plan unchanged between steps) — skips the SparseAllGather
    collectives entirely.
    """
    me = jax.lax.axis_index(ep_axis)
    M = jax.lax.axis_size(ep_axis)
    T, D = x.shape
    all_axes = tuple(fsdp_axes) + (ep_axis,)
    K = pa.local_rows.shape[-1] + m if impl != "dense" \
        else pa.local_rows.shape[-1] + pa.extra_experts.shape[-1]

    # SparseAllGather FIRST (§4.2 overlap): the expert-chunk collectives
    # (ring/a2a over the EP axis + the FSDP-axis all-gather) have no data
    # dependence on the gate, so issuing them before the gate / dispatch
    # arithmetic lets an async-collective scheduler hide their latency
    # behind that compute — first use is in _expert_ffn, after dispatch.
    if premat is not None:
        # produced by materialize_layer / materialize_chunks, which
        # checkpoint-name their output — do NOT re-name here, or the remat
        # policies would save the same chunks twice
        chunks = premat[0]                           # (K, chunk_len)
    else:
        with jax.named_scope("spag"):
            chunks = _materialize(cfg, buf, pa, impl, ep_axis, fsdp_axes,
                                  m, batch=batch_coll)
        chunks = checkpoint_name(chunks, "moe_materialized")

    idx, vals, counts, aux, z = gate(cfg, wr, x, valid,
                                     psum_axes=all_axes)
    k = idx.shape[1]

    # ---- dispatch plan (§4.4: local replica first, else round-robin) ----
    with jax.named_scope("dispatch"):
        e_flat = idx.reshape(-1)                               # (T*k,)
        w_flat = vals.reshape(-1)
        valid_w = w_flat > 0
        e_safe = jnp.maximum(e_flat, 0)
        tk = e_flat.shape[0]
        cap_eff = M * capacity if impl == "dense" else capacity
        if impl == "dense":
            # every expert local: pure data parallelism for the MoE (FSDP).
            # Cells are slots; one expert per slot, so pos = per-expert
            # rank (counting valid entries only — kept rows stay a cell
            # prefix).
            dest = jnp.full((tk,), me, jnp.int32)
            slot = jnp.take(pa.expert_slot[me], e_safe)
            pos = segment_ranks(jnp.where(valid_w, e_safe,
                                          cfg.moe.num_experts))
            keep = valid_w & (pos < cap_eff) & (slot >= 0)
            cnt = jnp.zeros((K + 1,), jnp.int32).at[
                jnp.where(keep, slot, K)].add(1)[:K]
        else:
            dest, slot, pos, keep, send_cnt = replica_dispatch(
                e_safe, valid_w, pa.expert_slot, pa.replicas,
                pa.n_replicas, me, K, cap_eff, local_first)
        dropped = 1.0 - keep.sum() / jnp.maximum(valid_w.sum(), 1)
        pos_w = jnp.where(keep, pos, cap_eff)              # OOB -> dropped
        xtok = x[jnp.arange(tk) // k]

    if impl == "dense":
        # no token communication at all — local (K, M*C, D) compute buffer;
        # positions are a per-slot valid prefix, so the kept counts are the
        # group sizes directly
        with jax.named_scope("dispatch"):
            gs = cnt                                           # (K,)
            buf_x = jnp.zeros((K, cap_eff, D), x.dtype)
            buf_x = buf_x.at[slot, pos_w].set(xtok, mode="drop")
        yr = _expert_ffn(cfg, chunks, buf_x, use_pallas, group_sizes=gs)
        with jax.named_scope("combine"):
            got = yr[slot, pos_w] * keep[:, None].astype(x.dtype)
            dev_loads_l = jnp.zeros((M,), jnp.float32).at[me].set(
                gs.sum().astype(jnp.float32))
        rows_per_dev = K * cap_eff
    else:
        with jax.named_scope("dispatch"):
            send = jnp.zeros((M, K, capacity, D), x.dtype)
            send = send.at[dest, slot, pos_w].set(xtok, mode="drop")
            recv = jax.lax.all_to_all(send, ep_axis, 0, 0,
                                      tiled=False)             # (M,K,C,D)
            xr = recv.transpose(1, 0, 2, 3).reshape(K, M * capacity, D)
            valid_row = None
            if use_pallas:
                # per-row validity rides a tiny (M, K) int all_to_all; the
                # dispatch lands kept tokens in a valid prefix of each
                # source's capacity stripe, so validity is metadata — the
                # kernels skip token tiles with no valid row directly in
                # the uncompacted layout (no (K, T, D) gather/scatter
                # compaction copies)
                recv_cnt = jax.lax.all_to_all(send_cnt, ep_axis, 0, 0,
                                              tiled=False)     # (M, K)
                r_src = jnp.arange(M * capacity,
                                   dtype=jnp.int32) // capacity
                r_off = jnp.arange(M * capacity,
                                   dtype=jnp.int32) % capacity
                valid_row = r_off[None, :] < recv_cnt.T[:, r_src]
        yr = _expert_ffn(cfg, chunks, xr, use_pallas, row_valid=valid_row)
        with jax.named_scope("combine"):
            yback = yr.reshape(K, M, capacity, D).transpose(1, 0, 2, 3)
            ret = jax.lax.all_to_all(yback, ep_axis, 0, 0, tiled=False)
            got = ret[dest, slot, pos_w] * keep[:, None].astype(x.dtype)
            dev_loads_l = send_cnt.sum(1).astype(jnp.float32)
        rows_per_dev = K * M * capacity

    with jax.named_scope("combine"):
        y = (got.reshape(T, k, D)
             * vals.reshape(T, k, 1).astype(x.dtype)).sum(axis=1)
        dev_loads = jax.lax.psum(dev_loads_l, all_axes)
        n_dev = jax.lax.psum(1, all_axes)
        pad_frac = 1.0 - dev_loads.sum() / float(rows_per_dev * n_dev)
    return y, counts, aux, z, dropped, dev_loads, pad_frac


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MoERuntime:
    """Distribution context for the MoE layer."""
    mesh: Optional[Mesh] = None
    ep_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("data",)   # token-sharding axes (w/ pod)
    impl: str = "ring"                        # ring | a2a | dense
    m: int = 2
    k_local: int = 0
    capacity: int = 0                         # per (pair, slot); 0 = auto
    r_max: int = 0
    use_pallas: bool = False
    local_first: bool = True                  # §4.4 dispatch rule
    # batch the m materialization collectives into stacked ops.  None =
    # auto: on for accelerators, off on the CPU backend, whose host
    # collective emulation slows down sharply with message size (measured
    # in benchmarks/dispatch_microbench.py; wire volume is identical)
    batch_collectives: Optional[bool] = None

    @property
    def fsdp_axes(self):
        return self.batch_axes

    def ep_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.ep_axis]


def auto_capacity(cfg: ModelConfig, t_loc: int, ep: int, k_total: int) -> int:
    want = cfg.moe.capacity_factor * t_loc * cfg.moe.experts_per_token \
        / max(ep * k_total, 1)
    return max(1, int(-(-want // 1)))


def moe_layer(cfg: ModelConfig, rt: MoERuntime, x, wr, buf,
              pa: PlanArrays, valid=None, premat=None):
    """Distributed FSSDP MoE layer.

    x: (T, D) tokens, globally sharded over (batch_axes..., ep_axis) on dim 0
       (T must be divisible by the full device count).
    wr: (D, E) router weights for THIS layer.
    buf: the global flat chunk buffer (rows, chunk_len).
    pa: this layer's PlanArrays slice (leading L dim removed).
    premat: optional (M, K, chunk_len) pre-materialized compute slots from
       ``materialize_chunks`` — skips this layer's SparseAllGather (decode
       path: the plan and buffer are unchanged between steps).
    Returns (y: (T, D), MoEAux).
    """
    if valid is None:
        valid = jnp.ones((x.shape[0],), bool)
    # mixed precision: materialize/dispatch in the compute dtype; the f32
    # master buffer stays sharded (AD upcasts the gradient on the way back)
    buf = buf.astype(x.dtype)
    if rt.mesh is None:
        idx, vals, counts, aux, z = gate(cfg, wr, x, valid)
        y, dropped = moe_layer_ref(cfg, x, idx, vals, buf, pa)
        return y, MoEAux(counts, aux, z, dropped,
                         counts.sum()[None], jnp.zeros(()))

    ep = rt.ep_size()
    all_axes = tuple(rt.batch_axes) + (rt.ep_axis,)
    t_loc = x.shape[0] // rt.mesh.shape[rt.ep_axis] // int(
        np.prod([rt.mesh.shape[a] for a in rt.batch_axes]))
    k_total = pa.local_rows.shape[-1] + (
        pa.extra_experts.shape[-1] if rt.impl == "dense" else rt.m)
    cap = rt.capacity or auto_capacity(cfg, t_loc, ep, k_total)

    body = partial(_moe_body, cfg, rt.impl, rt.ep_axis, rt.fsdp_axes,
                   _m_of(rt, pa), cap, rt.use_pallas, rt.local_first,
                   _coll_batch(rt))
    pspecs = plan_arrays_specs(rt.mesh, rt.ep_axis)
    in_specs = (P(all_axes, None), P(all_axes), P(),
                P(rt.ep_axis, rt.fsdp_axes), pspecs)
    args = (x, valid, wr, buf, pa)
    if premat is not None:
        in_specs += (P(rt.ep_axis, None, None),)
        args += (premat.astype(x.dtype),)
    y, counts, aux, z, dropped, dev_loads, pad_frac = jax.shard_map(
        body, mesh=rt.mesh,
        in_specs=in_specs,
        out_specs=(P(all_axes, None), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )(*args)
    return y, MoEAux(counts, aux, z, dropped, dev_loads, pad_frac)


def moe_layer_regather(cfg: ModelConfig, rt: MoERuntime, x, wr, buf,
                       pa_l: PlanArrays, valid, premat):
    """``moe_layer(premat=...)`` with ``rematerialize="gather"`` semantics:
    TRUE re-materialization (paper §4.3) as a custom VJP.

    Forward: consume the prefetched compute slots exactly like
    ``moe_layer(premat=premat)``.  Residuals are ``(x, wr, buf)`` — the
    (K, chunk_len) materialized chunks are NOT stored (``buf`` is the live
    sharded parameter, effectively free), and neither are the MoE layer's
    dispatch/FFN intermediates (the layer interior is re-run under the
    VJP).  ``premat``, the plan tables and the padding mask are closed
    over through a ``stop_gradient`` as non-differentiable constants, so
    the forward pipeline's producer is never transposed (no dead
    zero-filled collectives) and the scan never keeps the carried chunks
    alive for AD.

    Backward: REPLAY the SparseAllGather from the sharded buffer (the
    re-materialization collectives, issued at the head of the VJP so the
    async scheduler can overlap them with the preceding layer's backward
    compute) and re-run the layer under ``jax.vjp`` — AD's transpose of
    the replayed gather is the SparseReduceScatter that lands the buffer
    gradient on its owning shards.
    """
    premat = jax.lax.stop_gradient(premat)

    def primal(x_, wr_, buf_, premat_, pa_, valid_):
        return moe_layer(cfg, rt, x_, wr_, buf_, pa_, valid_,
                         premat=premat_)

    consume = jax.custom_vjp(primal)

    def fwd(x_, wr_, buf_, premat_, pa_, valid_):
        # residuals: plan tables + mask (tiny int/bool) — NOT premat
        return primal(x_, wr_, buf_, premat_, pa_, valid_), \
            (x_, wr_, buf_, pa_, valid_)

    def bwd(res, ct):
        x_, wr_, buf_, pa_, valid_ = res

        def replay(xr_, wrr_, bufr_):
            pm = materialize_layer(cfg, rt, bufr_, pa_, dtype=xr_.dtype)
            return moe_layer(cfg, rt, xr_, wrr_, bufr_, pa_, valid_,
                             premat=pm)

        _, vjp = jax.vjp(replay, x_, wr_, buf_)
        dx, dwr, dbuf = vjp(ct)
        # None = symbolic-zero cotangents: premat's cotangent is zero BY
        # CONSTRUCTION (its producer is stop_gradient'd in the pipelined
        # forward), and a None keeps it symbolic — no dead (M, K, chunk)
        # zeros tensor, no cotangent carry in the backward scan
        return dx, dwr, dbuf, None, None, None

    consume.defvjp(fwd, bwd)
    return consume(x, wr, buf, premat, pa_l, valid)


def moe_layer_regather_pipelined(cfg: ModelConfig, rt: MoERuntime, x, wr,
                                 buf, pa_l: PlanArrays,
                                 pa_prev: PlanArrays, valid, premat,
                                 pipe_in, warm_start: bool = False):
    """``moe_layer_regather`` with an EXPLICIT backward re-gather pipeline
    — the backward mirror of the forward's one-layer-ahead prefetch.

    The plain regather VJP issues its own layer's re-gather at the head of
    its backward and merely *hopes* the async collective scheduler hoists
    it over the preceding layer's backward compute.  This variant makes the
    schedule structural: layer l's backward CONSUMES compute slots that
    were re-gathered one backward step earlier (during layer l+1's
    backward) and ISSUES layer l−1's re-gather before its own dgrad/wgrad
    kernels — jaxpr-assertable ordering, one layer of lookahead, exactly
    like ``_pipelined_blocks`` in the forward.

    The transport is a chunk-shaped *pipe channel* threaded through the
    forward (``pipe_in`` -> returned ``pipe_out``): a value flowing
    forward from layer l to layer l+1 has its cotangent computed in layer
    l+1's backward and consumed in layer l's — precisely the
    backward-execution-order data path the prefetch needs.  Layer l's bwd
    returns the freshly gathered layer-(l−1) slots as the pipe cotangent;
    layer l−1's bwd receives them as ``ct(pipe_out)``.  In the PRIMAL the
    pipe is fresh zeros, NOT a pass-through of ``pipe_in``: custom_vjp's
    bwd defines the cotangent routing regardless of primal data flow, and
    a known-constant carry costs nothing — partial eval neither stacks it
    as a per-iteration scan residual (a pass-through pipe was saved as
    (n_sb, M, K, chunk) — exactly the chunk memory gather mode exists to
    avoid) nor keeps a serializing fake dependency in the compiled
    forward (the unused ``pipe_in`` operand DCEs away after AD).

    Backward of layer l, in ISSUE ORDER:
      1. slots for THIS layer: ``ct(pipe_out)`` — or, for the LAST MoE
         layer of the network (``warm_start=True``, the first backward
         executed, whose pipe cotangent is zero), a warm-up self-gather;
      2. the PREVIOUS layer's re-gather (``pa_prev``) — the backward
         prefetch, data-independent of everything below, so it overlaps
         this layer's recompute + dgrad/wgrad;
      3. recompute the layer interior under ``jax.vjp`` from the
         pre-gathered slots (premat path — no gather inside);
      4. the explicit ``jax.linear_transpose`` of this layer's gather maps
         the chunk cotangent to the buffer gradient — the
         SparseReduceScatter, landing OFF the critical path (it depends on
         step 3's output and nothing depends on it within this layer).

    For the FIRST MoE layer of the network ``pa_prev`` should be its own
    tables: the emitted gather's consumer is the (dead) cotangent of the
    zeros-initialized pipe head, and XLA drops it at compile time — the
    jaxpr-level collective law is (3L+1)·m ring ppermutes vs the
    un-pipelined regather's 3L·m (see tests/test_pipeline_remat.py).

    Residuals are (x, wr, buf, plan tables, mask) — no chunks, no layer
    interior, identical to ``moe_layer_regather``.
    """
    premat = jax.lax.stop_gradient(premat)
    dt = jnp.dtype(cfg.dtype)

    def primal(x_, wr_, buf_, pipe_, premat_, pa_, pa_p_, valid_):
        y, aux = moe_layer(cfg, rt, x_, wr_, buf_, pa_, valid_,
                           premat=premat_)
        return y, aux, jnp.zeros_like(pipe_)

    consume = jax.custom_vjp(primal)

    def fwd(x_, wr_, buf_, pipe_, premat_, pa_, pa_p_, valid_):
        return primal(x_, wr_, buf_, pipe_, premat_, pa_, pa_p_, valid_), \
            (x_, wr_, buf_, pa_, pa_p_, valid_)

    def bwd(res, cts):
        x_, wr_, buf_, pa_, pa_p_, valid_ = res
        ct_y, ct_aux, ct_pipe = cts
        # (1) this layer's compute slots: prefetched during the NEXT
        # layer's backward (they arrive as the pipe cotangent), except at
        # the backward's head, which self-gathers — the warm-up
        if warm_start:
            ch = materialize_layer(cfg, rt, buf_, pa_, dtype=dt,
                                   name=False)
        else:
            ch = ct_pipe.astype(dt)
        # (2) BACKWARD PREFETCH: issue layer l-1's re-gather before this
        # layer's dgrad/wgrad consumers below; it leaves this VJP as the
        # pipe cotangent and is consumed one backward step later
        prev = materialize_layer(cfg, rt, buf_, pa_p_, dtype=dt,
                                 name=False)
        # (3) recompute the layer interior from the pre-gathered slots
        # (premat path — no materialization collectives in here)
        buf0 = jax.lax.stop_gradient(buf_)

        def use(ch_, xr_, wrr_):
            return moe_layer(cfg, rt, xr_, wrr_, buf0, pa_, valid_,
                             premat=ch_)

        _, vjp = jax.vjp(use, ch, x_, wr_)
        dch, dx, dwr = vjp((ct_y, ct_aux))
        # (4) SparseReduceScatter: the linear transpose of THIS layer's
        # gather lands the chunk cotangent on the owning buffer shards —
        # nothing in this layer consumes it, so it sits off the critical
        # path of the backward pipeline
        with jax.named_scope("sprs"):
            dbuf = jax.linear_transpose(
                lambda b: materialize_layer(cfg, rt, b, pa_, dtype=dch.dtype,
                                            name=False), buf_)(dch)[0]
        return dx, dwr, dbuf.astype(buf_.dtype), prev, None, None, None, \
            None

    consume.defvjp(fwd, bwd)
    return consume(x, wr, buf, pipe_in, premat, pa_l, pa_prev, valid)


def _coll_batch(rt: MoERuntime) -> bool:
    return rt.batch_collectives if rt.batch_collectives is not None \
        else jax.default_backend() != "cpu"


def _m_of(rt: MoERuntime, pa: PlanArrays) -> int:
    return rt.m if rt.impl != "dense" else pa.extra_experts.shape[-1]


def materialize_layer(cfg: ModelConfig, rt: MoERuntime, buf,
                      pa_l: PlanArrays, dtype=None, name: bool = True):
    """SparseAllGather for ONE layer, traceable inline: (M, K, chunk_len).

    This is the pipelined forward's prefetch primitive: unlike
    ``materialize_chunks`` it is NOT jitted itself, so the model can issue
    layer l+1's materialization collectives inside the compiled train step
    one layer before their ``moe_layer(premat=...)`` consumer — the
    collectives overlap the whole of layer l's attention/FFN compute.  The
    output is checkpoint-named ``moe_materialized`` at this producer (and
    only here on the premat path) so the ``rematerialize`` policies see
    exactly one named value per layer.

    ``name=False`` skips the checkpoint naming — required wherever the
    gather must stay LINEAR-transposable (``jax.linear_transpose`` has no
    rule for the name primitive): the backward re-gathers issued inside
    ``moe_layer_regather_pipelined``'s VJP, whose explicit transpose is the
    SparseReduceScatter landing the buffer gradient.
    """
    with jax.named_scope("spag"):
        buf, _ = unwrap_buffer(buf)
        buf = buf.astype(dtype or jnp.dtype(cfg.dtype))
        m = _m_of(rt, pa_l)
        batch = _coll_batch(rt)

        def body(buf_, pa_):
            ch = _materialize(cfg, buf_, pa_, rt.impl, rt.ep_axis,
                              rt.fsdp_axes, m, batch=batch)
            return ch[None]                              # (1, K, chunk_len)

        out = jax.shard_map(
            body, mesh=rt.mesh,
            in_specs=(P(rt.ep_axis, rt.fsdp_axes),
                      plan_arrays_specs(rt.mesh, rt.ep_axis)),
            out_specs=P(rt.ep_axis, None, None),
            check_vma=False)(buf, pa_l)
        return checkpoint_name(out, "moe_materialized") if name else out


def materialize_stack(cfg: ModelConfig, rt: MoERuntime, buf, pa: PlanArrays,
                      dtype=None, name: bool = True):
    """SparseAllGather for EVERY MoE layer, traceable inline:
    (L, M, K, chunk_len).

    The step-level materialization primitive: ONE stacked shard_map issues
    all L layers' gathers (L·m ring ppermutes / L stacked all_to_alls in a
    single region) so the train step can build every layer's compute slots
    ONCE per step — before the gradient-accumulation loop — and feed each
    microbatch's forward via ``premat=``.  Under gradient accumulation this
    is L SparseAllGathers per step instead of L·n (the collectives are
    hoisted off every microbatch's critical path), and in "save" mode one
    shared set of chunk residuals instead of n.

    Unlike ``materialize_chunks`` this is NOT jitted (it traces into the
    caller's step) and it is linear in ``buf``: its AD transpose is the
    stacked SparseReduceScatter that lands the accumulated chunk cotangent
    on the owning buffer shards, once per step.  ``materialize_chunks``
    wraps this body in a cached jit for the serving path.
    """
    with jax.named_scope("spag"):
        buf, _ = unwrap_buffer(buf)
        dt = jnp.dtype(dtype or jnp.dtype(cfg.dtype))
        m = _m_of(rt, pa)
        batch = _coll_batch(rt)
        L = pa.local_rows.shape[0]

        def body(buf_, pa_):
            buf_ = buf_.astype(dt)
            outs = [_materialize(cfg, buf_,
                                 jax.tree.map(lambda a, l=l: a[l], pa_),
                                 rt.impl, rt.ep_axis, rt.fsdp_axes, m,
                                 batch=batch)
                    for l in range(L)]
            return jnp.stack(outs)[:, None]          # (L, 1, K, chunk_len)

        specs = plan_arrays_specs(rt.mesh, rt.ep_axis)
        stacked = PlanArrays(*[P(None, *tuple(s)) for s in specs])
        out = jax.shard_map(
            body, mesh=rt.mesh,
            in_specs=(P(rt.ep_axis, rt.fsdp_axes), stacked),
            out_specs=P(None, rt.ep_axis, None, None),
            check_vma=False)(buf, pa)
        return checkpoint_name(out, "moe_materialized") if name else out


# jitted stacked-materialize cache: plans change CONTENTS every iteration
# but never shapes, so one compile serves every plan swap of a serving
# process (and the engine's double-buffered next-plan build).  Bounded —
# each entry pins a compiled executable AND a Mesh; long-lived processes
# that cycle meshes/configs must not grow it monotonically.
_MAT_FNS: Dict[Any, Any] = {}
_MAT_FNS_MAX = 8

# slot-RESULT memo for versioned buffers: (compile key, buffer version,
# plan token) -> (source buffer, source plan tables, the built
# (L, M, K, chunk_len) slots).  The caller-supplied counters alone cannot
# be trusted as identity (a params tree swapped behind the engine's back
# keeps the version; two engines in one process each start at version 0
# and epoch 0 — possibly with different plans), so a hit additionally
# requires the stored source buffer AND plan tables to BE the requested
# ones — a stale or foreign entry misses and is rebuilt/overwritten.
# Two entries: a serving process double-buffers exactly one
# (plan, version) pair against the live one, and each entry pins L layers
# of device chunks.  The builder thread and the consumer's lazy path may
# touch these dicts concurrently — all lookup/insert/evict sections hold
# _CACHE_LOCK (an unlocked FIFO evict can KeyError mid-decode).
_SLOT_RESULTS: Dict[Any, Any] = {}
_SLOT_RESULTS_MAX = 2
_CACHE_LOCK = threading.Lock()


def materialize_chunks(cfg: ModelConfig, rt: MoERuntime, buf,
                       pa: PlanArrays, dtype=None, pa_token=None):
    """Run SparseAllGather alone for every MoE layer: (L, M, K, chunk_len).

    ONE stacked jitted shard_map call covers all L layers (previously L
    separate jitted calls in a Python loop — L dispatches + L sets of
    collectives with host round-trips between them), which is what makes
    serve startup and background plan swaps cheap.  The decode path reuses
    these slots across steps while the plan (and the parameter buffer) is
    unchanged — ``moe_layer(..., premat=out[l])`` then issues NO
    materialization collectives.  Returns None without a mesh (the
    single-device oracle never materializes).

    ``buf`` may be a ``VersionedBuffer``.  When it is AND the caller
    passes a ``pa_token`` identifying the plan the tables came from, the
    built slots are memoized under (compile key, buffer version,
    pa_token), validated against the source buffer and plan-table
    identities: re-requesting the slots of an already-built
    (plan, version) pair — an engine re-validating its cache after a
    restore, or the lazy path racing a background publication build —
    returns the existing device arrays and issues ZERO collectives.
    """
    if rt.mesh is None:
        return None
    buf, version = unwrap_buffer(buf)
    dt = jnp.dtype(dtype or jnp.dtype(cfg.dtype))
    m = _m_of(rt, pa)
    batch = _coll_batch(rt)
    L = pa.local_rows.shape[0]
    key = (cfg, rt.mesh, rt.ep_axis, tuple(rt.batch_axes), rt.impl, m,
           batch, dt, L)
    rkey = (key, version, pa_token) \
        if version is not None and pa_token is not None else None
    with _CACHE_LOCK:
        if rkey is not None:
            hit = _SLOT_RESULTS.get(rkey)
            if hit is not None and hit[0] is buf and hit[1] is pa:
                return hit[2]
        fn = _MAT_FNS.get(key)
        if fn is None:
            fn = jax.jit(partial(materialize_stack, cfg, rt, dtype=dt,
                                 name=False))
            while len(_MAT_FNS) >= _MAT_FNS_MAX:   # FIFO eviction
                _MAT_FNS.pop(next(iter(_MAT_FNS)))
            _MAT_FNS[key] = fn
    out = fn(buf, pa)               # compile/dispatch outside the lock
    if rkey is not None:
        with _CACHE_LOCK:
            _SLOT_RESULTS.pop(rkey, None)          # refresh insert order
            while len(_SLOT_RESULTS) >= _SLOT_RESULTS_MAX:
                _SLOT_RESULTS.pop(next(iter(_SLOT_RESULTS)))
            _SLOT_RESULTS[rkey] = (buf, pa, out)
    return out


def clear_materialize_cache() -> None:
    """Drop every cached stacked-materialize executable and slot result.

    Each ``_MAT_FNS`` entry pins a compiled executable AND a Mesh (and
    each ``_SLOT_RESULTS`` entry pins device arrays); the FIFO bounds cap
    steady-state growth, but test suites (and long-lived processes that
    cycle meshes/configs) need an explicit way to release them — otherwise
    compiled programs for dead meshes survive across test cases.  Called
    from the test suite's per-test teardown.
    """
    with _CACHE_LOCK:
        _MAT_FNS.clear()
        _SLOT_RESULTS.clear()


# ---------------------------------------------------------------------------
# Single-device reference (oracle) — identical routing semantics, no drops
# ---------------------------------------------------------------------------
def moe_layer_ref(cfg: ModelConfig, x, idx, vals, buf, pa: PlanArrays):
    """Dense-compute oracle: every expert applied to every token, combined
    with the top-k weights.  buf is the UNSHARDED (rows, chunk_len) buffer;
    expert e's chunk sits at global row owner_dev*rows_per_dev... — for the
    single-device case rows are owner_row directly (M=1)."""
    e_count = cfg.moe.num_experts
    chunks = jnp.take(buf, pa.owner_row, axis=0)       # (E, chunk_len)
    wi, wg, wo = unpack_chunks(cfg, chunks)
    dt = x.dtype
    h = jnp.einsum("td,edf->etf", x, wi.astype(dt))
    if wg is not None:
        from repro.models.layers import glu_fn
        h = glu_fn(cfg.act)(h) * jnp.einsum("td,edf->etf", x, wg.astype(dt))
    else:
        h = jax.nn.gelu(h)
    y_all = jnp.einsum("etf,efd->etd", h, wo.astype(dt))  # (E, T, D)
    comb = jnp.zeros((x.shape[0], e_count), jnp.float32)
    comb = comb.at[jnp.arange(x.shape[0])[:, None], idx].add(vals)
    y = jnp.einsum("te,etd->td", comb.astype(dt), y_all)
    return y, jnp.zeros((), jnp.float32)
