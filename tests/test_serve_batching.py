"""Overload-safe continuous batching: the paged-KV request scheduler.

1. Paged-cache parity: a block-paged decode step matches the dense-cache
   decode step for the same trace (same KV width) — BIT-EXACT on the
   pure-XLA gather path (``cfg.paged_attn_kernel=False``), ≤1e-6 f32 on
   the Pallas paged-kernel path (online softmax reorders the reduction;
   the math is otherwise identical) — across the ``attn``, ``local``
   sliding-window and mrope configs; the jitted paged step materializes
   NO (B, max_kv, ...) KV gather copy and NO pool-sized GQA head
   expansion (jaxpr-asserted); and the scheduler's end-to-end traces
   equal ``Engine.generate`` token-for-token — including mixed prompt
   lengths decoded concurrently and a sequence that was preempted and
   resumed.
2. Overload is a typed RESULT, never an exception: bounded queue
   (``queue_full``), impossible requests (``too_long``), TTL deadlines
   (TIMED_OUT), prefill crashes past the retry budget (REJECTED), and
   page-pool exhaustion (youngest-sequence preemption) all terminate
   requests in exactly one of DONE / REJECTED / TIMED_OUT.
3. Chaos soak: all three serve fault sites (``serve.page_exhausted``,
   ``serve.request_hang``, ``serve.prefill_crash``) armed in randomized
   order — the decode path never raises, every admitted request
   terminates, and the page pool drains back to empty (no leaks).
4. Publication consistency: a prefill that straddles a staged publication
   reads ONE consistent (plan, version) pair — the promoted one.
5. Backpressure: scheduler load (queue depth, KV occupancy) surfaces
   through ``EngineHealth`` into ``PublicationBus.route()``, which orders
   replicas least-loaded first.
6. Collective law (dist): the premat paged decode step issues ZERO
   SparseAllGather collectives on a real (data, model) mesh.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.common import faults
from repro.models import model as mdl
from repro.serve.bus import PublicationBus
from repro.serve.engine import (Engine, build_paged_serve_step,
                                build_serve_step)
from repro.serve.kv_pool import KVPagePool, PageTable
from repro.serve.scheduler import (DONE, REJECTED, TERMINAL, TIMED_OUT,
                                   RequestScheduler)
from repro.train.trainer import HecateScheduler


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


def _smoke_engine(params_seed=0, max_len=32, mutate=None):
    cfg = C.get_smoke("gpt-moe-s")
    if mutate is not None:
        cfg = mutate(cfg)
    rt = mdl.Runtime()
    sched = HecateScheduler(cfg, ep=1, impl="ep")
    pa = sched.plan_arrays()
    sched.close()
    params = mdl.init_params(cfg, jax.random.PRNGKey(params_seed))
    return cfg, rt, params, pa, Engine(cfg, rt, params, max_len=max_len,
                                       pa=pa)


# ---------------------------------------------------------------------------
# 0. the page pool (host-side allocator)
# ---------------------------------------------------------------------------
def test_kv_pool_alloc_free_deterministic():
    pool = KVPagePool(num_pages=5, page_size=4)
    assert pool.usable_pages == 4 and pool.num_rows == 20
    a = pool.alloc(2)
    assert a == [1, 2]                  # lowest-first, page 0 reserved
    b = pool.alloc(2)
    assert b == [3, 4]
    assert pool.alloc(1) is None        # exhaustion is a result, not a raise
    assert pool.used_frac == 1.0
    pool.free(a)
    assert pool.alloc(2) == [1, 2]      # deterministic after free
    with pytest.raises(AssertionError):
        pool.free([0])                  # page 0 can never be freed
    pool2 = KVPagePool(num_pages=3, page_size=2)
    p = pool2.alloc(1)
    pool2.free(p)
    with pytest.raises(AssertionError):
        pool2.free(p)                   # double free


def test_page_table_row_idx_maps_tokens_and_parks_tail_on_trash():
    t = PageTable(page_size=4, max_kv=12, pages=[3, 1])
    rows = t.row_idx()
    assert rows.shape == (12,)
    np.testing.assert_array_equal(rows[:8],
                                  [12, 13, 14, 15, 4, 5, 6, 7])
    np.testing.assert_array_equal(rows[8:], 0)      # trash page
    assert t.capacity == 8


# ---------------------------------------------------------------------------
# 1. parity with the dense cache
# ---------------------------------------------------------------------------
_PARITY_VARIANTS = {
    "attn": lambda c: c,
    "local": lambda c: c.replace(layer_pattern=("attn", "local"),
                                 sliding_window=5),
    "mrope": lambda c: c.replace(mrope=True),
}


@pytest.mark.parametrize("variant", sorted(_PARITY_VARIANTS))
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_paged_decode_step_parity_vs_dense(variant, impl):
    """Same trace, same KV width: every decode step's logits match between
    the dense cache and the paged pool, for global-attn, sliding-window
    ``local`` and mrope configs.  The pure-XLA gather path
    (``paged_attn_kernel=False``) is BIT-identical (masked trash rows
    softmax to exact 0.0 and the reduction width matches); the Pallas
    kernel path is ≤1e-6 in f32 — its online softmax visits KV tiles in
    page order, so only the reduction order differs."""
    def mutate(c):
        c = _PARITY_VARIANTS[variant](c)
        return c.replace(paged_attn_kernel=(impl == "kernel"))
    cfg, rt, params, pa, eng = _smoke_engine(max_len=16, mutate=mutate)
    max_kv = 16
    dense_step = jax.jit(build_serve_step(cfg, rt))
    paged_step = jax.jit(build_paged_serve_step(cfg, rt, page_size=4))
    premat = eng._materialized()

    dense_cache = mdl.init_cache(cfg, 1, max_kv)
    paged_cache = mdl.init_paged_cache(cfg, 1, 5 * 4)   # 5 pages of 4
    table = PageTable(page_size=4, max_kv=max_kv, pages=[1, 2, 3, 4])
    row_idx = jnp.asarray(table.row_idx()[None])

    toks = np.asarray([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    for i, t in enumerate(toks):
        tt = jnp.asarray([[t]], jnp.int32)
        ld, dense_cache = dense_step(params, dense_cache, tt,
                                     jnp.int32(i), pa, premat)
        lp, paged_cache = paged_step(params, paged_cache, tt,
                                     jnp.asarray([i], jnp.int32),
                                     row_idx, pa, premat)
        if impl == "xla":
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        else:
            np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                                       atol=1e-5, rtol=1e-5)
    eng.close()


def test_paged_step_materializes_no_gather_and_no_gqa_expansion():
    """The jitted paged decode step on the kernel path never materializes
    a (B, max_kv, heads, hd) gathered KV copy and never expands the nkv
    pool heads up to nq (no head-replicating repeat/broadcast): no
    equation in its jaxpr produces a value of either shape.  The same
    detector FIRES on the pure-XLA fallback, which is exactly the gather
    materialization the kernel removes."""
    from repro.common.jaxprs import iter_eqns

    def mutate(c):
        return c.replace(num_kv_heads=2)            # GQA: group = 2
    cfg, rt, params, pa, eng = _smoke_engine(max_len=16, mutate=mutate)
    b, max_kv, nq, nkv, hd = 2, 16, cfg.num_heads, cfg.num_kv_heads, \
        cfg.head_dim
    num_rows = 5 * 4
    banned = {
        (b, max_kv, nkv, hd),           # gathered KV copy (pool heads)
        (b, max_kv, nq, hd),            # gathered + GQA-expanded copy
        (nq, num_rows, hd),             # pool-sized head expansion
    }
    cache = mdl.init_paged_cache(cfg, b, num_rows)
    row_idx = jnp.stack([jnp.asarray(PageTable(4, max_kv, [1, 2]).row_idx()),
                         jnp.asarray(PageTable(4, max_kv, [3, 4]).row_idx())])
    toks = jnp.asarray([[5], [7]], jnp.int32)
    pos = jnp.asarray([3, 1], jnp.int32)
    premat = eng._materialized()

    def shapes(step):
        closed = jax.make_jaxpr(step)(params, cache, toks, pos, row_idx,
                                      pa, premat)
        out = set()
        for eqn in iter_eqns(closed.jaxpr):
            for v in eqn.outvars:
                if hasattr(v.aval, "shape"):
                    out.add(tuple(v.aval.shape))
        return out

    kern = shapes(build_paged_serve_step(cfg, rt, page_size=4))
    assert not (kern & banned), kern & banned
    # detector sanity: the XLA gather fallback DOES materialize the copy
    xcfg = cfg.replace(paged_attn_kernel=False)
    xla = shapes(build_paged_serve_step(xcfg, rt, page_size=4))
    assert (b, max_kv, nkv, hd) in xla
    eng.close()


def test_scheduler_matches_engine_generate():
    """End-to-end single-request trace equals the fixed-batch engine."""
    cfg, rt, params, pa, eng = _smoke_engine()
    base = eng.generate(np.asarray([[1, 2, 3]], np.int32), steps=6)
    with RequestScheduler(eng, max_slots=2, num_pages=9, page_size=4,
                          max_kv=32) as rs:
        r = rs.submit([1, 2, 3], max_new_tokens=6)
        rs.run(max_ticks=100)
        assert r.state == DONE and r.finish_reason == "length"
        np.testing.assert_array_equal(r.output(), base[0])
        assert rs.pool.free_pages == rs.pool.usable_pages   # all freed
    eng.close()


def test_mixed_length_concurrent_parity():
    """Mixed prompt lengths decoded CONCURRENTLY each match their own
    dense-cache baseline — per-sequence positions and page tables do not
    leak across slots."""
    cfg, rt, params, pa, eng = _smoke_engine()
    prompts = [[7], [1, 2, 3], [4, 5, 6, 8, 9], [2, 4, 6, 8, 1, 3, 5]]
    base = {i: eng.generate(np.asarray([p], np.int32), steps=5)[0]
            for i, p in enumerate(prompts)}
    with RequestScheduler(eng, max_slots=4, num_pages=17, page_size=4,
                          max_kv=32) as rs:
        reqs = [rs.submit(p, max_new_tokens=5) for p in prompts]
        rs.run(max_ticks=200)
        for i, r in enumerate(reqs):
            assert r.state == DONE
            np.testing.assert_array_equal(r.output(), base[i])
        assert max(r.preemptions for r in reqs) == 0    # pool was ample
    eng.close()


def test_preemption_is_lossless_and_youngest_first():
    """A pool that cannot hold both sequences preempts the YOUNGEST; the
    victim resumes via re-prefill and still produces the exact baseline
    trace."""
    cfg, rt, params, pa, eng = _smoke_engine()
    base_a = eng.generate(np.asarray([[1, 2, 3]], np.int32), steps=10)[0]
    base_b = eng.generate(np.asarray([[4, 5, 6]], np.int32), steps=10)[0]
    with RequestScheduler(eng, max_slots=2, num_pages=5, page_size=4,
                          max_kv=16) as rs:
        a = rs.submit([1, 2, 3], max_new_tokens=10)     # 13 tokens: 4 pages
        b = rs.submit([4, 5, 6], max_new_tokens=10)
        rs.run(max_ticks=300)
        assert a.state == DONE and b.state == DONE
        assert rs.requests_preempted >= 1
        assert a.preemptions == 0       # the OLDEST always progresses
        assert b.preemptions >= 1
        np.testing.assert_array_equal(a.output(), base_a)
        np.testing.assert_array_equal(b.output(), base_b)
        assert rs.robustness().requests_preempted == rs.requests_preempted
    eng.close()


# ---------------------------------------------------------------------------
# 2. typed overload results
# ---------------------------------------------------------------------------
def test_typed_rejections_never_raise():
    cfg, rt, params, pa, eng = _smoke_engine()
    with RequestScheduler(eng, max_slots=1, num_pages=5, page_size=4,
                          max_kv=16, max_queue=1) as rs:
        too_long = rs.submit(list(range(1, 15)), max_new_tokens=10)
        assert too_long.state == REJECTED
        assert too_long.finish_reason == "too_long"
        ok = rs.submit([1, 2], max_new_tokens=2)
        overflow = rs.submit([3, 4], max_new_tokens=2)
        assert overflow.state == REJECTED
        assert overflow.finish_reason == "queue_full"
        assert rs.requests_rejected == 2
        rs.run(max_ticks=50)
        assert ok.state == DONE         # the admitted one still completes
    eng.close()


def test_ttl_reaps_queued_and_wedged_requests():
    """Deadlines bound every state: a request stuck in the queue and a
    request wedged mid-decode (``serve.request_hang``) both terminate as
    TIMED_OUT, with their pages returned to the pool."""
    cfg, rt, params, pa, eng = _smoke_engine()
    now = [0.0]
    with RequestScheduler(eng, max_slots=1, num_pages=9, page_size=4,
                          max_kv=16, default_ttl_s=10.0,
                          clock=lambda: now[0]) as rs:
        active = rs.submit([1, 2], max_new_tokens=12)
        queued = rs.submit([3, 4], max_new_tokens=2, ttl_s=5.0)
        faults.inject("serve.request_hang", exc=RuntimeError("wedge"),
                      only=active.rid, times=None)
        for _ in range(4):
            rs.step()                   # the hung request makes no progress
        assert active.state == "DECODING" and len(active.generated) == 1
        now[0] = 6.0
        rs.step()                       # queued TTL fires first
        assert queued.state == TIMED_OUT and queued.finish_reason == "ttl"
        now[0] = 11.0
        rs.step()
        assert active.state == TIMED_OUT
        assert rs.requests_timed_out == 2
        assert rs.pool.free_pages == rs.pool.usable_pages
    eng.close()


def test_prefill_crash_retries_then_rejects():
    cfg, rt, params, pa, eng = _smoke_engine()
    # one crash: the bounded retry admits it on the next tick
    faults.inject("serve.prefill_crash", exc=RuntimeError("boom"), times=1)
    with RequestScheduler(eng, max_slots=1, num_pages=9, page_size=4,
                          max_kv=16, max_prefill_retries=1) as rs:
        r = rs.submit([1, 2, 3], max_new_tokens=3)
        rs.run(max_ticks=50)
        assert r.state == DONE and r.prefill_failures == 1
    faults.clear()
    # crashes past the budget: typed REJECTED, pages all back
    faults.inject("serve.prefill_crash", exc=RuntimeError("boom"), times=None)
    with RequestScheduler(eng, max_slots=1, num_pages=9, page_size=4,
                          max_kv=16, max_prefill_retries=1) as rs:
        r = rs.submit([1, 2, 3], max_new_tokens=3)
        rs.run(max_ticks=50)
        assert r.state == REJECTED and r.finish_reason == "prefill_crash"
        assert rs.pool.free_pages == rs.pool.usable_pages
    eng.close()


def test_page_exhaustion_at_admission_waits_then_admits():
    """An armed ``serve.page_exhausted`` makes admission see a full pool:
    arrivals WAIT (stay QUEUED, nothing raises) and admit once the fault
    budget runs out — same dynamics as a genuinely full pool draining."""
    cfg, rt, params, pa, eng = _smoke_engine()
    faults.inject("serve.page_exhausted", exc=RuntimeError("full"), times=2)
    with RequestScheduler(eng, max_slots=1, num_pages=9, page_size=4,
                          max_kv=16) as rs:
        r = rs.submit([1, 2], max_new_tokens=2)
        rs.step()
        assert r.state == "QUEUED"      # first alloc attempt: exhausted
        rs.run(max_ticks=50)
        assert r.state == DONE
    eng.close()


# ---------------------------------------------------------------------------
# 3. the chaos soak — the scheduler invariant under all three sites
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_soak_every_request_terminates(seed):
    """All three serve fault sites armed in RANDOMIZED order with
    randomized budgets: the decode path never raises, every submitted
    request terminates in exactly one of DONE/REJECTED/TIMED_OUT, and the
    pool drains back to empty."""
    rng = random.Random(seed)
    cfg, rt, params, pa, eng = _smoke_engine()
    with RequestScheduler(eng, max_slots=2, num_pages=7, page_size=4,
                          max_kv=16, max_queue=8,
                          default_ttl_s=3.0) as rs:
        reqs = [rs.submit([rng.randrange(1, 500) for _ in
                           range(rng.randrange(1, 6))],
                          max_new_tokens=rng.randrange(1, 8))
                for _ in range(6)]
        hang_rid = rng.choice(reqs).rid
        sites = [
            lambda: faults.inject("serve.page_exhausted",
                                  exc=RuntimeError("full"),
                                  times=rng.randrange(1, 4)),
            lambda: faults.inject("serve.request_hang",
                                  exc=RuntimeError("wedge"),
                                  only=hang_rid, times=None),
            lambda: faults.inject("serve.prefill_crash",
                                  exc=RuntimeError("boom"),
                                  times=rng.randrange(1, 3)),
        ]
        rng.shuffle(sites)
        for arm in sites:
            arm()
        rs.run(max_ticks=3000)          # never raises
        states = [r.state for r in reqs]
        assert all(s in TERMINAL for s in states), states
        # exactly-one-terminal is structural (state is a single field);
        # the counters must account for every non-DONE outcome
        n_done = sum(s == DONE for s in states)
        assert n_done == rs.requests_completed
        assert (len(reqs) - n_done
                == rs.requests_rejected + rs.requests_timed_out)
        assert rs.pool.free_pages == rs.pool.usable_pages   # no leaks
    eng.close()


# ---------------------------------------------------------------------------
# 4. publication consistency
# ---------------------------------------------------------------------------
def test_prefill_straddling_publication_reads_one_version():
    """A request admitted while a publication is staged prefills against
    ONE consistent (plan, version) snapshot — the promoted new one — and
    its whole trace matches a fresh engine at that version."""
    cfg, rt, params, pa, eng = _smoke_engine()
    params2 = mdl.init_params(cfg, jax.random.PRNGKey(7))
    eng.publish_params(params2, wait=True)
    assert eng.version == 0 and eng._staged is not None     # staged only
    with RequestScheduler(eng, max_slots=1, num_pages=9, page_size=4,
                          max_kv=32) as rs:
        r = rs.submit([1, 2, 3], max_new_tokens=6)
        rs.run(max_ticks=50)
        assert r.state == DONE
        assert eng.version == 1         # the prefill snapshot promoted it
    with Engine(cfg, rt, params2, max_len=32, pa=pa, version=1) as fresh:
        base = fresh.generate(np.asarray([[1, 2, 3]], np.int32), steps=6)
    np.testing.assert_array_equal(r.output(), base[0])
    eng.close()


# ---------------------------------------------------------------------------
# 5. backpressure into the fleet router
# ---------------------------------------------------------------------------
def test_route_orders_replicas_by_scheduler_load():
    cfg, rt, params, pa, eng_a = _smoke_engine()
    eng_b = Engine(cfg, rt, params, max_len=32, pa=pa, name="b")
    bus = PublicationBus([("a", eng_a), ("b", eng_b)])
    assert bus.route() == [eng_a, eng_b]    # unloaded: registration order
    with RequestScheduler(eng_a, max_slots=1, num_pages=9, page_size=4,
                          max_kv=16, max_queue=8) as rs:
        for i in range(4):
            rs.submit([1, 2], max_new_tokens=2)
        h = eng_a.health()
        assert h.queue_depth == 4 and h.kv_used_frac == 0.0
        assert bus.route() == [eng_b, eng_a]    # loaded replica last
        st = bus.health()
        assert st["a"].queue_depth == 4 and st["b"].queue_depth == 0
        rs.run(max_ticks=200)
        assert bus.route() == [eng_a, eng_b]    # drained: order restored
    # probe detached on close: health reads unloaded again
    assert eng_a.health().queue_depth == 0
    bus.close()
    eng_a.close()
    eng_b.close()


# ---------------------------------------------------------------------------
# 6. the collective law on a real mesh (subprocess, 8 host devices)
# ---------------------------------------------------------------------------
PAGED_LAW_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.common.jaxprs import find_prims
from repro.configs.gpt_moe_s import smoke
from repro.core.placement import homogeneous_sharding
from repro.core.schedule import sparse_materialization
from repro.core import moe as moe_core
from repro.models import model as mdl
from repro.serve.engine import Engine
from repro.serve.kv_pool import PageTable

cfg = smoke()
EP = 4
mesh = jax.make_mesh((2, EP), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
L = moe_core.num_moe_layers(cfg)
E = cfg.moe.num_experts
sh = homogeneous_sharding(L, E, EP)
plan = sparse_materialization(sh, np.ones((L, E)), t=4, m=1, impl="ring")
pa = moe_core.plan_to_arrays(plan)
rt = mdl.Runtime(mesh=mesh, moe=moe_core.MoERuntime(
    mesh=mesh, batch_axes=("data",), impl="ring", m=1, capacity=16,
    use_pallas=True))
params = mdl.init_params(cfg, jax.random.PRNGKey(0), ep=EP)
COLL = {"ppermute", "all_gather"}

eng = Engine(cfg, rt, params, max_len=16, pa=pa)
premat = eng._materialized()
cache = mdl.init_paged_cache(cfg, 2, 5 * 4)
row_idx = jnp.stack([jnp.asarray(PageTable(4, 16, [1, 2]).row_idx()),
                     jnp.asarray(PageTable(4, 16, [3, 4]).row_idx())])
toks = np.asarray([[5], [7]], np.int32)
pos = jnp.asarray([3, 1], jnp.int32)

step = lambda p, c, t, pm: mdl.decode_step(cfg, rt, p, c, t, pos, pa,
                                           premat=pm, row_idx=row_idx,
                                           page_size=4)
n_step = len(find_prims(step, params, cache, toks, premat, prims=COLL))
assert n_step == 0, n_step          # the premat paged KERNEL step: ZERO spAG
n_nopm = len(find_prims(lambda p, c, t: mdl.decode_step(
    cfg, rt, p, c, t, pos, pa, row_idx=row_idx, page_size=4), params,
    cache, toks, prims=COLL))
assert n_nopm > 0, n_nopm           # without premat the spAG is in-step
print(f"paged step collectives with/without premat: {n_step}/{n_nopm}")
eng.close()
print("PAGED_LAW_OK")
"""


def test_paged_decode_step_zero_spag_on_mesh(dist):
    out = dist(PAGED_LAW_SCRIPT, n_devices=8)
    assert "PAGED_LAW_OK" in out
