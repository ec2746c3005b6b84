"""Pallas kernel tests: shape/dtype sweeps, interpret=True vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import (flash_attention_ref, grouped_mlp_ref,
                               paged_decode_attention_ref)
from repro.serve.kv_pool import PageTable


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("K,T,D,F", [
    (1, 128, 128, 128), (4, 256, 128, 256), (3, 384, 256, 128),
    (8, 128, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["silu_glu", "gelu"])
def test_grouped_mlp_sweep(K, T, D, F, dtype, act):
    rng = np.random.default_rng(K * T + D)
    x = jnp.asarray(rng.standard_normal((K, T, D)) * 0.3, dtype)
    wi = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, dtype)
    wg = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, dtype) \
        if act.endswith("_glu") else None
    wo = jnp.asarray(rng.standard_normal((K, F, D)) * 0.05, dtype)
    gs = jnp.asarray(rng.integers(0, T + 1, (K,)), jnp.int32)
    y = ops.grouped_mlp(x, wi, wg, wo, gs, act=act)
    yr = grouped_mlp_ref(x.astype(jnp.float32),
                         wi.astype(jnp.float32),
                         None if wg is None else wg.astype(jnp.float32),
                         wo.astype(jnp.float32), act=act, group_sizes=gs)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               **_tol(dtype))


def test_grouped_mlp_zero_group_is_skipped():
    """Rows past the group boundary must be exactly zero (tile skipping)."""
    K, T, D, F = 2, 256, 128, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((K, T, D)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((K, D, F)) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.standard_normal((K, F, D)) * 0.1, jnp.float32)
    gs = jnp.asarray([0, 100], jnp.int32)
    y = np.asarray(ops.grouped_mlp(x, wi, None, wo, gs, act="gelu"))
    assert (y[0] == 0).all()
    assert (y[1, 100:] == 0).all()
    assert np.abs(y[1, :100]).max() > 0


@pytest.mark.parametrize("act", ["silu_glu", "gelu"])
def test_grouped_mlp_ragged_grad_matches_ref(act):
    """Forward AND gradient with ragged group_sizes vs the jnp oracle —
    the custom VJP must zero every contribution past the group boundary."""
    K, T, D, F = 3, 256, 128, 128
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((K, T, D)) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32) \
        if act.endswith("_glu") else None
    wo = jnp.asarray(rng.standard_normal((K, F, D)) * 0.05, jnp.float32)
    gs = jnp.asarray([0, 100, 256], jnp.int32)

    y_k = ops.grouped_mlp(x, wi, wg, wo, gs, act=act)
    y_r = grouped_mlp_ref(x, wi, wg, wo, act=act, group_sizes=gs)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-5, rtol=1e-4)

    def loss_kernel(a, b, c, d):
        return jnp.sum(ops.grouped_mlp(a, b, c, d, gs, act=act) ** 2)

    def loss_ref(a, b, c, d):
        return jnp.sum(grouped_mlp_ref(a, b, c, d, act=act,
                                       group_sizes=gs) ** 2)

    argnums = (0, 1, 2, 3) if wg is not None else (0, 1, 3)
    g_k = jax.grad(loss_kernel, argnums=argnums)(x, wi, wg, wo)
    g_r = jax.grad(loss_ref, argnums=argnums)(x, wi, wg, wo)
    for got, want in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
    # padded rows get exactly zero input gradient
    dx = np.asarray(g_k[0])
    assert (dx[0] == 0).all()
    assert (dx[1, 100:] == 0).all()
    assert np.abs(dx[1, :100]).max() > 0


def _grad_parity(x, wi, wg, wo, act, atol, rtol, group_sizes=None,
                 row_valid=None):
    """jax.grad through the Pallas kernels (interpret) vs the jnp oracle,
    f32 tolerances supplied by the caller."""
    kw = dict(group_sizes=group_sizes, row_valid=row_valid)

    def loss_kernel(*a):
        args = (a[0], a[1], a[2], a[3]) if wg is not None \
            else (a[0], a[1], None, a[2])
        return jnp.sum(ops.grouped_mlp(*args, group_sizes, row_valid,
                                       act=act).astype(jnp.float32) ** 2)

    def loss_ref(*a):
        args = (a[0], a[1], a[2], a[3]) if wg is not None \
            else (a[0], a[1], None, a[2])
        return jnp.sum(grouped_mlp_ref(*args, act=act,
                                       **kw).astype(jnp.float32) ** 2)

    args = (x, wi, wg, wo) if wg is not None else (x, wi, wo)
    nums = tuple(range(len(args)))
    g_k = jax.grad(loss_kernel, argnums=nums)(*args)
    g_r = jax.grad(loss_ref, argnums=nums)(*args)
    for got, want in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=atol, rtol=rtol)


@pytest.mark.parametrize("act", ["silu_glu", "gelu"])
@pytest.mark.parametrize("case", ["zero_groups", "all_full", "odd_shapes"])
def test_backward_adversarial_shapes(act, case):
    """Pallas dgrad/wgrad vs the oracle on the shapes most likely to break
    tile skipping: every group empty, every group full, and
    non-tile-multiple T/F (partial tiles on both grid axes)."""
    import zlib
    K, T, D, F = 3, 96 if case == "odd_shapes" else 256, 64, \
        200 if case == "odd_shapes" else 128
    rng = np.random.default_rng(zlib.crc32(f"{act}/{case}".encode()))
    x = jnp.asarray(rng.standard_normal((K, T, D)) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32) \
        if act.endswith("_glu") else None
    wo = jnp.asarray(rng.standard_normal((K, F, D)) * 0.05, jnp.float32)
    gs = {"zero_groups": jnp.zeros((K,), jnp.int32),
          "all_full": jnp.full((K,), T, jnp.int32),
          "odd_shapes": jnp.asarray([0, 37, T], jnp.int32)}[case]
    y = ops.grouped_mlp(x, wi, wg, wo, gs, act=act)
    yr = grouped_mlp_ref(x, wi, wg, wo, act=act, group_sizes=gs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-5, rtol=1e-4)
    _grad_parity(x, wi, wg, wo, act, 1e-4, 1e-4, group_sizes=gs)
    if case == "zero_groups":
        g = jax.grad(lambda a: jnp.sum(
            ops.grouped_mlp(a, wi, wg, wo, gs, act=act) ** 2))(x)
        assert (np.asarray(g) == 0).all()     # every tile skipped -> zero


@pytest.mark.parametrize("act", ["silu_glu", "gelu"])
def test_backward_row_valid_scattered(act):
    """The fused-dispatch layout: arbitrary scattered row validity (valid
    segments from several source devices, no compaction) — forward and
    gradients must match the oracle, invalid rows get exactly zero dx."""
    K, T, D, F = 2, 384, 64, 128
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((K, T, D)) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((K, D, F)) * 0.05, jnp.float32) \
        if act.endswith("_glu") else None
    wo = jnp.asarray(rng.standard_normal((K, F, D)) * 0.05, jnp.float32)
    # segment-prefix validity as produced by dispatch (M=3 stripes of 128),
    # including one all-invalid stripe and one all-invalid 128-row tile
    cnt = np.asarray([[128, 0, 60], [0, 5, 128]])          # (K, M)
    rv = np.zeros((K, T), bool)
    for k in range(K):
        for r in range(3):
            rv[k, r * 128:r * 128 + cnt[k, r]] = True
    rv = jnp.asarray(rv)
    y = ops.grouped_mlp(x, wi, wg, wo, None, rv, act=act)
    yr = grouped_mlp_ref(x, wi, wg, wo, act=act, row_valid=rv)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-5, rtol=1e-4)
    _grad_parity(x, wi, wg, wo, act, 1e-4, 1e-4, row_valid=rv)
    g = jax.grad(lambda a: jnp.sum(
        ops.grouped_mlp(a, wi, wg, wo, None, rv, act=act) ** 2))(x)
    assert (np.asarray(g)[~np.asarray(rv)] == 0).all()


def test_backward_bf16_params_f32_accum():
    """bf16 operands, f32 accumulation: gradients stay close to the f32
    oracle (the kernels must not accumulate in bf16)."""
    K, T, D, F = 2, 256, 128, 128
    rng = np.random.default_rng(5)
    x32 = rng.standard_normal((K, T, D)).astype(np.float32) * 0.3
    wi32 = rng.standard_normal((K, D, F)).astype(np.float32) * 0.05
    wg32 = rng.standard_normal((K, D, F)).astype(np.float32) * 0.05
    wo32 = rng.standard_normal((K, F, D)).astype(np.float32) * 0.05
    gs = jnp.asarray([100, 256], jnp.int32)
    x, wi, wg, wo = (jnp.asarray(a, jnp.bfloat16)
                     for a in (x32, wi32, wg32, wo32))

    def loss(fn, *a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    g_k = jax.grad(lambda *a: loss(
        lambda *b: ops.grouped_mlp(*b, gs, act="silu_glu"), *a),
        argnums=(0, 1, 2, 3))(x, wi, wg, wo)
    g_r = jax.grad(lambda *a: loss(
        lambda *b: grouped_mlp_ref(*b, act="silu_glu", group_sizes=gs), *a),
        argnums=(0, 1, 2, 3))(*(jnp.asarray(a) for a in
                                (x32, wi32, wg32, wo32)))
    for got, want in zip(g_k, g_r):
        assert got.dtype == jnp.bfloat16
        scale = max(float(np.abs(np.asarray(want, np.float32)).max()), 1e-6)
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(want, np.float32)).max() / scale
        assert err < 4e-2, err      # bf16 rounding only, not accumulation


@pytest.mark.parametrize("B,S,NQ,NKV,H", [
    (1, 128, 4, 4, 64), (2, 256, 4, 2, 64), (1, 384, 8, 1, 128),
    (1, 1152, 8, 4, 32),         # three 384-row blocks, 4 heads a program
    (1, 2048, 2, 2, 64)])        # two 1024-row blocks
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 128), (False, 0),
    (True, 767)])   # under a 1024 block; at 384, one tile's last key is the edge
def test_flash_attention_sweep(B, S, NQ, NKV, H, dtype, causal, window):
    rng = np.random.default_rng(S + NQ)
    q = jnp.asarray(rng.standard_normal((B, S, NQ, H)) * 0.4, dtype)
    k = jnp.asarray(rng.standard_normal((B, S, NKV, H)) * 0.4, dtype)
    v = jnp.asarray(rng.standard_normal((B, S, NKV, H)) * 0.6, dtype)
    o = ops.flash_attention(q, k, v, causal=causal, window=window)
    rep = NQ // NKV
    kk = jnp.repeat(k, rep, axis=2).astype(jnp.float32)
    vv = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
    orf = flash_attention_ref(q.astype(jnp.float32), kk, vv,
                              causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(orf),
                               **_tol(dtype))


@pytest.mark.parametrize("B,S,N,H,window", [
    (8, 2048, 12, 64, 0),        # gpt-moe-s at the benchmark cell's batch
    (1, 8192, 16, 256, 4096),    # a Gemma-2 local layer
    (2, 4096, 12, 64, 1026)])    # its first key lies one past a block edge
def test_flash_attention_tiles_fetch_only_the_band(B, S, N, H, window):
    """The grid is at most 1/16 of the 128 x 128, one-head grid, and,
    walking it in order, each q block copies in each K/V block of its
    band exactly once and no block outside it: the pipeline copies a
    block when the index map names another block than the step before."""
    from repro.kernels import flash_attention as fa
    bq, bk, hb = fa.tile_sizes(S, S, N, H, 2)
    nq, nk = S // bq, S // bk
    assert B * (N // hb) * nq * nk * 16 <= B * N * (S // 128) ** 2
    kv_map = fa.kv_index_map(bq=bq, bk=bk, nk=nk, causal=True,
                             window=window)
    pos = np.arange(S)
    for qi in range(nq):
        qpos = pos[qi * bq:(qi + 1) * bq, None]
        band = [ki for ki in range(nk)
                if ((pos[None, ki * bk:(ki + 1) * bk] <= qpos)
                    & ((window == 0)
                       | (pos[None, ki * bk:(ki + 1) * bk] > qpos - window))
                    ).any()]
        idx = [int(kv_map(0, 0, qi, ki)[2]) for ki in range(nk)]
        runs = [i for j, i in enumerate(idx) if j == 0 or i != idx[j - 1]]
        assert runs == band, (qi, runs, band)


# ---------------------------------------------------------------------------
# paged decode attention (the serving kernel) vs the gather oracle
# ---------------------------------------------------------------------------
PS, MAX_KV = 4, 16                      # 4 KV blocks per sequence


def _paged_tables(rng, positions, num_pages):
    """Adversarial page layouts: every sequence gets ceil((pos+1)/PS)
    DISTINCT pages drawn in shuffled (non-contiguous, non-monotonic)
    order — the kernel must follow the table, not the allocation order."""
    avail = list(range(1, num_pages))
    rng.shuffle(avail)
    rows = []
    for pos in positions:
        pages = [avail.pop() for _ in range(pos // PS + 1)]
        rows.append(PageTable(PS, MAX_KV, pages).row_idx())
    return jnp.asarray(np.stack(rows))


def _paged_case(seed, positions, nkv, group, h=32, num_pages=24,
                dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    b, nq = len(positions), nkv * group
    q = jnp.asarray(rng.standard_normal((b, nq, h)) * 0.4, dtype)
    # head-major pool (nkv, num_rows, h)
    k = jnp.asarray(rng.standard_normal((num_pages * PS, nkv, h)) * 0.4,
                    dtype).swapaxes(0, 1)
    v = jnp.asarray(rng.standard_normal((num_pages * PS, nkv, h)) * 0.6,
                    dtype).swapaxes(0, 1)
    row_idx = _paged_tables(rng, positions, num_pages)
    return q, k, v, row_idx, jnp.asarray(positions, jnp.int32)


def _paged_tol(dtype):
    # f32: the online softmax only reorders the reduction (≤1e-6);
    # bf16: inputs/outputs round to bf16 but accumulation stays f32.
    return dict(atol=1e-6, rtol=1e-6) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_gqa_ragged_parity(group, dtype):
    """Native-GQA ratios 1/4/8 with ragged per-sequence lengths (including
    a fresh pos=0 sequence and a full pos=MAX_KV-1 one): kernel vs the
    gather oracle.  bf16 inputs must still accumulate in f32 — the bf16
    tolerance only allows input/output rounding."""
    q, k, v, row_idx, pos = _paged_case(group * 31, [2, 7, 11, 0, 15],
                                        nkv=2, group=group, dtype=dtype)
    out = ops.paged_decode_attention(q, k, v, row_idx, pos, page_size=PS)
    ref = paged_decode_attention_ref(q, k, v, row_idx, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **_paged_tol(dtype))


def test_paged_decode_position_edges():
    """Positions exactly at 0, the last row of a page (PS-1), the first
    row of the next page (PS), and the final row of the table (MAX_KV-1)
    — the tile-skip predicate and the in-tile mask meet at every one."""
    q, k, v, row_idx, pos = _paged_case(3, [0, PS - 1, PS, MAX_KV - 1],
                                        nkv=4, group=1)
    out = ops.paged_decode_attention(q, k, v, row_idx, pos, page_size=PS)
    ref = paged_decode_attention_ref(q, k, v, row_idx, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_paged_tol(jnp.float32))


def test_paged_decode_trash_page_never_contributes():
    """Rows of the reserved trash page (page 0) park every unallocated
    table slot.  Poisoning page 0 with huge finite values must not change
    any ACTIVE sequence's output by a single bit — its tiles are either
    skipped outright or their trash rows get exactly zero probability."""
    q, k, v, row_idx, pos = _paged_case(9, [5, 0, 13], nkv=2, group=2)
    out_clean = ops.paged_decode_attention(q, k, v, row_idx, pos,
                                           page_size=PS)
    kp = k.at[:, :PS].set(1e4)
    vp = v.at[:, :PS].set(1e4)
    out_poison = ops.paged_decode_attention(q, kp, vp, row_idx, pos,
                                            page_size=PS)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))
    # parity holds on the poisoned pool too (the oracle reads the same rows)
    ref = paged_decode_attention_ref(q, kp, vp, row_idx, pos)
    np.testing.assert_allclose(np.asarray(out_poison), np.asarray(ref),
                               **_paged_tol(jnp.float32))


def test_paged_decode_fully_parked_sequence_matches_oracle():
    """A sequence with NO allocated pages (an idle slot: every row is
    trash row 0, pos 0) still runs and matches the oracle — the scheduler
    relies on idle slots being harmless, not skipped."""
    rng = np.random.default_rng(17)
    q = jnp.asarray(rng.standard_normal((2, 4, 32)) * 0.4, jnp.float32)
    k = jnp.asarray(rng.standard_normal((5 * PS, 2, 32)),
                    jnp.float32).swapaxes(0, 1)
    v = jnp.asarray(rng.standard_normal((5 * PS, 2, 32)),
                    jnp.float32).swapaxes(0, 1)
    row_idx = jnp.stack([jnp.asarray(PageTable(PS, MAX_KV, [2, 1]).row_idx()),
                         jnp.asarray(PageTable(PS, MAX_KV, []).row_idx())])
    pos = jnp.asarray([6, 0], jnp.int32)
    out = ops.paged_decode_attention(q, k, v, row_idx, pos, page_size=PS)
    ref = paged_decode_attention_ref(q, k, v, row_idx, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_paged_tol(jnp.float32))


@pytest.mark.parametrize("window", [3, 4, 7])
def test_paged_decode_sliding_window_parity(window):
    """Sliding windows that end mid-page, exactly on a page boundary, and
    span multiple pages: the tile-skip must drop tiles strictly OUTSIDE
    [pos-window, pos] and the in-tile mask must trim both edges."""
    q, k, v, row_idx, pos = _paged_case(window, [2, 7, 11, 15],
                                        nkv=2, group=2)
    out = ops.paged_decode_attention(q, k, v, row_idx, pos, page_size=PS,
                                     window=window)
    ref = paged_decode_attention_ref(q, k, v, row_idx, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_paged_tol(jnp.float32))


def test_paged_decode_softcap_parity():
    """gemma2-style logit softcap is applied in-kernel (after scale,
    before mask) — same ordering as the oracle and ``_sdpa``."""
    q, k, v, row_idx, pos = _paged_case(23, [3, 9, 14], nkv=2, group=2)
    out = ops.paged_decode_attention(q, k, v, row_idx, pos, page_size=PS,
                                     softcap=50.0)
    ref = paged_decode_attention_ref(q, k, v, row_idx, pos, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_paged_tol(jnp.float32))


def test_flash_attention_grad_flows():
    """The kernels are forward-only ops; training uses them under
    jax.checkpoint with XLA backward — verify value_and_grad works via the
    XLA reference path in attention (use_pallas only wraps forward)."""
    q = jnp.ones((1, 128, 2, 64), jnp.float32) * 0.1
    f = lambda q: ops.flash_attention(q, q, q).sum()
    val = f(q)
    assert np.isfinite(float(val))


def test_flash_attention_grad_matches_oracle():
    """The kernel's custom VJP (XLA recompute per batch row) gives the
    oracle's gradients for causal and windowed attention."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 2, 32)) * 0.5,
                           jnp.float32) for _ in range(3))
    for window in (0, 48):
        f = lambda *a: (ops.flash_attention(*a, window=window) ** 2).sum()
        r = lambda *a: (flash_attention_ref(*a, window=window) ** 2).sum()
        got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, rtol=1e-4)
