"""Plain float32 reference of the repository's MoE transformer family.

Independent of the program under test: nothing here imports ``repro``.
It follows the published description of a GPT-style (or OLMoE-style)
decoder with top-k routed experts, written out as straightforward
``jax.numpy`` at ``jax.default_matmul_precision("highest")``:

  x = E[tokens] * sqrt(d_model)
  per layer:  x += Attn(Norm1(x));  x += MoE(Norm2(x))
  logits = Norm_f(x) @ E^T   (or @ U when the embeddings are untied)

- Norm is LayerNorm without bias (``norm: "ln"``) or RMSNorm (``"rms"``),
  with epsilon ``norm_eps`` (default 1e-6) in every norm.
- Attention is causal multi-head attention with rotary embeddings on
  the two halves of each head (theta ``rope_theta``), scale 1/sqrt(hd).
  With ``qk_norm: true`` (OLMoE) an RMSNorm with a learned scale runs
  over the whole q projection (``num_heads * head_dim``) and the whole
  k projection (``num_kv_heads * head_dim``) before rotary: leaves
  ``q_norm`` and ``k_norm``.
- The router is a softmax over ``num_experts`` logits in float32; each
  token takes its top ``experts_per_token`` experts, weights renormalised
  to sum to 1 (GShard) unless ``moe.norm_topk_prob`` is false (OLMoE),
  which leaves them as the softmax gave them.
- Capacity follows GShard's groups: the flat (B*S) tokens split into
  ``groups`` consecutive equal parts (the tokens one device holds), and
  in each part an expert keeps at most ``capacity`` of its (token, k)
  assignments, the first in token-major (token, k) order; the rest
  contribute nothing.  One group is one cap over the whole batch.
  Experts are GELU (tanh) FFNs, or SwiGLU for ``act: "silu_glu"``.
- The loss is the mean next-token cross entropy, plus
  ``aux_loss_weight`` x the GShard balance loss and
  ``router_z_loss_weight`` x the router z-loss, each summed over layers.
- AdamW with global-norm clipping, linear warm-up and cosine decay to a
  tenth, weight decay on every parameter.

Departures from the published models, shared with the program: norms
carry no bias; GPT-MoE uses rotary positions rather than learned ones;
OLMoE is dropless, and a cell of it runs with a capacity.

Everything is blocked so that it fits at the benchmark's sizes: layers
are rematerialised one at a time, attention runs one sequence at a time,
the experts one group at a time, the output head one sequence at a time.
The caller may lay the weights out over several devices
(``bench.train_cell``); nothing here depends on that.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale (gradients pass straight through),
which is the next precision below the bfloat16 that the configuration
computes in.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6
FP8_MAX = 448.0                    # largest finite float8_e4m3fn


def _fake_fp8(x):
    """Round to float8 e4m3 with a per-tensor scale; identity gradient."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / FP8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    if quant == "fp8":
        a, b = _fake_fp8(a), _fake_fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(x, scale, kind, eps):
    if kind != "rms":
        x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * scale


def _rope(x, theta):
    """x: (S, H, hd) -> rotated, rotate-half convention."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _act(h, act):
    return jax.nn.silu(h) if act.startswith("silu") else jax.nn.gelu(h)


def capacity(cfg: Dict, tokens_per_device: int, ep: int) -> int:
    """Assignments one expert keeps in one group (the tokens of one
    device): ``capacity_factor`` x the balanced share of a device's
    (token, k) assignments over its compute slots (its ``ceil(E / ep)``
    owned experts plus ``slots_per_device``)."""
    moe = cfg["moe"]
    slots = -(-moe["num_experts"] // ep) + moe["slots_per_device"]
    want = (moe["capacity_factor"] * tokens_per_device
            * moe["experts_per_token"] / (ep * slots))
    return max(1, math.ceil(want))


def init_params(cfg: Dict, key) -> Dict:
    """Seeded weights in float32: matrices normal(0, 0.02), norm scales 1."""
    d, h, kv, hd = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                    cfg["head_dim"])
    v, n = cfg["vocab_size"], cfg["num_layers"]
    moe = cfg["moe"]
    e, f = moe["num_experts"], moe["d_ff"]
    shapes = {
        "embed": (v, d),
        "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
        "wo": (n, h, hd, d), "router": (n, d, e),
        "wi": (n, e, d, f), "wo_e": (n, e, f, d),
    }
    if cfg["act"].endswith("_glu"):
        shapes["wg"] = (n, e, d, f)
    if not cfg["tie_embeddings"]:
        shapes["unembed"] = (d, v)
    keys = jax.random.split(key, len(shapes))
    p = {name: 0.02 * jax.random.normal(k, shp, jnp.float32)
         for k, (name, shp) in zip(keys, sorted(shapes.items()))}
    p["ln1"] = jnp.ones((n, d), jnp.float32)
    p["ln2"] = jnp.ones((n, d), jnp.float32)
    if cfg.get("qk_norm", False):
        p["q_norm"] = jnp.ones((n, h * hd), jnp.float32)
        p["k_norm"] = jnp.ones((n, kv * hd), jnp.float32)
    p["final_norm"] = jnp.ones((d,), jnp.float32)
    return p


LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
              "router", "wi", "wg", "wo_e")


def _attention(lp, h, cfg, quant):
    """h: (B, S, D) normed; one sequence at a time."""
    hd = cfg["head_dim"]
    group = cfg["num_heads"] // cfg["num_kv_heads"]
    eps = cfg.get("norm_eps", NORM_EPS)

    def proj(hs, w, norm):
        y = _mm("sd,dnh->snh", hs, lp[w], quant)
        if norm in lp:                          # QK-norm over all heads
            s, n, _ = y.shape
            y = _norm(y.reshape(s, n * hd), lp[norm], "rms",
                      eps).reshape(s, n, hd)
        return y

    @jax.checkpoint
    def one(hs):
        q = _rope(proj(hs, "wq", "q_norm"), cfg["rope_theta"])
        k = _rope(proj(hs, "wk", "k_norm"), cfg["rope_theta"])
        v = _mm("sd,dnh->snh", hs, lp["wv"], quant)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = _mm("qnh,knh->nqk", q, k, quant) / math.sqrt(hd)
        n = hs.shape[0]
        causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        o = _mm("nqk,knh->qnh", jax.nn.softmax(s, -1), v, quant)
        return _mm("snh,nhd->sd", o, lp["wo"], quant)

    return jax.lax.map(one, h)


def _moe(lp, h, cfg, cap, groups, quant):
    """h: (T, D) normed tokens in ``groups`` consecutive parts of T/groups,
    ``cap`` kept per (part, expert).  Returns (y, aux, z, counts,
    dropped)."""
    moe = cfg["moe"]
    e, k = moe["num_experts"], moe["experts_per_token"]
    t, d = h.shape
    if t % groups:
        raise ValueError(f"{t} tokens do not split into {groups} groups")
    logits = _mm("td,de->te", h, lp["router"], quant)
    probs = jax.nn.softmax(logits, -1)
    vals, idx = jax.lax.top_k(probs, k)
    if moe.get("norm_topk_prob", True):
        vals = vals / vals.sum(-1, keepdims=True)
    flat_e = idx.reshape(-1)                                  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    # an assignment's rank among its expert's in its own group
    ranks = jnp.cumsum(onehot.reshape(groups, -1, e), 1).reshape(-1, e)
    rank = jnp.take_along_axis(ranks, flat_e[:, None], 1)[:, 0] - 1
    keep = rank < cap
    tg = t // groups
    tok = jnp.arange(t * k) // k
    # per group, an (E, cap) table of the group's token that each kept
    # assignment carries; an empty place points one past the group's
    # tokens: it reads an appended zero row, weighs nothing, writes nothing
    at = (tok // tg, flat_e, jnp.where(keep, rank, cap))
    table = jnp.full((groups, e, cap), tg, jnp.int32).at[at].set(
        tok % tg, mode="drop")
    weight = jnp.zeros((groups, e, cap), jnp.float32).at[at].set(
        vals.reshape(-1), mode="drop")

    @jax.checkpoint
    def experts(args):                  # one group at a time, so it fits
        hg, tbl, w = args
        hp = jnp.concatenate([hg, jnp.zeros((1, d), hg.dtype)])
        xe = hp[tbl]                                          # (E, cap, D)
        a = _mm("ecd,edf->ecf", xe, lp["wi"], quant)
        if "wg" in lp:
            a = _act(a, cfg["act"]) * _mm("ecd,edf->ecf", xe, lp["wg"],
                                          quant)
        else:
            a = _act(a, cfg["act"])
        ye = _mm("ecf,efd->ecd", a, lp["wo_e"], quant)
        return jnp.zeros((tg, d), jnp.float32).at[tbl].add(
            ye * w[..., None], mode="drop")

    y = jax.lax.map(experts, (h.reshape(groups, tg, d), table,
                              weight)).reshape(t, d)
    counts = onehot.sum(0).astype(jnp.float32)
    frac = jax.lax.stop_gradient(counts / counts.sum())
    aux = e * jnp.sum(frac * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    dropped = 1.0 - keep.mean()
    return y, aux, z, counts, dropped


def loss_fn(params, tokens, cfg: Dict, cap: int,
            quant: Optional[str] = None, groups: int = 1):
    """tokens: (B, S+1) int32.  Returns (loss, metrics)."""
    with jax.default_matmul_precision("highest"):
        return _loss(params, tokens, cfg, cap, quant, groups)


def _loss(params, tokens, cfg, cap, quant, groups):
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    b, s = inp.shape
    d = cfg["d_model"]
    eps = cfg.get("norm_eps", NORM_EPS)
    x = params["embed"][inp] * math.sqrt(d)
    layers = {k: params[k] for k in LAYER_KEYS if k in params}

    @jax.checkpoint
    def layer(x, lp):
        h = _norm(x, lp["ln1"], cfg["norm"], eps)
        x = x + _attention(lp, h, cfg, quant)
        h = _norm(x, lp["ln2"], cfg["norm"], eps).reshape(b * s, d)
        y, aux, z, counts, dropped = _moe(lp, h, cfg, cap, groups, quant)
        return x + y.reshape(b, s, d), (aux, z, counts, dropped)

    x, (aux, z, counts, dropped) = jax.lax.scan(layer, x, layers)
    x = _norm(x, params["final_norm"], cfg["norm"], eps)
    head = (params["embed"].T if cfg["tie_embeddings"]
            else params["unembed"])

    @jax.checkpoint
    def xent(xl):
        xs, ls = xl
        logits = _mm("sd,dv->sv", xs, head, quant)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, ls[:, None], -1)[:, 0]
        return jnp.sum(lse - ll)

    nll = jax.lax.map(xent, (x, labels)).sum() / (b * s)
    moe = cfg["moe"]
    loss = (nll + moe["aux_loss_weight"] * aux.sum()
            + moe["router_z_loss_weight"] * z.sum())
    return loss, {"xent": nll, "expert_counts": counts,
                  "dropped_frac": dropped.mean()}


# ---------------------------------------------------------------- AdamW
def lr_at(opt: Dict, count):
    """Learning rate of the update that brings the step count to ``count``
    (linear warm-up, cosine decay to a tenth)."""
    count = jnp.asarray(count, jnp.float32)
    warm = jnp.minimum(count / max(opt["warmup_steps"], 1), 1.0)
    total = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = jnp.clip((count - opt["warmup_steps"]) / total, 0.0, 1.0)
    return opt["learning_rate"] * warm * (0.1 + 0.9 * 0.5
                                          * (1 + jnp.cos(jnp.pi * frac)))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(tree)))


def adamw(params, grads, mu, nu, count, opt: Dict):
    """One AdamW update after global-norm clipping.  ``count`` is the
    number of updates made before this one.  Returns (params, mu, nu,
    clipped grads)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    n = count + 1
    lr = lr_at(opt, n)
    b1, b2 = opt["beta1"], opt["beta2"]
    c1, c2 = 1 - b1 ** n, 1 - b2 ** n
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, grads


def make_train_step(cfg: Dict, opt: Dict, cap: int,
                    quant: Optional[str] = None, groups: int = 1):
    """fn(params, mu, nu, count, tokens) -> (params, mu, nu, loss, grads)
    with the clipped gradients as the optimizer gets them."""
    def step(params, mu, nu, count, tokens):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg, cap, quant, groups)
        params, mu, nu, grads = adamw(params, grads, mu, nu, count, opt)
        return params, mu, nu, loss, grads
    return step
