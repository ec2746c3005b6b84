"""The reference's OLMoE options and its group capacity rule.

Each option left off (``qk_norm: false``, ``norm_eps: 1e-6``,
``moe.norm_topk_prob: true``) computes bit for bit what the file without
the key computes.  With the options on, the reference's loss matches a
forward pass written out here, token by token and head by head in
float64: QK-norm over the whole q and k projections before
rotary, top-k weights left as the softmax gave them, the norms'
epsilon, and an expert keeping the first ``cap`` of its assignments in
each group of consecutive tokens."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

CFG = {
    "num_layers": 2, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 8, "vocab_size": 64, "act": "silu_glu", "norm": "rms",
    "tie_embeddings": False, "rope_theta": 10000.0,
    "moe": {"num_experts": 4, "experts_per_token": 2, "d_ff": 16,
            "capacity_factor": 1.0, "slots_per_device": 0,
            "aux_loss_weight": 0.01, "router_z_loss_weight": 0.001},
}
B, S = 2, 16
NO_DROPS = B * S * 2


def _with(cfg, key, value):
    if key.startswith("moe."):
        return dict(cfg, moe=dict(cfg["moe"], **{key[4:]: value}))
    return dict(cfg, **{key: value})


def _tokens(seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, S + 1)).astype(np.int32)


def _params(cfg):
    p = reference.init_params(cfg, jax.random.PRNGKey(1))
    # scales away from 1, so a norm whose scale is not applied shows
    rng = np.random.default_rng(2)
    return {k: (v if k not in ("ln1", "ln2", "final_norm", "q_norm",
                               "k_norm")
                else v * (1 + 0.5 * rng.standard_normal(v.shape))
                .astype(np.float32))
            for k, v in p.items()}


def _loss_and_grads(cfg, cap):
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference.loss_fn(p, t, cfg, cap),
        has_aux=True))(_params(cfg), jnp.asarray(_tokens()))
    return loss, grads


@pytest.mark.parametrize("key,off", [("qk_norm", False), ("norm_eps", 1e-6),
                                     ("moe.norm_topk_prob", True)])
def test_an_option_off_is_the_computation_without_it(key, off):
    cap = 20
    base_loss, base_grads = _loss_and_grads(CFG, cap)
    loss, grads = _loss_and_grads(_with(CFG, key, off), cap)
    assert np.asarray(loss).tobytes() == np.asarray(base_loss).tobytes()
    assert set(grads) == set(base_grads)
    for k in grads:
        assert np.array_equal(np.asarray(grads[k]),
                              np.asarray(base_grads[k])), k


# ------------------------------------------------- the forward written out
def _hand_loss(p, tokens, cfg, cap, groups):
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    eps = cfg.get("norm_eps", 1e-6)
    moe = cfg["moe"]
    d, nh, nkv, hd = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                      cfg["head_dim"])
    e_num, k_top = moe["num_experts"], moe["experts_per_token"]

    def norm(x, scale, kind):
        if kind == "ln":
            x = x - x.mean(-1, keepdims=True)
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def rope(x):                                    # (S, H, hd)
        half = hd // 2
        inv = 1.0 / cfg["rope_theta"] ** (np.arange(half) / half)
        ang = np.arange(x.shape[0])[:, None, None] * inv[None, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x1 * np.sin(ang) + x2 * np.cos(ang)], -1)

    def softmax(v):
        v = np.exp(v - v.max(-1, keepdims=True))
        return v / v.sum(-1, keepdims=True)

    def silu(v):
        return v / (1 + np.exp(-v))

    inp, labels = tokens[:, :-1], tokens[:, 1:]
    b, s = inp.shape
    t_all = b * s
    x = p["embed"][inp] * math.sqrt(d)
    aux = z = 0.0
    for layer in range(cfg["num_layers"]):
        h = norm(x, p["ln1"][layer], cfg["norm"])
        for r in range(b):
            q = np.einsum("sd,dnh->snh", h[r], p["wq"][layer])
            k = np.einsum("sd,dnh->snh", h[r], p["wk"][layer])
            v = np.einsum("sd,dnh->snh", h[r], p["wv"][layer])
            if cfg.get("qk_norm", False):
                q = norm(q.reshape(s, nh * hd), p["q_norm"][layer],
                         "rms").reshape(s, nh, hd)
                k = norm(k.reshape(s, nkv * hd), p["k_norm"][layer],
                         "rms").reshape(s, nkv, hd)
            q, k = rope(q), rope(k)
            o = np.zeros((s, nh, hd))
            for n in range(nh):
                kv = n // (nh // nkv)
                sc = q[:, n] @ k[:, kv].T / math.sqrt(hd)
                sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
                o[:, n] = softmax(sc) @ v[:, kv]
            x[r] = x[r] + np.einsum("snh,nhd->sd", o, p["wo"][layer])
        h = norm(x, p["ln2"][layer], cfg["norm"]).reshape(t_all, d)
        logits = h @ p["router"][layer]
        probs = softmax(logits)
        counts = np.zeros(e_num)
        kept = np.zeros((groups, e_num), int)
        y = np.zeros((t_all, d))
        for tok in range(t_all):
            top = np.argsort(-probs[tok], kind="stable")[:k_top]
            w = probs[tok, top]
            if moe.get("norm_topk_prob", True):
                w = w / w.sum()
            g = tok // (t_all // groups)
            for wj, e in zip(w, top):
                counts[e] += 1
                if kept[g, e] < cap:
                    kept[g, e] += 1
                    a = silu(h[tok] @ p["wi"][layer, e]) \
                        * (h[tok] @ p["wg"][layer, e])
                    y[tok] += wj * (a @ p["wo_e"][layer, e])
        aux += e_num * np.sum(counts / counts.sum() * probs.mean(0))
        lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
            + logits.max(-1)
        z += np.mean(lse ** 2)
        x = x + y.reshape(b, s, d)
    x = norm(x, p["final_norm"], cfg["norm"])
    lg = x @ p["unembed"]
    lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
        + lg.max(-1)
    ll = np.take_along_axis(lg, labels[..., None], -1)[..., 0]
    nll = np.mean(lse - ll)
    return nll + moe["aux_loss_weight"] * aux \
        + moe["router_z_loss_weight"] * z


CASES = {
    "off": (CFG, NO_DROPS, 1),
    "qk_norm": (_with(CFG, "qk_norm", True), NO_DROPS, 1),
    "topk_not_renormalised": (_with(CFG, "moe.norm_topk_prob", False),
                              NO_DROPS, 1),
    "norm_eps": (_with(CFG, "norm_eps", 1e-2), NO_DROPS, 1),
    "olmoe": (_with(_with(_with(CFG, "qk_norm", True), "norm_eps", 1e-5),
                    "moe.norm_topk_prob", False), NO_DROPS, 1),
    "four_groups_dropping": (CFG, 3, 4),
    "one_group_dropping": (CFG, 12, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_the_forward_written_out(case):
    cfg, cap, groups = CASES[case]
    params = _params(cfg)
    tokens = _tokens()
    loss, metrics = reference.loss_fn(params, jnp.asarray(tokens), cfg, cap,
                                      groups=groups)
    want = _hand_loss(params, tokens.astype(np.int64), cfg, cap, groups)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    if cap < NO_DROPS:
        assert float(metrics["dropped_frac"]) > 0


def test_qk_norm_leaves():
    p = reference.init_params(_with(CFG, "qk_norm", True),
                              jax.random.PRNGKey(0))
    assert p["q_norm"].shape == (2, 4 * 8)
    assert p["k_norm"].shape == (2, 2 * 8)
    assert "q_norm" not in reference.init_params(CFG, jax.random.PRNGKey(0))


def test_groups_must_divide_the_tokens():
    with pytest.raises(ValueError, match="groups"):
        reference.loss_fn(_params(CFG), jnp.asarray(_tokens()), CFG, 4,
                          groups=3)
