"""Plain reference for the Ling-linear configurations (Ling-3.0-flash's
language model): the forward pass and next-token loss in straightforward
`jax.numpy`, float32, matmuls at `highest` precision, no kernels, no cache,
no chunks (the delta rule a position at a time), no absorbed projections
(every head's key and value are formed), every held expert looped plainly.

`h` is `(B, S, hidden)`. Every layer: `h += Mixer(RMSNorm(h)); h +=
FFN(RMSNorm(h))` (eps `rms_norm_eps`); then the final RMSNorm and an untied
output head. A layer's mixer is decided by its PUBLISHED index p (the file's
`published_layers`): latent attention where `(p + 1) % layer_group_size ==
0`, KDA elsewhere. With H heads of d = `head_dim`:

- **KDA** (Kimi Linear, arXiv:2510.26692; the open `fla` library's
  `KimiDeltaAttention`): `[q, k, v] = silu(causal depthwise conv(x W_qkv))`
  (kernel `short_conv_kernel_size`, no bias; the three convolutions side by
  side); `q, k <- x / sqrt(sum x^2 + 1e-6)` a head, `q <- q d^-0.5`;
  `[f, z] = x W_fg`; `g = kda_lower_bound * sigmoid(exp(A_log)[head] *
  (f + dt_bias))` a channel; `beta = sigmoid(x W_b)` a head. A head's state
  `S` (d x d), zero at the start, a position at a time:
  `S <- diag(exp(g_t)) S`; `S <- S + beta_t k_t (v_t - S^T k_t)^T`;
  `o_t = S^T q_t`. Output `(RMSNorm(o_t) w * sigmoid(z)) W_o`, the norm over
  each head's d with one d-weight.
- **MLA** (DeepSeek-V2's, no query compression): `q = x W_q` -> H x (nope +
  rope); `[c, k_r] = x W_kva`; `c <- RMSNorm(c)`; `[k_nope, v] = c W_kvb`
  (a head's first `qk_nope_head_dim` columns its key); `q <- RMSNorm(q)`
  over each head's whole width, `k_r <- RMSNorm(k_r)`; rotary (theta
  `rope_theta`, pairs (i, i + rope / 2)) on `q`'s rope part and on `k_r`,
  which all heads share; causal softmax of `q . [k_nope | k_r]` times
  `(nope + rope)^-0.5`; a head's output times `sigmoid(x W_g)[head]`; `W_o`.
- **FFN**: `W_down(silu(W_gate x) * W_up x)`, dense at `intermediate_size`
  for the first `first_k_dense_replace` layers; after them experts: `s =
  sigmoid(x W_r)` over all `router_experts`; the choice on `s + bias`: the
  experts lie in `n_group` groups, a group's score is the sum of its best
  two, the best `topk_group` groups stay, the best `num_experts_per_tok`
  experts inside them are taken; weights `s[taken] / sum(s[taken]) *
  routed_scaling_factor`; the result is the sum over the taken experts THAT
  ARE HELD HERE (`num_experts` from `expert_offset` on) plus the shared
  expert. What the absent experts would add is left out, as the
  configuration's `deployment` says.

Departures: none in the mathematics, as far as the catalog's config settles
it; what it does not is listed under `assumed` in the configuration's file.
The layers are walked in Python over the program's weight tree
(`layers/layer_<i>`, `layer_<i>_mlp` and their norms), which is only how the
weights are stored; weights are upcast a layer (an expert) at a time so that
the float32 copy fits beside the bf16 tree. It reads the program's weight
TREE and none of its code.

THE ROUTING MARGIN is measured where bf16 rounding of the hidden state acts,
in the router's logits (`nemotron_h_reference.py` says why), and of the part
of the choice that THIS CHIP computes. Two experts that swap places at the
edge of the top `k` change this chip's result only if one of them is held
here: the absent ones are left out whichever is taken, and the weights' sum
moves by the two scores' difference, continuously. So the experts' margin is
the smaller of (the lowest HELD expert taken - the best one left) and (the
last one taken - the best HELD one left), inside the groups that stay, over
the sigmoid's slope `s (1 - s)` at the edge (the larger of the last taken's
and the best left's); with every expert held it is the README's pair. Over
the choice of GROUPS it is the gap between the last group that stays and the
best that falls out, whichever holds them (a group that changes moves the
edge for every expert), over the larger of the two groups' summed slopes of
their best two (a group's score moves by that when its two logits move
together). A row's margin is the smaller of the two, the smallest over its
layers. At the published router (512 logits of spread 1, 4 of 8 groups, top
8, five layers) about a fifth of seeded rows reach `MARGIN_SAFE`; with the
pair taken over absent experts too it would be a tenth (a simulation of the
router alone; PERF.md, PR 47, has what the chip read).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(t):
    return t.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(w)


def _kinds(cfg):
    kept = cfg.get("published_layers") or range(cfg["num_hidden_layers"])
    return ["A" if (p + 1) % cfg["layer_group_size"] == 0 else "K"
            for p in kept]


def _kda(x, p, cfg):
    nh, d, kw = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["short_conv_kernel_size"])
    b, s, _ = x.shape
    qkv = x @ _f(p["qkv_proj"]["kernel"])
    padded = jnp.pad(qkv, ((0, 0), (kw - 1, 0), (0, 0)))
    w = _f(p["conv_kernel"])                                    # (K, 3 H d)
    conv = jax.nn.silu(sum(w[j] * padded[:, j:j + s] for j in range(kw)))
    q, k, v = (t.reshape(b, s, nh, d) for t in jnp.split(conv, 3, axis=-1))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * d ** -0.5, unit(k)
    f, z = jnp.split(x @ _f(p["fg_proj"]["kernel"]), 2, axis=-1)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f(p["A_log"]))[:, None]
        * (f + _f(p["dt_bias"])).reshape(b, s, nh, d))
    beta = jax.nn.sigmoid(x @ _f(p["b_proj"]["kernel"]))        # (B, S, H)

    def step(S, t):                       # S (B, H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., :, None] * S
        u = v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + b_t[..., None, None] * k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, nh, d, d), F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), p["norm_weight"], cfg["rms_norm_eps"])
    return (o.reshape(b, s, nh * d) * jax.nn.sigmoid(z)) \
        @ _f(p["o_proj"]["kernel"])


def _rope(x, theta):
    """x (B, S, H, D), positions 0 .. S - 1, pairs (i, i + D / 2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(x, p, cfg):
    nh, dn, dr, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                            cfg["kv_lora_rank"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    q = (x @ _f(p["q_proj"]["kernel"])).reshape(b, s, nh, dn + dr)
    c, k_r = jnp.split(x @ _f(p["kv_a_proj"]["kernel"]), [rank], axis=-1)
    c = _rms(c, p["kv_a_norm"]["weight"], eps)
    kv = (c @ _f(p["kv_b_proj"])).reshape(b, s, nh, dn + dv)
    q = _rms(q, p["q_norm"]["weight"], eps)
    k_r = _rms(k_r, p["k_norm"]["weight"], eps)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k_r = _rope(k_r[:, :, None], theta)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, s, nh, dr))],
                        axis=-1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], logits,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1),
                     kv[..., dn:])
    gate = jax.nn.sigmoid(x @ _f(p["g_proj"]["kernel"]))        # (B, S, H)
    return (out * gate[..., None]).reshape(b, s, nh * dv) \
        @ _f(p["o_proj"]["kernel"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def _in_kept_groups(chosen_by, cfg):
    """(what the top-k is taken of: `chosen_by` (..., E) with the experts of
    the groups that fell out at -inf; the margin of the choice of groups in
    the router's logits, given the scores' slopes, as a function)."""
    n, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    e = chosen_by.shape[-1]
    grouped = chosen_by.reshape(chosen_by.shape[:-1] + (n, e // n))
    best, at = jax.lax.top_k(grouped, 2)                        # (..., G, 2)
    score = jnp.sum(best, axis=-1)                              # (..., G)
    if n == 1 or keep >= n:
        return chosen_by, lambda slopes: jnp.full(score.shape[:-1], jnp.inf)
    order = jnp.argsort(-score, axis=-1)
    rank = jnp.argsort(order, axis=-1)                          # a group's place
    stays = rank < keep
    limited = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(
        chosen_by.shape)

    def margin(slopes):
        """gap between the last group that stays and the best that falls
        out, over the larger of their summed slopes of their best two"""
        per = jnp.sum(jnp.take_along_axis(
            slopes.reshape(grouped.shape), at, axis=-1), axis=-1)   # (..., G)
        pair = jnp.take_along_axis(order, jnp.stack(
            [jnp.full(order.shape[:-1], keep - 1),
             jnp.full(order.shape[:-1], keep)], axis=-1), axis=-1)
        s_pair = jnp.take_along_axis(score, pair, axis=-1)
        return (s_pair[..., 0] - s_pair[..., 1]) / jnp.max(
            jnp.take_along_axis(per, pair, axis=-1), axis=-1)
    return limited, margin


def _experts(x, p, cfg):
    """(this chip's part of the layer's result, the routing margin at every
    position): the taken experts that are held, and the shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    scores = jax.nn.sigmoid(x @ _f(p["gate"]["wg"]))            # all of them
    limited, group_margin = _in_kept_groups(
        scores + _f(p["gate"]["bias"]), cfg)
    top, taken = jax.lax.top_k(limited, k + 1)   # the k taken, the best left
    idx = taken[..., :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]

    def one(out, e):
        gate, up, down, local = e
        weight = jnp.sum(jnp.where(idx == local + offset, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(x, gate, up, down), None

    ex = p["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    sh = p["shared_expert"]
    out = out + _swiglu(x, sh["gate"][0], sh["up"][0], sh["down"][0])
    # the margin, in the router's logits, of the choice among HELD experts
    slopes = scores * (1.0 - scores)
    slope = jnp.max(jnp.take_along_axis(slopes, taken[..., k - 1:], axis=-1),
                    axis=-1)
    ids = jnp.arange(scores.shape[-1])
    here = (ids >= offset) & (ids < offset + held)
    is_taken = jnp.any(idx[..., None] == ids, axis=-2)          # (..., E)
    low_held = jnp.min(jnp.where(is_taken & here, limited, jnp.inf), axis=-1)
    best_held = jnp.max(jnp.where(~is_taken & here, limited, -jnp.inf),
                        axis=-1)
    expert_margin = jnp.minimum(low_held - top[..., k],
                                top[..., k - 1] - best_held) / slope
    return out, jnp.minimum(expert_margin, group_margin(slopes))


def _walk(params, ids, cfg):
    """(hidden states after the final norm (B, S, hidden), the routing
    margin (B, S), the smallest over the expert layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
        for i, kind in enumerate(_kinds(cfg)):
            x = _rms(h, layers[f"layer_{i}_norm"]["weight"], eps)
            mixer = _kda if kind == "K" else _mla
            h = h + mixer(x, layers[f"layer_{i}"], cfg)
            x = _rms(h, layers[f"layer_{i}_mlp_norm"]["weight"], eps)
            p = layers[f"layer_{i}_mlp"]
            if i < cfg["first_k_dense_replace"]:
                h = h + _swiglu(x, p["gate_proj"]["kernel"],
                                p["up_proj"]["kernel"],
                                p["down_proj"]["kernel"])
            else:
                out, m = _experts(x, p, cfg)
                h = h + out
                margin = jnp.minimum(margin, m)
        return _rms(h, params["norm_f"]["weight"], eps), margin


def hidden_states(params, ids, cfg):
    return _walk(params, ids, cfg)[0]


def _head(h, params):
    with jax.default_matmul_precision("highest"):
        return h @ _f(params["lm_head"])


def _last(h, last, params):
    return _head(jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0],
                 params)


def last_logits(params, ids, last, cfg):
    """(B, vocab) float32 logits at position `last[b]` of each row."""
    return _last(hidden_states(params, ids, cfg), last, params)


def last_logits_and_margin(params, ids, last, cfg):
    """(logits, routing margin) from one pass: the margin in the router's
    logits over the choice of groups and of experts (the module text says
    how), the smallest over the expert layers, at position `last`."""
    h, margin = _walk(params, ids, cfg)
    return _last(h, last, params), \
        jnp.take_along_axis(margin, last[:, None], axis=1)[:, 0]


def logits_at(params, ids, positions, cfg):
    """(B, len(positions), vocab) float32 logits at the given positions of
    every row, from one full pass."""
    return logits_and_margin_at(params, ids, positions, cfg)[0]


def logits_and_margin_at(params, ids, positions, cfg):
    """(`logits_at`, the routing margin there (B, len(positions))), from one
    full pass (the builder's decode-logits tool)."""
    h, margin = _walk(params, ids, cfg)
    at = jnp.asarray(positions)
    return _head(h[:, at], params), margin[:, at]


def mean_loss(params, ids, cfg):
    """Mean next-token cross-entropy over rows of `ids` (B, S), one row's
    logits at a time."""
    def row(r):
        h = hidden_states(params, r[None], cfg)[0, :-1]
        logp = jax.nn.log_softmax(_head(h, params), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, r[1:, None], axis=1))
    return jnp.mean(jax.lax.map(row, ids))
