"""Plain reference for the afmoe configurations (Arcee Trinity-Mini): the
forward pass and next-token loss in straightforward `jax.numpy`, float32,
matmuls at `highest` precision, no kernels, no cache, no rings, no staged
tokens: a causal (and, in a window layer, banded) mask over the row's own
positions, a softmax over the masked row, every held expert looped plainly.

`h` is `(B, S, hidden)`, D `hidden_size`, each `N` an RMSNorm with its own
weight (eps `rms_norm_eps`). `x0 = Embed[ids] * sqrt(D)` (`mup_enabled`).
Layer i, of kind `layer_types[i]`:

    a = N_in(x)
    q = a W_q (H heads of `head_dim`), k = a W_k, v = a W_v (Hkv heads),
    g = a W_g (H x head_dim), no bias
    q = N_q(q), k = N_k(k)          over a head's values, one weight each
    sliding_attention: q, k rotated at their position, pairs (i, i +
        head_dim / 2), pair i's frequency `rope_theta^(-2i / head_dim)`;
        full_attention: NO rotary (no positional embedding at all)
    o[t] = sum_j softmax_j(q[t] . k[j] head_dim^-0.5) v[j]   over j <= t and,
        sliding, t - j < `sliding_window`; query head h reads KV head
        h // (H / Hkv)
    x = x + N_post_attn((o * sigmoid(g)) W_o)
    m = N_pre_mlp(x)
    i < num_dense_layers:  f = W_down(silu(W_gate m) * W_up m)
    else: s = sigmoid(m W_r) over all `router_experts`, float32; the
        `num_experts_per_tok` largest of `s + b` are taken (`b` the selection
        bias, in the CHOICE only; no groups); weights `s[taken] /
        (sum(s[taken]) + 1e-20) * route_scale` (`route_norm`); f = the sum
        over the taken experts THAT ARE HELD HERE (`num_experts` from
        `expert_offset` on) plus the shared expert, unweighted. What the
        absent experts would add is left out, as the configuration's
        `deployment` says.
    x = x + N_post_mlp(f)

then the final RMSNorm and an untied head.

AT THE CELL'S SIZE it must fit beside the raw bf16 tree (5.67 GB of 16): one
ROW at a time (`jax.lax.map`; only the positions asked for leave a row),
queries in blocks of `QUERY_BLOCK` against the row's keys and values (a
block's scores are H x QUERY_BLOCK x S float32, 1.07 GB at a row of 8,192),
everything a token computes alone in blocks of `TOKEN_BLOCK` tokens, weights
upcast a matrix (an expert) at a time. Blocking changes no value. It reads
the program's weight TREE and none of its code.

THE ROUTING MARGIN is `openpangu_reference.py`'s, with the selection bias in
the choice: measured in the router's logits, of the part of the choice that
THIS CHIP computes. The choice is by `c = s + b`; two experts that swap
places at the edge of the top `k` change this chip's result only if one of
them is held here. So the margin is the smaller of (the lowest HELD expert
taken - the best one left) and (the last one taken - the best HELD one
left), in `c`, over the sigmoid's slope `s (1 - s)` at the edge (the larger
of the last taken's and the best left's). A row's margin is the smallest
over its expert layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 1024
TOKEN_BLOCK = 2048
SLIDING = "sliding_attention"


def _f(t):
    return t.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(w)


def _blocks(n, size):
    """The largest block up to `size` that divides `n`."""
    return max(c for c in range(1, min(n, size) + 1) if n % c == 0)


def _rope(x, cos, sin):
    """x (T, heads, d) at the positions of `cos`, `sin` (T, d / 2), pairs
    (i, i + d / 2)."""
    d = x.shape[-1]
    cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _positioned(x, cos, sin, sliding):
    """A layer's rotary: a window layer's queries and keys are rotated, a
    full layer's are NOT."""
    return _rope(x, cos, sin) if sliding else x


def _head_norm(x, w, eps):
    """The norm of every query and key head."""
    return _rms(x, w, eps)


def _post_norm(x, w, eps):
    """A sub-layer's OUTPUT normed before it joins the stream."""
    return _rms(x, w, eps)


def _gate(o, g):
    """The attention's output under its sigmoid gate."""
    return o * jax.nn.sigmoid(g)


def _embed_scale(cfg):
    return cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled", True) else 1.0


def _attention(h, p, norm_w, cfg, sliding):
    """One row: `Attn(RMSNorm(h))` for h (S, hidden)."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    s = h.shape[0]
    freq = cfg["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                       # (S, hd / 2)

    def cached(blk):
        """What a token leaves for later queries: (k | v), (T, Hkv, 2 hd)."""
        hb, cos, sin = blk
        x = _rms(hb, norm_w, eps)
        k = _head_norm((x @ _f(p["k_proj"]["kernel"])).reshape(-1, nkv, hd),
                       p["k_norm"]["weight"], eps)
        v = (x @ _f(p["v_proj"]["kernel"])).reshape(-1, nkv, hd)
        return jnp.concatenate([_positioned(k, cos, sin, sliding), v], -1)

    tb = _blocks(s, TOKEN_BLOCK)
    tokens = lambda t: t.reshape((s // tb, tb) + t.shape[1:])  # noqa: E731
    k, v = jnp.split(
        jax.lax.map(cached, (tokens(h), tokens(cos), tokens(sin))).reshape(
            s, nkv, 2 * hd), 2, axis=-1)
    size = _blocks(s, QUERY_BLOCK)
    scale = hd ** -0.5

    def block(first):
        """The queries at positions `first .. first + size - 1`."""
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, 0)  # noqa: E731
        t = first + jnp.arange(size)
        j = jnp.arange(s)
        keep = j[None, :] <= t[:, None]                         # (Q, S)
        if sliding:
            keep = keep & (t[:, None] - j[None, :] < window)
        x = _rms(cut(h), norm_w, eps)
        q = _head_norm((x @ _f(p["q_proj"]["kernel"])).reshape(size, nh, hd),
                       p["q_norm"]["weight"], eps)
        q = _positioned(q, cut(cos), cut(sin), sliding)
        q = q.reshape(size, nkv, nh // nkv, hd)
        logits = jnp.einsum("qgrd,sgd->grqs", q, k) * scale
        logits = jnp.where(keep[None, None], logits, -jnp.inf)
        o = jnp.einsum("grqs,sgd->qgrd", jax.nn.softmax(logits, -1), v)
        g = x @ _f(p["gate_proj"]["kernel"])
        return _gate(o.reshape(size, nh * hd), g) @ _f(p["o_proj"]["kernel"])

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def _router_scores(x, wg):
    """The router's scores, float32: a sigmoid of every expert's logit."""
    return jax.nn.sigmoid(x @ _f(wg))


def _weighed(scores, choice):
    """What the taken experts are WEIGHED by: the scores, without the
    selection bias that `choice` carries."""
    del choice
    return scores


def _experts(x, p, cfg):
    """(this chip's part of the layer's result for x (T, hidden), the
    routing margin at every position): the taken experts that are held, and
    the shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    scores = _router_scores(x, p["gate"]["wg"])                 # all of them
    choice = scores + _f(p["gate"]["bias"])      # the bias: in the choice only
    top, taken = jax.lax.top_k(choice, k + 1)    # the k taken, the best left
    idx = taken[..., :k]
    w = jnp.take_along_axis(_weighed(scores, choice), idx, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]

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
    low_held = jnp.min(jnp.where(is_taken & here, choice, jnp.inf), axis=-1)
    best_held = jnp.max(jnp.where(~is_taken & here, choice, -jnp.inf),
                        axis=-1)
    return out, jnp.minimum(low_held - top[..., k],
                            top[..., k - 1] - best_held) / slope


def _row(params, ids, cfg):
    """One row's (hidden states after the final norm (S, hidden), routing
    margin (S,), the smallest over the expert layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    h = _f(jnp.take(params["embed_tokens"], ids, axis=0)) * _embed_scale(cfg)
    tb = _blocks(ids.shape[0], TOKEN_BLOCK)
    for i in range(cfg["num_hidden_layers"]):
        weight = lambda name, i=i: layers[f"layer_{i}_{name}"]["weight"]  # noqa: E731
        a = _attention(h, layers[f"layer_{i}"], weight("norm"), cfg,
                       cfg["layer_types"][i] == SLIDING)
        p = layers[f"layer_{i}_mlp"]

        def rest(blk, p=p, weight=weight,
                 dense=i < cfg["num_dense_layers"]):
            """The layer from the attention's output on, a block of tokens."""
            hb, ab = blk
            hb = hb + _post_norm(ab, weight("post_attn_norm"), eps)
            x = _rms(hb, weight("mlp_norm"), eps)
            if dense:
                f, m = _swiglu(x, p["gate_proj"]["kernel"],
                               p["up_proj"]["kernel"],
                               p["down_proj"]["kernel"]), \
                    jnp.full(hb.shape[:1], jnp.inf, F32)
            else:
                f, m = _experts(x, p, cfg)
            return hb + _post_norm(f, weight("post_mlp_norm"), eps), m

        blocked = lambda t: t.reshape(-1, tb, t.shape[-1])  # noqa: E731
        h, m = jax.lax.map(rest, (blocked(h), blocked(a)))
        h, margin = h.reshape(-1, h.shape[-1]), jnp.minimum(margin,
                                                            m.reshape(-1))
    return _rms(h, params["norm_f"]["weight"], eps), margin


def _walk(params, ids, at, cfg):
    """(hidden states (B, P, hidden), the routing margin (B, P)) at
    positions `at` (B, P) of each row, a row at a time."""
    def row(xs):
        r, at = xs
        h, margin = _row(params, r, cfg)
        return h[at], margin[at]
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, (ids, jnp.asarray(at)))


def hidden_states(params, ids, cfg):
    b, s = ids.shape
    return _walk(params, ids, jnp.broadcast_to(jnp.arange(s), (b, s)), cfg)[0]


def _head(h, params):
    with jax.default_matmul_precision("highest"):
        return h @ _f(params["lm_head"])


def last_logits(params, ids, last, cfg):
    """(B, vocab) float32 logits at position `last[b]` of each row."""
    return last_logits_and_margin(params, ids, last, cfg)[0]


def last_logits_and_margin(params, ids, last, cfg):
    """(logits, routing margin) from one pass: the margin in the router's
    logits of the biased choice among the held experts (the module text says
    how), the smallest over the expert layers, at position `last`."""
    h, margin = _walk(params, ids, jnp.asarray(last)[:, None], cfg)
    return _head(h[:, 0], params), margin[:, 0]


def logits_at(params, ids, positions, cfg):
    """(B, len(positions), vocab) float32 logits at the given positions of
    every row, from one full pass."""
    return logits_and_margin_at(params, ids, positions, cfg)[0]


def logits_and_margin_at(params, ids, positions, cfg):
    """(`logits_at`, the routing margin there (B, len(positions))), from one
    full pass (the builder's decode-logits tool)."""
    at = jnp.broadcast_to(jnp.asarray(positions), (ids.shape[0],
                                                   len(positions)))
    h, margin = _walk(params, ids, at, cfg)
    return _head(h, params), margin


def mean_loss(params, ids, cfg):
    """Mean next-token cross-entropy over rows of `ids` (B, S), one row's
    logits at a time."""
    def row(r):
        with jax.default_matmul_precision("highest"):
            h = _row(params, r, cfg)[0][:-1]
        logp = jax.nn.log_softmax(_head(h, params), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, r[1:, None], axis=1))
    return jnp.mean(jax.lax.map(row, ids))
