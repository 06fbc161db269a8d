"""Plain reference for the openPangu configurations (openPangu-Ultra-MoE):
the forward pass and next-token loss in straightforward `jax.numpy`, float32,
matmuls at `highest` precision, no kernels, no cache, no staged tokens, no
absorbed projections: every head's key and value FORMED, a causal mask, a
softmax over the masked row, every held expert looped plainly.

`h` is `(B, S, hidden)`. Every layer, each `N` an RMSNorm with its own
weight (eps `rms_norm_eps`), the SANDWICH of `sandwich_norm: true`:

    a = Attn(N_in(h));          h1 = h  + N_post_attn(a)
    f = FFN(N_pre_mlp(h1));     h2 = h1 + N_post_mlp(f)

then the final RMSNorm and an untied head. With `u = N_in(h)`, H =
`num_attention_heads`, dn = `qk_nope_head_dim`, dr = `qk_rope_head_dim`, dv
= `v_head_dim`:

- `cq = RMSNorm(u W_qa)` (`q_lora_rank`); `q = cq W_qb` -> H x (dn + dr),
  rotary on the last dr;
- `[ckv | kr] = u W_kva` (`kv_lora_rank` | dr); `c = RMSNorm(ckv)`; `kr =
  rope(kr)`, shared by all heads; `[k_nope | v] = c W_kvb` (a head's first
  dn columns its key);
- `o[t, h] = sum_{s <= t} softmax_{s <= t}(q[t, h] . [k_nope[s, h] | kr[s]]
  (dn + dr)^-0.5) v[s, h]`: EVERY position up to the query's own; `a = o
  W_o`; no bias anywhere;
- ROTARY, plain: pairs (i, i + dr / 2), pair i's frequency `rope_theta^(-2i
  / dr)`, no scaling of the frequencies, the tables or the softmax;
- FFN: `W_down(silu(W_gate y) * W_up y)`, dense at `intermediate_size` for
  the first `first_k_dense_replace` layers; after them experts: `s =
  sigmoid(y W_r)` over all `router_experts`, float32; the
  `num_experts_per_tok` largest of ALL of them at once are taken (no groups,
  no selection bias); weights `s[taken] / (sum(s[taken]) + 1e-20) *
  routed_scaling_factor`; the result is the sum over the taken experts THAT
  ARE HELD HERE (`n_routed_experts` from `expert_offset` on) plus the shared
  expert, unweighted. What the absent experts would add is left out, as the
  configuration's `deployment` says.

AT THE CELL'S SIZE it must fit beside the raw bf16 tree (9.84 GB of 16): one
ROW at a time (`jax.lax.map`; only the positions asked for leave a row),
queries in blocks of `QUERY_BLOCK` against the row's `c` and `kr`, the heads
of a block in groups of `HEAD_BLOCK` whose keys and values are formed for
the group (a group's scores are `HEAD_BLOCK x QUERY_BLOCK x S` float32, 403
MB at a row of 24,576), the FFNs and what a token caches in blocks of
`TOKEN_BLOCK` tokens, weights upcast a matrix (an expert) at a time.
Blocking changes no value. It reads the program's weight TREE and none of
its code.

THE ROUTING MARGIN is measured where bf16 rounding of the hidden state acts,
in the router's logits, and of the part of the choice that THIS CHIP
computes. Two experts that swap places at the edge of the top `k` change
this chip's result only if one of them is held here: the absent ones are
left out whichever is taken, and the weights' sum moves by the two scores'
difference, continuously. So the margin is the smaller of (the lowest HELD
expert taken - the best one left) and (the last one taken - the best HELD
one left), over the sigmoid's slope `s (1 - s)` at the edge (the larger of
the last taken's and the best left's); there is no choice of groups to fold
in. A row's margin is the smallest over its expert layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 2048
HEAD_BLOCK = 2
TOKEN_BLOCK = 2048


def _f(t):
    return t.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(w)


def _blocks(n, size):
    """The largest block up to `size` that divides `n`."""
    return max(c for c in range(1, min(n, size) + 1) if n % c == 0)


def _rope(x, cos, sin):
    """x (T, heads, dr) at the positions of `cos`, `sin` (T, dr / 2), pairs
    (i, i + dr / 2)."""
    dr = x.shape[-1]
    cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _key_rope(x, cos, sin):
    """The rotary of the one rope key all heads share."""
    return _rope(x, cos, sin)


def _post_norm(x, w, eps):
    """A sub-layer's OUTPUT normed before it joins the stream."""
    return _rms(x, w, eps)


def _attention(h, p, norm_w, cfg):
    """One row: `Attn(RMSNorm(h))` for h (S, hidden)."""
    nh, dn, dr, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                            cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    s = h.shape[0]
    freq = cfg["rope_theta"] ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                       # (S, dr / 2)

    def cached(blk):
        """What a token leaves for later queries: (c | kr)."""
        hb, cos, sin = blk
        x = _rms(hb, norm_w, eps)
        ckv, kr = jnp.split(x @ _f(p["kv_a_proj"]["kernel"]), [rank], axis=-1)
        return jnp.concatenate(
            [_rms(ckv, p["kv_a_norm"]["weight"], eps),
             _key_rope(kr[:, None], cos, sin)[:, 0]], axis=-1)

    tb = _blocks(s, TOKEN_BLOCK)
    tokens = lambda t: t.reshape((s // tb, tb) + t.shape[1:])  # noqa: E731
    c, kr = jnp.split(
        jax.lax.map(cached, (tokens(h), tokens(cos), tokens(sin))).reshape(
            s, rank + dr), [rank], axis=-1)
    size = _blocks(s, QUERY_BLOCK)
    hb = _blocks(nh, HEAD_BLOCK)
    scale = (dn + dr) ** -0.5
    grouped = lambda t, g: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[0], -1, g, t.shape[-1]), 1, 0)
    w_kvb = grouped(_f(p["kv_b_proj"]).reshape(rank, nh, dn + dv), hb)
    w_qb = grouped(_f(p["q_b_proj"]["kernel"]).reshape(-1, nh, dn + dr), hb)

    def block(first):
        """The queries at positions `first .. first + size - 1`."""
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, 0)  # noqa: E731
        t = first + jnp.arange(size)
        causal = jnp.arange(s)[None, :] <= t[:, None]           # (Q, S)
        x = _rms(cut(h), norm_w, eps)
        cq = _rms(x @ _f(p["q_a_proj"]["kernel"]), p["q_a_norm"]["weight"],
                  eps)
        cos_b, sin_b = cut(cos), cut(sin)

        def heads(ws):
            w_q, w_kv = ws                  # (rq, hb, dn + dr), (rank, hb, .)
            q = jnp.einsum("qr,rhd->qhd", cq, w_q)
            kv = jnp.einsum("sr,rhd->shd", c, w_kv)             # (S, hb, .)
            logits = (jnp.einsum("qhd,shd->hqs", q[..., :dn], kv[..., :dn])
                      + jnp.einsum("qhd,sd->hqs",
                                   _rope(q[..., dn:], cos_b, sin_b), kr)) \
                * scale
            logits = jnp.where(causal[None], logits, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(logits, -1),
                              kv[..., dn:])

        out = jax.lax.map(heads, (w_qb, w_kvb))                 # (G, Q, hb, dv)
        return jnp.moveaxis(out, 0, 1).reshape(size, nh * dv) \
            @ _f(p["o_proj"]["kernel"])

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def _experts(x, p, cfg):
    """(this chip's part of the layer's result for x (T, hidden), the
    routing margin at every position): the taken experts that are held, and
    the shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    scores = jax.nn.sigmoid(x @ _f(p["gate"]["wg"]))            # all of them
    top, taken = jax.lax.top_k(scores, k + 1)    # the k taken, the best left
    idx = taken[..., :k]
    w = top[..., :k]
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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
    low_held = jnp.min(jnp.where(is_taken & here, scores, jnp.inf), axis=-1)
    best_held = jnp.max(jnp.where(~is_taken & here, scores, -jnp.inf),
                        axis=-1)
    return out, jnp.minimum(low_held - top[..., k],
                            top[..., k - 1] - best_held) / slope


def _row(params, ids, cfg):
    """One row's (hidden states after the final norm (S, hidden), routing
    margin (S,), the smallest over the expert layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
    tb = _blocks(ids.shape[0], TOKEN_BLOCK)
    for i in range(cfg["num_hidden_layers"]):
        weight = lambda name, i=i: layers[f"layer_{i}_{name}"]["weight"]  # noqa: E731
        a = _attention(h, layers[f"layer_{i}"], weight("norm"), cfg)
        p = layers[f"layer_{i}_mlp"]

        def rest(blk, p=p, weight=weight,
                 dense=i < cfg["first_k_dense_replace"]):
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
    logits of the ungrouped choice among the held experts (the module text
    says how), the smallest over the expert layers, at position `last`."""
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
