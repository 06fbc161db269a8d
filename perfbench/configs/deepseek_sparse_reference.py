"""Plain reference for the DeepSeek-sparse configurations (DeepSeek-V3.2's
language model): the forward pass and next-token loss in straightforward
`jax.numpy`, float32, matmuls at `highest` precision, no kernels, no cache,
no staged tokens, no absorbed projections: the full causal index scores `I`,
`jax.lax.top_k`, a mask, every head's key and value FORMED, a softmax over
the masked row, every held expert looped plainly, YaRN written out.

`h` is `(B, S, hidden)`. Every layer: `h += Attn(RMSNorm(h)); h +=
FFN(RMSNorm(h))` (eps `rms_norm_eps`); then the final RMSNorm and an untied
head. With `u = RMSNorm(h)`, H = `num_attention_heads`, dn =
`qk_nope_head_dim`, dr = `qk_rope_head_dim`, dv = `v_head_dim`:

- `cq = RMSNorm(u W_qa)` (`q_lora_rank`); `q = cq W_qb` -> H x (dn + dr),
  rotary on the last dr;
- `[ckv | kr] = u W_kva` (`kv_lora_rank` | dr); `c = RMSNorm(ckv)`; `kr =
  rope(kr)`, shared by all heads; `[k_nope | v] = c W_kvb` (a head's first
  dn columns its key);
- the indexer (Hi = `index_n_heads` heads of Di = `index_head_dim` on ONE
  key head, `index_topk`): `qI = cq W_Iq` (Hi x Di), `kI = LayerNorm(u
  W_Ik)` (Di; eps 1e-6, weight and bias), rotary on the FIRST dr of both,
  `w = u W_Iw` (Hi); `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` for s
  <= t; `S_t` = the `min(index_topk, t + 1)` positions s <= t of largest
  `I[t, s]`, as `jax.lax.top_k` orders them (ties to the lower position);
- `o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . [k_nope[s, h] |
  kr[s]] (dn + dr)^-0.5 m^2) v[s, h]`; `h += o W_o`;
- ROTARY, YaRN (`rope_scaling`): pairs (i, i + dr / 2); pair i's frequency
  `f_i = rope_theta^(-2i / dr)`; with `n(r) = dr ln(original_max / (2 pi
  r)) / (2 ln rope_theta)` the (fractional) pair that turns `r` times over
  the original context, `low = floor(n(beta_fast))`, `high =
  ceil(n(beta_slow))` (inside 0 .. dr - 1), `ramp_i = clip((i - low) /
  (high - low), 0, 1)`: the frequency served is `f_i (1 - ramp_i) + (f_i /
  factor) ramp_i`; `m = 0.1 mscale_all_dim ln(factor) + 1`, and the tables
  themselves are times `(0.1 mscale ln(factor) + 1) / m` (1 as published);
- FFN: `W_down(silu(W_gate y) * W_up y)`, dense at `intermediate_size` for
  the first `first_k_dense_replace` layers; after them experts: `s =
  sigmoid(y W_r)` over all `router_experts`; the choice on `s + bias`: the
  experts lie in `n_group` groups, a group's score is the sum of its best
  two, the best `topk_group` groups stay, the best `num_experts_per_tok`
  experts inside them are taken; weights `s[taken] / sum(s[taken]) *
  routed_scaling_factor`; the result is the sum over the taken experts THAT
  ARE HELD HERE (`n_routed_experts` from `expert_offset` on) plus the shared
  expert. What the absent experts would add is left out, as the
  configuration's `deployment` says.

AT THE CELL'S SIZE it must fit beside the raw bf16 tree (9.27 GB of 16): one
ROW at a time (`jax.lax.map`; only the positions asked for leave a row),
queries in blocks of `QUERY_BLOCK` against the row's `c`, `kr`, `kI`, the
heads of a block (the indexer's too) in groups of `HEAD_BLOCK` whose keys
and values are formed for the group (a block's `I` is `QUERY_BLOCK x S`
float32, 268 MB at a row of 32,768; a group's scores `HEAD_BLOCK` times
that), the FFNs and what a token caches in blocks of `TOKEN_BLOCK` tokens,
weights upcast a matrix (an expert) at a time.
Blocking changes no value. It reads the program's weight TREE and none of
its code.

THE ROUTING MARGIN is `ling_linear_reference.py`'s, which argues it: in the
router's logits, of the part of the choice that THIS CHIP computes (the held
experts' edge inside the groups that stay, and the choice of groups), the
smallest over the expert layers. The held range may cut a group: the groups
are scored over all `router_experts`, whoever holds them. The SELECTION's
own boundary is not folded in (`keye_sparse_reference.py` says why).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 2048
HEAD_BLOCK = 2
TOKEN_BLOCK = 2048
INDEX_NORM_EPS = 1e-6


def _f(t):
    return t.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(w)


def _blocks(n, size):
    """The largest block up to `size` that divides `n`."""
    return max(c for c in range(1, min(n, size) + 1) if n % c == 0)


def yarn(cfg):
    """(the rotary pairs' frequencies (dr / 2,), the factor on the tables,
    the softmax's temperature m): YaRN as the module text writes it out; the
    plain frequencies and ones without `rope_scaling`."""
    dr, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    freq = theta ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)
    rs = cfg.get("rope_scaling")
    if not rs or rs.get("type", rs.get("rope_type")) != "yarn":
        return freq, 1.0, 1.0
    def pair(turns):
        return dr * math.log(rs["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair(rs["beta_fast"])), 0)
    high = min(math.ceil(pair(rs["beta_slow"])), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    tables = (0.1 * rs["mscale"] * math.log(rs["factor"]) + 1.0) / m
    return freq * (1.0 - ramp) + freq / rs["factor"] * ramp, tables, m


def _rope(x, cos, sin):
    """x (T, heads, dr) at the positions of `cos`, `sin` (T, dr / 2), pairs
    (i, i + dr / 2)."""
    dr = x.shape[-1]
    cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _index_rope(x, cos, sin):
    """The indexer's rotary (of its queries' and its key's first dr)."""
    return _rope(x, cos, sin)


def _index_input(cq, x):
    """What the indexer's queries are projected from: the query's own
    compression `cq`, not the layer's input `x`."""
    del x
    return cq


def _candidates(t, s):
    """(Q, S) bool: the positions a query at position t[q] chooses among."""
    return jnp.arange(s)[None, :] <= t[:, None]


def _attention(h, p, norm_w, cfg):
    """One row: `Attn(RMSNorm(h))` for h (S, hidden)."""
    nh, dn, dr, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                            cfg["kv_lora_rank"])
    hi, di, topk = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["index_topk"])
    eps = cfg["rms_norm_eps"]
    freq, tables, m = yarn(cfg)
    s = h.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang) * tables, jnp.sin(ang) * tables     # (S, dr / 2)

    def cached(blk):
        """What a token leaves for later queries: (c | kr | kI)."""
        hb, cos, sin = blk
        x = _rms(hb, norm_w, eps)
        ckv, kr = jnp.split(x @ _f(p["kv_a_proj"]["kernel"]), [rank], axis=-1)
        k_i = x @ _f(p["index_k_proj"]["kernel"])
        mean = jnp.mean(k_i, axis=-1, keepdims=True)
        var = jnp.mean((k_i - mean) ** 2, axis=-1, keepdims=True)
        k_i = (k_i - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
            * _f(p["index_k_norm"]["scale"]) + _f(p["index_k_norm"]["bias"])
        return jnp.concatenate(
            [_rms(ckv, p["kv_a_norm"]["weight"], eps),
             _rope(kr[:, None], cos, sin)[:, 0],
             _index_rope(k_i[:, None, :dr], cos, sin)[:, 0], k_i[:, dr:]],
            axis=-1)

    tb = _blocks(s, TOKEN_BLOCK)
    tokens = lambda t: t.reshape((s // tb, tb) + t.shape[1:])  # noqa: E731
    c, kr, k_i = jnp.split(
        jax.lax.map(cached, (tokens(h), tokens(cos), tokens(sin))).reshape(
            s, rank + dr + di), [rank, rank + dr], axis=-1)
    size = _blocks(s, QUERY_BLOCK)
    hb, ib = _blocks(nh, HEAD_BLOCK), _blocks(hi, HEAD_BLOCK)
    kk = min(topk, s)
    scale = (dn + dr) ** -0.5 * m * m
    grouped = lambda t, g: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[0], -1, g, t.shape[-1]), 1, 0)
    w_kvb = grouped(_f(p["kv_b_proj"]).reshape(rank, nh, dn + dv), hb)
    w_qb = grouped(_f(p["q_b_proj"]["kernel"]).reshape(-1, nh, dn + dr), hb)
    w_iq = grouped(_f(p["index_q_proj"]["kernel"]).reshape(-1, hi, di), ib)

    def block(first):
        """The queries at positions `first .. first + size - 1`."""
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, 0)  # noqa: E731
        t = first + jnp.arange(size)
        causal = jnp.arange(s)[None, :] <= t[:, None]           # (Q, S)
        live = _candidates(t, s) & causal
        x = _rms(cut(h), norm_w, eps)
        cq = _rms(x @ _f(p["q_a_proj"]["kernel"]), p["q_a_norm"]["weight"],
                  eps)
        cos_b, sin_b = cut(cos), cut(sin)
        w_i = jnp.moveaxis((x @ _f(p["index_w_proj"]["kernel"])).reshape(
            size, hi // ib, ib), 1, 0)

        def index_heads(scores, ws):
            w_q, w = ws                     # (rq, ib, Di), (Q, ib)
            q = jnp.einsum("qr,rhd->qhd", _index_input(cq, x), w_q)
            q = jnp.concatenate([_index_rope(q[..., :dr], cos_b, sin_b),
                                 q[..., dr:]], axis=-1)
            return scores + jnp.einsum("qh,qhs->qs", w, jax.nn.relu(
                jnp.einsum("qhd,sd->qhs", q, k_i))), None

        scores, _ = jax.lax.scan(index_heads, jnp.zeros((size, s), F32),
                                 (w_iq, w_i))
        _, at = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), kk)
        taken = jnp.arange(kk)[None, :] < jnp.minimum(t + 1, topk)[:, None]
        mask = jnp.zeros((size, s), bool).at[
            jnp.arange(size)[:, None], at].set(taken) & live

        def heads(ws):
            w_q, w_kv = ws                  # (rq, hb, dn + dr), (rank, hb, .)
            q = jnp.einsum("qr,rhd->qhd", cq, w_q)
            kv = jnp.einsum("sr,rhd->shd", c, w_kv)             # (S, hb, .)
            logits = (jnp.einsum("qhd,shd->hqs", q[..., :dn], kv[..., :dn])
                      + jnp.einsum("qhd,sd->hqs",
                                   _rope(q[..., dn:], cos_b, sin_b), kr)) \
                * scale
            logits = jnp.where(mask[None], logits, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(logits, -1),
                              kv[..., dn:])

        out = jax.lax.map(heads, (w_qb, w_kvb))                 # (G, Q, hb, dv)
        return jnp.moveaxis(out, 0, 1).reshape(size, nh * dv) \
            @ _f(p["o_proj"]["kernel"])

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, -1)


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
    """(this chip's part of the layer's result for x (T, hidden), the
    routing margin at every position): the taken experts that are held, and
    the shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
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


def _row(params, ids, cfg):
    """One row's (hidden states after the final norm (S, hidden), routing
    margin (S,), the smallest over the expert layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
    tb = _blocks(ids.shape[0], TOKEN_BLOCK)
    for i in range(cfg["num_hidden_layers"]):
        h = h + _attention(h, layers[f"layer_{i}"],
                           layers[f"layer_{i}_norm"]["weight"], cfg)
        p, norm_w = layers[f"layer_{i}_mlp"], \
            layers[f"layer_{i}_mlp_norm"]["weight"]

        def ffn(hb, p=p, norm_w=norm_w, dense=i < cfg["first_k_dense_replace"]):
            x = _rms(hb, norm_w, eps)
            if dense:
                return hb + _swiglu(x, p["gate_proj"]["kernel"],
                                    p["up_proj"]["kernel"],
                                    p["down_proj"]["kernel"]), \
                    jnp.full(hb.shape[:1], jnp.inf, F32)
            out, m = _experts(x, p, cfg)
            return hb + out, m

        h, m = jax.lax.map(ffn, h.reshape(-1, tb, h.shape[-1]))
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
    logits over the choice of groups and of the held experts (the module
    text says how), the smallest over the expert layers, at position
    `last`."""
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
