"""Plain reference for the Keye-sparse configurations (Keye-VL-2.0's language
model): the forward pass and next-token loss in straightforward `jax.numpy`,
float32, matmuls at `highest` precision, no kernels, no cache, no staged
tokens: the full index scores `I`, `jax.lax.top_k`, a mask, a softmax over
the masked row, every held expert looped plainly.

`h` is `(B, S, hidden)`. Every layer, with `u = RMSNorm(h)` (eps
`rms_norm_eps`), H = `num_attention_heads`, Hkv = `num_key_value_heads`, D =
`head_dim`, and the indexer's `sa_config` (Hi = `indexer_num_heads` heads of
Di = `indexer_head_dim` on one key head, `topk`):

- `q = u W_q` (H x D), `k = u W_k`, `v = u W_v` (Hkv x D); `q`, `k` <-
  RMSNorm over each head's D with one D-weight; rotary (theta `rope_theta`,
  pairs (i, i + D / 2)) over all D, positions 0 .. S - 1;
- `qI = rope(u W_qI)` (Hi x Di), `kI = rope(LayerNorm(u W_kI))` (Di; eps
  1e-6, weight and bias), `w = u W_w` (Hi), rotary over all Di at the same
  theta; `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` for s <= t;
  `S_t` = the `min(topk, t + 1)` positions s <= t of largest `I[t, s]`, as
  `jax.lax.top_k` orders them (ties to the lower position);
- `o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // (H /
  Hkv)] D^-0.5) v[s, .]`; `h += o W_o`;
- `y = RMSNorm(h)`; `p = softmax(y W_r)` over all `router_experts`; the
  `num_experts_per_tok` largest are taken, weights `p / sum of the taken`
  (`norm_topk_prob`); the result is the sum over the taken experts THAT ARE
  HELD HERE (`num_experts` from `expert_offset` on) of `W_d(silu(y W_g) *
  (y W_u))`; `h += that`. What the absent experts would add is left out, as
  the configuration's `deployment` says.

Then the final RMSNorm and an untied head. The attention runs in blocks of
`QUERY_BLOCK` queries (`jax.lax.map`): a block's scores over all S keys for
all heads are float32 of `B x H x QUERY_BLOCK x S`, 1.07 GB at two rows of
32,768, beside the bf16 tree; blocking changes no value. Weights are upcast
a layer (an expert) at a time. It reads the program's weight TREE and none
of its code.

THE ROUTING MARGIN is measured where bf16 rounding of the hidden state acts,
in the router's logits, and of the part of the choice that THIS CHIP
computes (`ling_linear_reference.py` argues it): two experts that swap
places at the edge of the top `k` change this chip's result only if one of
them is held here (the taken weights' sum moves by the two scores'
difference, continuously). For softmax scores the README's relative margin
`(p_last - p_next) / p_last` is `1 - exp(z_next - z_last)` in the logits
`z`. So a position's margin is the smaller of (the lowest HELD expert taken
against the best one left) and (the last one taken against the best HELD one
left), each as `1 - exp(z_lower - z_upper)`; a row's is the smallest over
its layers. The SELECTION's own boundary (the 2,048th against the 2,049th
index score) is not folded in: that gap is always tiny, a swap exchanges one
of 2,048 keys of near-equal index score, and every row would be redrawn for
ever.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 128
INDEX_NORM_EPS = 1e-6


def _f(t):
    return t.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(w)


def _rope(x, theta):
    """x (B, S, H, D), positions 0 .. S - 1, pairs (i, i + D / 2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head_norm(x, w, eps):
    """RMSNorm over each head's width (q and k)."""
    return _rms(x, w, eps)


def _index_scores(q_i, k_i, w):
    """`I`: q_i (B, Q, Hi, Di), k_i (B, S, Di), w (B, Q, Hi) -> (B, Q, S)."""
    return jnp.einsum("bqh,bqhs->bqs", w,
                      jax.nn.relu(jnp.einsum("bqhd,bsd->bqhs", q_i, k_i)))


def _candidates(t, s):
    """(Q, S) bool: the positions a query at position t[q] chooses among."""
    return jnp.arange(s)[None, :] <= t[:, None]


def _attention(x, p, cfg):
    nh, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sa = cfg["sa_config"]
    hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    heads = lambda name, n, w: (x @ _f(p[name]["kernel"])).reshape(b, s, n, w)  # noqa: E731
    q = _rope(_head_norm(heads("q_proj", nh, d), p["q_norm"]["weight"], eps),
              theta)
    k = _rope(_head_norm(heads("k_proj", hkv, d), p["k_norm"]["weight"], eps),
              theta)
    v = heads("v_proj", hkv, d)
    q_i = _rope(heads("index_q_proj", hi, di), theta)
    k_i = x @ _f(p["index_k_proj"]["kernel"])
    mean = jnp.mean(k_i, axis=-1, keepdims=True)
    var = jnp.mean((k_i - mean) ** 2, axis=-1, keepdims=True)
    k_i = (k_i - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
        * _f(p["index_k_norm"]["scale"]) + _f(p["index_k_norm"]["bias"])
    k_i = _rope(k_i[:, :, None], theta)[:, :, 0]                # (B, S, Di)
    w = x @ _f(p["index_w_proj"]["kernel"])                     # (B, S, Hi)

    size = max(c for c in range(1, min(s, QUERY_BLOCK) + 1) if s % c == 0)
    kk = min(topk, s)
    rank = jnp.arange(kk)

    def block(first):
        """The queries at positions `first .. first + size - 1`."""
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, 1)  # noqa: E731
        t = first + jnp.arange(size)
        causal = jnp.arange(s)[None, :] <= t[:, None]           # (Q, S)
        scores = _index_scores(cut(q_i), k_i, cut(w))
        _, at = jax.lax.top_k(
            jnp.where(_candidates(t, s), scores, -jnp.inf), kk)
        taken = rank[None, None, :] < jnp.minimum(t + 1, topk)[None, :, None]
        # the taken positions as a mask (B, Q, S)
        mask = jnp.zeros((b, size, s), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(size)[None, :, None],
            at].set(jnp.broadcast_to(taken, at.shape))
        logits = jnp.einsum(
            "bqgrd,bsgd->bgrqs",
            cut(q).reshape(b, size, hkv, nh // hkv, d), k) * d ** -0.5
        logits = jnp.where((mask & causal)[:, None, None], logits, -jnp.inf)
        out = jnp.einsum("bgrqs,bsgd->bqgrd", jax.nn.softmax(logits, -1), v)
        return out.reshape(b, size, nh * d)

    out = jax.lax.map(block, jnp.arange(0, s, size))            # (N, B, Q, .)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh * d)
    return out @ _f(p["o_proj"]["kernel"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def _experts(x, p, cfg):
    """(this chip's part of the layer's result, the routing margin at every
    position)."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    z = x @ _f(p["gate"]["wg"])                                 # the logits
    scores = jax.nn.softmax(z, axis=-1)
    top, taken = jax.lax.top_k(z, k + 1)         # the k taken, the best left
    idx = taken[..., :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(out, e):
        gate, up, down, local = e
        weight = jnp.sum(jnp.where(idx == local + offset, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(x, gate, up, down), None

    ex = p["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    ids = jnp.arange(z.shape[-1])
    here = (ids >= offset) & (ids < offset + held)
    is_taken = jnp.any(idx[..., None] == ids, axis=-2)          # (..., E)
    low_held = jnp.min(jnp.where(is_taken & here, z, jnp.inf), axis=-1)
    best_held = jnp.max(jnp.where(~is_taken & here, z, -jnp.inf), axis=-1)
    gap = jnp.minimum(low_held - top[..., k], top[..., k - 1] - best_held)
    return out, 1.0 - jnp.exp(-gap)


def _walk(params, ids, cfg):
    """(hidden states after the final norm (B, S, hidden), the routing
    margin (B, S), the smallest over the layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
        for i in range(cfg["num_hidden_layers"]):
            x = _rms(h, layers[f"layer_{i}_norm"]["weight"], eps)
            h = h + _attention(x, layers[f"layer_{i}"], cfg)
            x = _rms(h, layers[f"layer_{i}_mlp_norm"]["weight"], eps)
            out, m = _experts(x, layers[f"layer_{i}_mlp"], cfg)
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
    """(logits, routing margin) from one pass: the margin of the held part
    of the experts' choice in the router's logits (the module text says
    how), the smallest over the layers, at position `last`."""
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
