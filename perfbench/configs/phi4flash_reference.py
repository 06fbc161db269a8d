"""Plain reference for the Phi-4-mini-flash-reasoning configuration: the
decoder-hybrid-decoder's forward pass and next-token loss in straightforward
`jax.numpy`, float32, matmuls at `highest` precision, no kernels, no cache, no
chunks, and the FULL pass: every position through every layer.

Follows the published architecture (SambaY, arXiv:2507.06607; Hugging Face
`modeling_phi4flash.py`; Mamba, Gu and Dao 2023; Differential Transformer, Ye
et al. 2024). `h` is `(B, S, hidden)`. Every layer i of L (32):
`h += Mixer_i(LN(h)); h += FFN_i(LN'(h))`, LN a LayerNorm with weight and
bias (eps `layer_norm_eps`); `FFN(x)`: `[g, u] = x W_fc1` (d -> 2 x
intermediate, no bias), `(u * silu(g)) W_fc2`. After the last layer a
LayerNorm, then `logits = h E^T` with the tied embedding, no bias. No
positional embedding of any kind. With `half = L / 2` the mixers are:

- **Mamba-1**, i even, i <= half. `[x, z] = u W_in` (d -> 2 x d_inner);
  `x = silu(conv_causal_depthwise(x) + b)` (kernel `mamba_d_conv`);
  `[r, B, C] = x W_x` (d_inner -> dt_rank + N + N);
  `dt = softplus(r W_dt + b_dt)` (per channel); `A = -exp(A_log)`;
  `H_t = exp(dt_t A) * H_{t-1} + (dt_t x_t) (x) B_t` (H: d_inner x N, every
  element its own decay); `y_t = H_t C_t + D * x_t`;
  `out = (y * silu(z)) W_out`. Layer `half` also publishes `m = y`: BEFORE
  the gate and WITH the `D` skip.
- **Differential attention**, i odd. `q, k, v = split(u W_qkv + b)` (H / Hkv
  / Hkv heads of `head_dim`; layers past `half + 1` have only the query's
  columns and read layer `half + 1`'s K and V). Heads pair up: heads 2p and
  2p + 1 are pair p's `(q1, q2)`; KV heads 2g and 2g + 1 are group g's
  `(k1, k2)` and `v_g = [v1 | v2]`; pair p reads group `p // (pairs / groups)`.
  `a1 = softmax(q1 k1^T / sqrt(head_dim) + mask) v_g`, `a2` likewise of
  `q2, k2`; `lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)`,
  `lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)`;
  `o_p = RMSNorm(a1 - lam a2) * w * (1 - lambda_init(i))` over the pair's
  2 x head_dim; `out = concat(o) W_o + b_o`. Mask: causal; for i < half also
  the window: a query at t sees keys `t - sliding_window + 1 .. t`.
- **Gated memory unit**, i even, i > half. `out = (silu(u W_in) * m) W_out`
  (no bias), `m` layer `half`'s at the same position.

Departures: none in the mathematics, as far as the source's `config.json` and
the paper settle it; what they do not is listed under `assumed` in the
configuration's file (the Mamba sizes, the biases, which half of `fc1` is the
gate, the window's edge, what `m` is). The layers are walked in Python over
the program's weight tree (`decoder/front` and `back` stacked over their
pairs of layers, `decoder/mid` the pair `half`, `half + 1`; `A_log` stored
`(N, d_inner)`), which is only how the weights are stored; weights are upcast
a layer at a time. It reads the program's weight TREE and none of its code.
No layer routes, so there is no routing margin.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(t):
    return t.astype(F32)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(p["weight"]) + _f(p["bias"])


def _ffn(x, p):
    y = x @ _f(p["fc1"]["kernel"])
    gate, up = jnp.split(y, 2, axis=-1)
    return (up * jax.nn.silu(gate)) @ _f(p["fc2"]["kernel"])


def _mamba(u, p, cfg):
    """(the mixer's output, y: the scan's output with the D skip, before the
    gate)."""
    n = cfg.get("mamba_d_state", 16)
    kw = cfg.get("mamba_d_conv", 4)
    rank = cfg.get("mamba_dt_rank") or math.ceil(cfg["hidden_size"] / 16)
    b, s, _ = u.shape
    x, z = jnp.split(u @ _f(p["in_proj"]["kernel"]), 2, axis=-1)
    padded = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    w = _f(p["conv_kernel"])                                  # (K, C)
    x = jax.nn.silu(sum(w[j] * padded[:, j:j + s] for j in range(kw))
                    + _f(p["conv_bias"]))
    r, bm, cm = jnp.split(x @ _f(p["x_proj"]["kernel"]), [rank, rank + n],
                          axis=-1)
    dt = jax.nn.softplus(r @ _f(p["dt_proj"]["kernel"])
                         + _f(p["dt_proj"]["bias"]))          # (B, S, C)
    a = -jnp.exp(_f(p["A_log"])).T                            # (C, N)

    def step(h, t):                                           # h (B, C, N)
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((b, x.shape[-1], n), F32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + _f(p["D"]) * x
    return (y * jax.nn.silu(z)) @ _f(p["out_proj"]["kernel"]), y


def _lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _diff_attention(u, p, cfg, i, kv=None, window=None):
    """(the mixer's output, this layer's (k, v) heads). `kv`: the K and V a
    cross layer reads, in which case `Wqkv` has the query's columns only."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    b, s, _ = u.shape
    qkv = u @ _f(p["Wqkv"]["kernel"]) + _f(p["Wqkv"]["bias"])
    q = qkv[..., :nh * hd].reshape(b, s, nh, hd)
    if kv is None:
        k, v = (t.reshape(b, s, nkv, hd)
                for t in jnp.split(qkv[..., nh * hd:], 2, axis=-1))
    else:
        k, v = kv
    pairs, groups = nh // 2, nkv // 2
    t = jnp.arange(s)
    keep = t[None, :] <= t[:, None]
    if window is not None:
        keep = keep & (t[None, :] > t[:, None] - window)
    lam = jnp.exp(jnp.sum(_f(p["lambda_q1"]) * _f(p["lambda_k1"]))) \
        - jnp.exp(jnp.sum(_f(p["lambda_q2"]) * _f(p["lambda_k2"]))) \
        + _lambda_init(i)

    def softmax_of(qh, kh):                                   # (B, S, hd) x 2
        logits = jnp.einsum("bqd,bkd->bqk", qh, kh) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(keep[None], logits, -jnp.inf), -1)

    def one(pair):            # a pair at a time: (B, S, S) logits, not 40 of them
        g = pair // (pairs // groups)
        head = lambda t, j: jnp.take(t, j, axis=2)            # noqa: E731
        v_g = jnp.concatenate([head(v, 2 * g), head(v, 2 * g + 1)], axis=-1)
        a1 = softmax_of(head(q, 2 * pair), head(k, 2 * g)) @ v_g
        a2 = softmax_of(head(q, 2 * pair + 1), head(k, 2 * g + 1)) @ v_g
        d = a1 - lam * a2
        d = d / jnp.sqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                         + cfg["layer_norm_eps"])
        return d * _f(p["subln_weight"]) * (1.0 - _lambda_init(i))

    outs = jax.lax.map(one, jnp.arange(pairs))                # (pairs, B, S, 2 hd)
    outs = jnp.moveaxis(outs, 0, 2).reshape(b, s, nh * hd)
    out = outs @ _f(p["out_proj"]["kernel"]) \
        + _f(p["out_proj"]["bias"])
    return out, (k, v)


def _memory_unit(u, p, m):
    return (jax.nn.silu(u @ _f(p["in_proj"]["kernel"])) * m) \
        @ _f(p["out_proj"]["kernel"])


def _layer(h, mixer, pair, name, eps):
    """`h += mixer(LN(h)); h += FFN(LN'(h))` with the parameters the tree
    keeps under `<name>`, `<name>_norm`, `<name>_mlp`, `<name>_mlp_norm` of
    `pair`. Returns (h, what else the mixer gave)."""
    out, extra = mixer(_layer_norm(h, pair[name + "_norm"], eps), pair[name])
    h = h + out
    h = h + _ffn(_layer_norm(h, pair[name + "_mlp_norm"], eps),
                 pair[name + "_mlp"])
    return h, extra


def hidden_states(params, ids, cfg):
    """(B, S, hidden) after the final LayerNorm."""
    eps = cfg["layer_norm_eps"]
    n_layers = cfg["num_hidden_layers"]
    half = n_layers // 2
    window = cfg["sliding_window"]
    at = lambda tree, j: jax.tree_util.tree_map(lambda t: t[j], tree)  # noqa: E731
    dec = params["decoder"]
    with jax.default_matmul_precision("highest"):
        h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
        m = kv = None
        for i in range(0, n_layers, 2):
            if i < half:
                pair = at(dec["front"], i // 2)
            elif i == half:
                pair = dec["mid"]
            else:
                pair = at(params["back"], (i - half - 2) // 2)
            if i <= half:
                h, y = _layer(h, lambda u, p: _mamba(u, p, cfg), pair,
                              "mamba", eps)
                if i == half:
                    m = y
                h, made = _layer(
                    h, lambda u, p: _diff_attention(
                        u, p, cfg, i + 1, window=window if i < half else None),
                    pair, "attn", eps)
                if i == half:
                    kv = made
            else:
                h, _ = _layer(h, lambda u, p: (_memory_unit(u, p, m), None),
                              pair, "gmu", eps)
                h, _ = _layer(h, lambda u, p: _diff_attention(
                    u, p, cfg, i + 1, kv=kv), pair, "attn", eps)
        return _layer_norm(h, params["final_layernorm"], eps)


def _head(h, params):
    with jax.default_matmul_precision("highest"):
        return h @ _f(params["embed_tokens"]).T


def last_logits(params, ids, last, cfg):
    """(B, vocab) float32 logits at position `last[b]` of each row."""
    h = hidden_states(params, ids, cfg)
    return _head(jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0],
                 params)


def logits_at(params, ids, positions, cfg):
    """(B, len(positions), vocab) float32 logits at the given positions of
    every row: the builder's comparison of a decode's logits
    (`tools/phi4flash_decode_logits.py`)."""
    h = hidden_states(params, ids, cfg)
    return _head(h[:, jnp.asarray(positions)], params)


def mean_loss(params, ids, cfg):
    """Mean next-token cross-entropy over rows of `ids` (B, S), one row's
    logits at a time."""
    def row(r):
        h = hidden_states(params, r[None], cfg)[0, :-1]
        logp = jax.nn.log_softmax(_head(h, params), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, r[1:, None], axis=1))
    return jnp.mean(jax.lax.map(row, ids))
