"""Plain reference for the qwen3_next configurations (Qwen3-Next-80B-A3B):
the forward pass and next-token loss in straightforward `jax.numpy`, float32
from the RAW bf16 tree, matmuls at `highest` precision, no kernels, no cache,
no batching: the delta rule as the RECURRENCE over positions (`lax.scan`,
never the chunked form), a causal mask over the row's own positions, a
softmax over the masked row, every held expert looped plainly.

`N(x, w) = x / rms(x) * (1 + w)` (eps `rms_norm_eps`): every hidden-size norm
and the query / key head norms. Layer i, of PUBLISHED index p
(`published_layers[i]`, else i), is FULL attention where `(p + 1) %
full_attention_interval == 0` and Gated DeltaNet (GDN) elsewhere:

    a = N_in(x)
    GDN (`linear_num_key_heads` key heads, `linear_num_value_heads` value
    heads, widths `linear_key_head_dim` / `linear_value_head_dim`):
      [q | k | v | z] = a W_qkvz,  [b | al] = a W_ba                (no bias)
      [q | k | v] = silu(conv([q | k | v]))    causal depthwise, kernel
          `linear_conv_kernel_dim`, zeros before the row's first position
      beta = sigmoid(b);  g = -exp(A_log) * softplus(al + dt_bias)  a value head
      q = l2(q) * d_k^-0.5,  k = l2(k)   a key head (eps 1e-6 under the root);
          key head j serves value heads (Hv / Hk) j .. (Hv / Hk) (j + 1) - 1
      a value head's S (d_k x d_v), from zeros, a position at a time:
          S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
          o_t = S^T q_t
      y = (w_n * o / rms_head(o) * silu(z)) W_out      a PLAIN norm weight
    FULL (`num_attention_heads` on `num_key_value_heads` of `head_dim`):
      [q | gate] = a W_q (a head: head_dim + head_dim), k = a W_k, v = a W_v
      q = N_q(q), k = N_k(k) over a head; the FIRST `head_dim *
          partial_rotary_factor` values of a head rotated at their position,
          pairs (i, i + half of that), pair i's frequency
          `rope_theta^(-2i / rotary width)`; the rest as they are
      o[t] = sum_{j <= t} softmax_j(q[t] . k[j] head_dim^-0.5) v[j]; query
          head h reads KV head h // (H / Hkv);  y = (o * sigmoid(gate)) W_o
    x = x + y;  m = N_post(x)
    p = softmax(m W_r) over all `router_experts`, float32; the
        `num_experts_per_tok` largest are taken, weights p / their sum
        (`norm_topk_prob`); f = the sum over the taken experts THAT ARE HELD
        HERE (`num_experts` from `expert_offset` on) + sigmoid(m . w_sg) *
        Shared(m), all SwiGLU. What the absent experts would add is left
        out, as the configuration's `deployment` says.
    x = x + f

then `N_f` and an untied head. DEPARTURES from the published description:
the columns of `W_qkvz` / `W_ba` lie `[q | k | v | z]` / `[b | al]`, whole
parts side by side (the source interleaves a key head's parts: a
permutation of seeded columns); the norm weights `w` are seeded normal(0.02)
where the source starts them at 0; the multi-token-prediction layer is out.
The configuration file's `assumed` says the same.

AT THE CELL'S SIZE it must fit beside the raw bf16 tree (6.95 GB of 16): one
ROW at a time (`jax.lax.map`; only the positions asked for leave a row),
queries in blocks of `QUERY_BLOCK` against the row's keys and values (a
block's scores are H x QUERY_BLOCK x S float32, 1.07 GB at a row of 32,768),
everything a token computes alone in blocks of `TOKEN_BLOCK` tokens, weights
upcast a matrix (an expert) at a time. Blocking changes no value. It reads
the program's weight TREE and none of its code.

THE ROUTING MARGIN is `afmoe_reference.py`'s for a softmax router with no
selection bias: measured in the router's LOGITS (a softmax keeps their
order), of the part of the choice that THIS CHIP computes. Two experts that
swap places at the edge of the top `k` change this chip's result only if one
of them is held here, so the margin is the smaller of (the lowest HELD
expert taken - the best one left) and (the last one taken - the best HELD
one left). A row's margin is the smallest over its layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048
L2_EPS = 1e-6


def _f(t):
    return t.astype(F32)


def _norm(x, w, eps):
    """`x / rms(x) * (1 + w)`."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + _f(w))


def _head_norm(x, w, eps):
    """The norm of every query and key head: `1 + w` too."""
    return _norm(x, w, eps)


def _blocks(n, size):
    """The largest block up to `size` that divides `n`."""
    return max(c for c in range(1, min(n, size) + 1) if n % c == 0)


def _kinds(cfg):
    pub = cfg.get("published_layers") or range(cfg["num_hidden_layers"])
    return ["A" if (p + 1) % cfg["full_attention_interval"] == 0 else "G"
            for p in pub]


def _rotary_width(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def _rope(x, cos, sin, rd):
    """x (T, heads, d): its first `rd` values rotated at the positions of
    `cos`, `sin` (T, rd / 2), pairs (i, i + rd / 2); the rest as they are."""
    cos, sin = cos[:, None], sin[:, None]
    x1, x2, rest = x[..., : rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _z_gate(z):
    """What the GDN output is gated by."""
    return jax.nn.silu(z)


def _decayed(s, u_of, g):
    """The state decayed, THEN corrected: `u_of(decayed state)` is the delta
    rule's correction."""
    s = s * jnp.exp(g)[:, None, None]
    return s + u_of(s)


def _gdn(h, p, norm_w, cfg, state_dtype=F32):
    """One row: `GDN(N_in(h))` for h (S, hidden), the recurrence a position
    at a time from a zero state (the state and the convolution's last
    inputs carried from one block of tokens to the next)."""
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kw, eps = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"]
    kd, vd = nk * dk, nv * dv
    s = h.shape[0]
    tb = _blocks(s, TOKEN_BLOCK)
    w = _f(p["conv_kernel"])                                    # (K, C)
    a = jnp.exp(_f(p["A_log"]))

    def step(state, t):
        q, k, v, g, beta = t

        def u_of(dec):
            u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", dec, k))
            return k[:, :, None] * u[:, None, :]
        state = _decayed(_f(state), u_of, g).astype(state_dtype)
        return state, jnp.einsum("hkv,hk->hv", _f(state), q)

    def block(carry, hb):
        state, tail = carry
        x = _norm(hb, norm_w, eps)
        qkv, z = jnp.split(x @ _f(p["in_proj_qkvz"]["kernel"]),
                           [2 * kd + vd], axis=-1)
        b, al = jnp.split(x @ _f(p["in_proj_ba"]["kernel"]), 2, axis=-1)
        window = jnp.concatenate([tail, qkv])       # the K - 1 inputs before
        conv = jax.nn.silu(sum(w[j] * window[j:j + tb] for j in range(kw)))
        q, k, v = jnp.split(conv, [kd, 2 * kd], axis=-1)
        q = _l2(q.reshape(tb, nk, dk)) * dk ** -0.5
        k = _l2(k.reshape(tb, nk, dk))
        q, k = (jnp.repeat(t, nv // nk, axis=1) for t in (q, k))
        beta = jax.nn.sigmoid(b)                                # (T, Hv)
        g = -a * jax.nn.softplus(al + _f(p["dt_bias"]))
        state, o = jax.lax.scan(step, state,
                                (q, k, v.reshape(tb, nv, dv), g, beta))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * _f(p["norm_weight"])
        out = (o.reshape(tb, vd) * _z_gate(z)) @ _f(p["out_proj"]["kernel"])
        return (state, window[tb:]), out

    zero = (jnp.zeros((nv, dk, dv), state_dtype),
            jnp.zeros((kw - 1, 2 * kd + vd), F32))
    _, out = jax.lax.scan(block, zero, h.reshape(s // tb, tb, -1))
    return out.reshape(s, -1)


def _attention(h, p, norm_w, cfg):
    """One row: `Attn(N_in(h))` for h (S, hidden)."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, rd = cfg["rms_norm_eps"], _rotary_width(cfg)
    s = h.shape[0]
    freq = cfg["rope_theta"] ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                       # (S, rd / 2)

    def cached(blk):
        """What a token leaves for later queries: (k | v), (T, Hkv, 2 hd)."""
        hb, cos, sin = blk
        x = _norm(hb, norm_w, eps)
        k = _head_norm((x @ _f(p["k_proj"]["kernel"])).reshape(-1, nkv, hd),
                       p["k_norm"]["weight"], eps)
        v = (x @ _f(p["v_proj"]["kernel"])).reshape(-1, nkv, hd)
        return jnp.concatenate([_rope(k, cos, sin, rd), v], -1)

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
        keep = jnp.arange(s)[None, :] <= (first + jnp.arange(size))[:, None]
        x = _norm(cut(h), norm_w, eps)
        q, gate = jnp.split((x @ _f(p["q_proj"]["kernel"])).reshape(
            size, nh, 2 * hd), 2, axis=-1)
        q = _rope(_head_norm(q, p["q_norm"]["weight"], eps), cut(cos),
                  cut(sin), rd)
        q = q.reshape(size, nkv, nh // nkv, hd)
        logits = jnp.einsum("qgrd,sgd->grqs", q, k) * scale
        logits = jnp.where(keep[None, None], logits, -jnp.inf)
        o = jnp.einsum("grqs,sgd->qgrd", jax.nn.softmax(logits, -1), v)
        o = o.reshape(size, nh * hd) * jax.nn.sigmoid(
            gate.reshape(size, nh * hd))
        return o @ _f(p["o_proj"]["kernel"])

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def _renormalised(w, cfg):
    """The taken experts' weights over their sum (`norm_topk_prob`)."""
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def _shared(x, p):
    """The shared expert under its sigmoid gate, one number a token."""
    sh = p["shared_expert"]
    return jax.nn.sigmoid(x @ _f(p["shared_expert_gate"])) \
        * _swiglu(x, sh["gate"][0], sh["up"][0], sh["down"][0])


def _experts(x, p, cfg):
    """(this chip's part of the layer's result for x (T, hidden), the
    routing margin at every position): the taken experts that are held, and
    the gated shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    logits = x @ _f(p["gate"]["wg"])                            # all of them
    top, taken = jax.lax.top_k(logits, k + 1)    # the k taken, the best left
    idx = taken[..., :k]
    w = _renormalised(jnp.take_along_axis(jax.nn.softmax(logits, -1), idx,
                                          axis=-1), cfg)

    def one(out, e):
        gate, up, down, local = e
        weight = jnp.sum(jnp.where(idx == local + offset, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(x, gate, up, down), None

    ex = p["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    out = out + _shared(x, p)
    # the margin, in the router's logits, of the choice among HELD experts
    ids = jnp.arange(logits.shape[-1])
    here = (ids >= offset) & (ids < offset + held)
    is_taken = jnp.any(idx[..., None] == ids, axis=-2)          # (..., E)
    low_held = jnp.min(jnp.where(is_taken & here, logits, jnp.inf), axis=-1)
    best_held = jnp.max(jnp.where(~is_taken & here, logits, -jnp.inf),
                        axis=-1)
    return out, jnp.minimum(low_held - top[..., k],
                            top[..., k - 1] - best_held)


def _row(params, ids, cfg):
    """One row's (hidden states after the final norm (S, hidden), routing
    margin (S,), the smallest over the layers)."""
    eps = cfg["rms_norm_eps"]
    margin = jnp.full(ids.shape, jnp.inf, F32)
    layers = params["layers"]
    h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
    tb = _blocks(ids.shape[0], TOKEN_BLOCK)
    for i, kind in enumerate(_kinds(cfg)):
        weight = lambda name, i=i: layers[f"layer_{i}_{name}"]["weight"]  # noqa: E731
        mixer = _gdn if kind == "G" else _attention
        h = h + mixer(h, layers[f"layer_{i}"], weight("norm"), cfg)
        p = layers[f"layer_{i}_mlp"]

        def rest(hb, p=p, weight=weight):
            """The layer's experts, a block of tokens."""
            f, m = _experts(_norm(hb, weight("mlp_norm"), eps), p, cfg)
            return hb + f, m

        h, m = jax.lax.map(rest, h.reshape(-1, tb, h.shape[-1]))
        h, margin = h.reshape(-1, h.shape[-1]), jnp.minimum(margin,
                                                            m.reshape(-1))
    return _norm(h, params["norm_f"]["weight"], eps), margin


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
    logits of the choice among the held experts (the module text says how),
    the smallest over the layers, at position `last`."""
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
