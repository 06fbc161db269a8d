"""Plain reference for the Nemotron-H / Nemotron-3 configurations: the hybrid
decoder's forward pass and next-token loss in straightforward `jax.numpy`,
float32, matmuls at `highest` precision, no kernels, no cache, no chunks.

Follows the published architecture (NVIDIA Nemotron-H and Nemotron 3 Nano
reports; Hugging Face `modeling_nemotron_h.py`; Mamba-2, Dao and Gu 2024).
`h` is `(B, S, hidden)`; every layer is `h = h + mixer(RMSNorm(h))`, one
mixer a layer, its kind read from `hybrid_override_pattern`; then the final
norm and an untied output head.

- `M`, Mamba-2: `[z, xBC, dt] = in_proj(u)`; `xBC = silu(causal depthwise
  conv1d(xBC) + bias)` split into `x` (heads of `mamba_head_dim`), `B`, `C`
  (`n_groups` groups of `ssm_state_size`; heads `g H/G ..` use group g);
  `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the recurrence
  `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t`, `y_t = H_t C_t + D x_t`
  as a plain `lax.scan` over POSITIONS; `y = GroupRMSNorm(y * silu(z)) * w`
  (gate first, then the norm over each group); `out_proj`.
- `*`, attention: grouped-query, causal softmax, scale head_dim^-0.5, no
  bias and NO rotary embedding (see `assumed` in the configuration file;
  `attention_rotary` switches it, in the program and here).
- `E`, experts: `s = sigmoid(u W_r)` over all `router_experts`; the choice is
  the top `num_experts_per_tok` of `s + e_score_correction_bias`; weights
  `s[choice] / sum(s[choice]) * routed_scaling_factor`; an expert is
  `relu(u W_up)^2 W_down`; the shared expert has the same form; the result is
  the sum over the chosen experts THAT ARE HELD HERE (`n_routed_experts` from
  `expert_offset` on) plus the shared expert. What the absent experts would
  add is left out, as the configuration's `deployment` says.

Departures: none in the mathematics. The layers are walked in Python over the
program's weight tree (`layers/layer_<i>/...`, `layers/layer_<i>_norm`), which is
only how the weights are stored; weights are upcast a layer (an expert) at a
time so that the float32 copy fits beside the bf16 tree. It reads the program's weight TREE and none of its code.

THE ROUTING MARGIN. `perfbench/README.md` words it as (score of the last
expert taken - score of the best one left) / score of the last expert taken.
For a softmax router that is the gap in logits, the unit in which bf16
rounding of the hidden state acts. For a sigmoid router it is not:
`d s / s = (1 - s) d(logit)`, so at the 6th of 128 scores the README's
measure reads about a twelfth of the logit gap, and at `MARGIN_SAFE` 0.02
one row in thousands would be judged (PERF.md, PR 41, has the counts read on
the chip). `last_logits_and_margin` therefore measures the gap where the
rounding acts, in the router's logits: `(c_taken - c_left) / max(s (1 - s) of
the two)`, `c = s + bias` being what the choice is made of and `s (1 - s)`
the slope of the sigmoid there. `routing_margins` gives both measures.
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


def _mamba(u, p, cfg):
    nh, hp, n, g = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                    cfg["ssm_state_size"], cfg["n_groups"])
    di, kw = nh * hp, cfg["conv_kernel"]
    b, s, _ = u.shape
    zxbcdt = u @ _f(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    w = _f(p["conv_kernel"])                            # (K, C)
    conv = sum(w[j] * padded[:, j:j + s] for j in range(kw))
    if "conv_bias" in p:
        conv = conv + _f(p["conv_bias"])
    x, bm, cm = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
    x = x.reshape(b, s, nh, hp)
    bm = jnp.repeat(bm.reshape(b, s, g, n), nh // g, axis=2)   # (B,S,H,N)
    cm = jnp.repeat(cm.reshape(b, s, g, n), nh // g, axis=2)
    dt = jax.nn.softplus(dt + _f(p["dt_bias"]))                # (B,S,H)
    a = -jnp.exp(_f(p["A_log"]))

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hp, n), F32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + _f(p["D"])[:, None] * x         # (B,S,H,P)
    y = _gated_group_norm(y.reshape(b, s, di), z, p["norm_weight"], g,
                          cfg["norm_eps"])
    return y @ _f(p["out_proj"]["kernel"])


def _gated_group_norm(y, z, w, groups, eps):
    """`RMSNorm_groups(y * silu(z)) * w`: the gate FIRST, then the norm over
    each of the `groups` groups of the inner width."""
    y = y * jax.nn.silu(z)
    yg = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape) * _f(w)


def _rope(x, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, p, cfg):
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b, s, _ = u.shape
    q = (u @ _f(p["q_proj"]["kernel"])).reshape(b, s, nh, hd)
    k = (u @ _f(p["k_proj"]["kernel"])).reshape(b, s, nkv, hd)
    v = (u @ _f(p["v_proj"]["kernel"])).reshape(b, s, nkv, hd)
    if cfg.get("attention_rotary", False):
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], logits,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    return out.reshape(b, s, nh * hd) @ _f(p["o_proj"]["kernel"])


def _relu2_ffn(x, up, down):
    return jnp.square(jax.nn.relu(x @ _f(up))) @ _f(down)


def routing_margins(scores, chosen_by, k):
    """Per token, both measures of how decided the choice of the top `k` is:
    (the README's: the gap between the last taken and the best left of what
    the choice is made of, over the last taken; the gap in the router's
    LOGITS: the same gap over the sigmoid's slope `s (1 - s)` at the nearer
    of the two, which is the measure the oracle uses here)."""
    top, idx = jax.lax.top_k(chosen_by, k + 1)
    gap = top[..., k - 1] - top[..., k]
    s_pair = jnp.take_along_axis(scores, idx[..., k - 1:k + 1], axis=-1)
    slope = jnp.max(s_pair * (1.0 - s_pair), axis=-1)
    return gap / top[..., k - 1], gap / slope


def _experts(u, p, cfg):
    """(this chip's part of the layer's result, both routing margins at every
    position): the chosen experts that are held, and the shared expert."""
    k = cfg["num_experts_per_tok"]
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    scores = jax.nn.sigmoid(u @ _f(p["gate"]["wg"]))          # all of them
    chosen_by = scores + _f(p["gate"]["bias"])
    _, idx = jax.lax.top_k(chosen_by, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]

    def one(out, e):
        up, down, local = e
        weight = jnp.sum(jnp.where(idx == local + offset, w, 0.0), axis=-1)
        return out + weight[..., None] * _relu2_ffn(u, up, down), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (p["experts"]["up"], p["experts"]["down"], jnp.arange(held)))
    shared = p["shared_expert"]
    out = out + _relu2_ffn(u, shared["up"][0], shared["down"][0])
    return out, routing_margins(scores, chosen_by, k)


def _walk(params, ids, cfg):
    """(hidden states after the final norm (B, S, hidden), both routing
    margins (B, S), each the smallest over the expert layers)."""
    eps = cfg["norm_eps"]
    big = jnp.full(ids.shape, jnp.inf, F32)
    margins = (big, big)
    with jax.default_matmul_precision("highest"):
        h = _f(jnp.take(params["embed_tokens"], ids, axis=0))
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            p = params["layers"][f"layer_{i}"]
            u = _rms(h, params["layers"][f"layer_{i}_norm"]["weight"], eps)
            if kind == "M":
                h = h + _mamba(u, p, cfg)
            elif kind == "*":
                h = h + _attention(u, p, cfg)
            else:
                out, m = _experts(u, p, cfg)
                h = h + out
                margins = tuple(jnp.minimum(a, b)
                                for a, b in zip(margins, m))
        return _rms(h, params["norm_f"]["weight"], eps), margins


def hidden_states(params, ids, cfg):
    return _walk(params, ids, cfg)[0]


def _last(h, last, params):
    rows = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    with jax.default_matmul_precision("highest"):
        return rows @ _f(params["lm_head"])


def last_logits(params, ids, last, cfg):
    """(B, vocab) float32 logits at position `last[b]` of each row."""
    return _last(hidden_states(params, ids, cfg), last, params)


def last_logits_and_margins(params, ids, last, cfg):
    """(logits, the README's margin, the margin in the router's logits), the
    margins at position `last` of each row: for the builder's reading of
    both (PERF.md, PR 41)."""
    h, margins = _walk(params, ids, cfg)
    at = lambda m: jnp.take_along_axis(m, last[:, None], axis=1)[:, 0]
    return _last(h, last, params), at(margins[0]), at(margins[1])


def last_logits_and_margin(params, ids, last, cfg):
    """(logits, routing margin) from one pass: the margin in the router's
    logits (the module text says why), the smallest over the expert layers,
    at position `last` of each row."""
    logits, _, margin = last_logits_and_margins(params, ids, last, cfg)
    return logits, margin


def mean_loss(params, ids, cfg):
    """Mean next-token cross-entropy over rows of `ids` (B, S), one row's
    logits at a time."""
    def row(r):
        h = hidden_states(params, r[None], cfg)[0, :-1]
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(h @ _f(params["lm_head"]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, r[1:, None], axis=1))
    return jnp.mean(jax.lax.map(row, ids))
