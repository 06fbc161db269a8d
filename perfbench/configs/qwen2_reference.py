"""Plain reference for the Qwen2.5 configurations: the decoder's forward pass
and next-token loss in straightforward `jax.numpy`, float32, matmuls at
`highest` precision, no kernels, no cache, no batching tricks.

Follows the published architecture (Qwen2 technical report; Hugging Face
`modeling_qwen2.py`): token embedding; per layer RMSNorm -> q/k/v projections
WITH bias -> rotary embedding (half-rotation form, theta from the config) ->
grouped-query causal attention -> output projection without bias -> residual
-> RMSNorm -> SwiGLU MLP -> residual; final RMSNorm; output head tied to the
embedding where the config says so. Departures: none in the mathematics; the
layers are walked with `lax.scan` over the program's stacked weight tree
(`layers/<module>/kernel` with a leading layer axis), which is only how the
weights are stored.

It reads the program's weight TREE (names and shapes) and none of its code.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    # x: (B, S, H, D); positions 0..S-1; rotate halves
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(params: Dict[str, Any], ids, cfg: Dict[str, Any]):
    """(B, S) token ids -> (B, S, hidden) after the final norm, float32."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s = ids.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, p):
        f = lambda t: t.astype(F32)
        att = p["self_attn"]
        x = _rms(h, p["input_layernorm"]["weight"], eps)
        q = x @ f(att["q_proj"]["kernel"]) + f(att["q_proj"]["bias"])
        k = x @ f(att["k_proj"]["kernel"]) + f(att["k_proj"]["bias"])
        v = x @ f(att["v_proj"]["kernel"]) + f(att["v_proj"]["bias"])
        q = _rope(q.reshape(b, s, nh, hd), theta)
        k = _rope(k.reshape(b, s, nkv, hd), theta)
        v = v.reshape(b, s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=2)      # query head i reads
        v = jnp.repeat(v, nh // nkv, axis=2)      # kv head i // (nh/nkv)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
        logits = jnp.where(causal[None, None], logits, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        h = h + out.reshape(b, s, nh * hd) @ f(att["o_proj"]["kernel"])
        x = _rms(h, p["post_attention_layernorm"]["weight"], eps)
        mlp = p["mlp"]
        gate = jax.nn.silu(x @ f(mlp["gate_proj"]["kernel"]))
        h = h + (gate * (x @ f(mlp["up_proj"]["kernel"]))) \
            @ f(mlp["down_proj"]["kernel"])
        return h, None

    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["embed_tokens"], ids, axis=0).astype(F32)
        h, _ = jax.lax.scan(layer, h, params["layers"])
        return _rms(h, params["norm"]["weight"], eps)


def _head(params, cfg):
    if cfg.get("tie_word_embeddings", False):
        return params["embed_tokens"].astype(F32).T
    return params["lm_head"].astype(F32)


def last_logits(params, ids, last, cfg):
    """(B, vocab) float32 logits at position `last[b]` of each right-padded
    row (causal attention never looks at the padding)."""
    h = hidden_states(params, ids, cfg)
    rows = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    with jax.default_matmul_precision("highest"):
        return rows @ _head(params, cfg)


def mean_loss(params, ids, cfg):
    """Mean next-token cross-entropy over rows of `ids` (B, S): position t
    predicts token t+1, the last position predicts nothing. One row's
    logits at a time ((S, vocab) float32 is 1.2 GB at 2048 x 151936)."""
    head = _head(params, cfg)

    def row(r):
        h = hidden_states(params, r[None], cfg)[0, :-1]
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(h @ head, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, r[1:, None], axis=1))
    return jnp.mean(jax.lax.map(row, ids))
