"""Plain reference of the toy sparse-expert decoder (Mixtral's equations,
Jiang et al. 2024): per layer RMSNorm -> q/k/v without bias -> rotary ->
grouped-query causal attention -> o -> residual -> RMSNorm -> router softmax
over all experts, the top k renormalised, each token through its k SwiGLU
experts -> residual; final RMSNorm; untied head. float32, `highest`, every
expert computed for every token and masked: no dispatch, no capacity."""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(params, ids, cfg):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, k = cfg["hidden_size"] // nh, cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s = ids.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, p):
        f = lambda t: t.astype(F32)
        att, moe = p["self_attn"], p["block_sparse_moe"]
        x = _rms(h, p["input_layernorm"]["weight"], eps)
        q = _rope((x @ f(att["q_proj"]["kernel"])).reshape(b, s, nh, hd), theta)
        kk = _rope((x @ f(att["k_proj"]["kernel"])).reshape(b, s, nkv, hd), theta)
        v = (x @ f(att["v_proj"]["kernel"])).reshape(b, s, nkv, hd)
        kk, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (kk, v))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(F32(hd))
        logits = jnp.where(causal[None, None], logits, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        h = h + out.reshape(b, s, nh * hd) @ f(att["o_proj"]["kernel"])
        x = _rms(h, p["post_attention_layernorm"]["weight"], eps)
        probs = jax.nn.softmax(x @ f(moe["gate"]["wg"]), -1)          # (b, s, E)
        kth = jnp.sort(probs, -1)[..., -k][..., None]
        w = jnp.where(probs >= kth, probs, 0.0)
        w = w / jnp.sum(w, -1, keepdims=True)
        ex = moe["experts"]
        up = jnp.einsum("bsd,edf->bsef", x, f(ex["up"]))
        gate = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, f(ex["gate"])))
        y = jnp.einsum("bsef,efd->bsed", gate * up, f(ex["down"]))
        return h + jnp.sum(w[..., None] * y, axis=2), None

    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["embed_tokens"], ids, axis=0).astype(F32)
        h, _ = jax.lax.scan(layer, h, params["layers"])
        return _rms(h, params["norm"]["weight"], eps)


def last_logits(params, ids, last, cfg):
    h = hidden_states(params, ids, cfg)
    rows = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    with jax.default_matmul_precision("highest"):
        return rows @ params["lm_head"].astype(F32)


def mean_loss(params, ids, cfg):
    h = hidden_states(params, ids, cfg)[:, :-1]
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(h @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=2))
