"""Ling-3.0-flash's language model (HF `BailingMoeV2_5`-style config keys;
inclusionAI): a hybrid of LINEAR attention and LATENT attention over sparse
experts. Every layer is `h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))`, then
a final RMSNorm and an untied head. By PUBLISHED layer index p:

- the mixer is **latent attention (MLA)** where `(p + 1) % layer_group_size
  == 0` and **Kimi Delta Attention (KDA)** elsewhere: five to one;
- the FFN is a dense SwiGLU for the first `first_k_dense_replace` layers
  and after them 512 sigmoid-routed SwiGLU experts (top 8, the choice
  limited to 4 of 8 groups, a selection bias, weights over their sum times
  2.5) beside one shared expert.

**KDA** (Kimi Linear, arXiv:2510.26692), H heads of d: `q, k, v =
silu(conv4(x W))` (causal depthwise, no bias); `q`, `k` L2-normalised a
head, `q` times `d^-0.5`; a log-decay a CHANNEL `g = kda_lower_bound *
sigmoid(exp(A_log)[head] * (x W_f + dt_bias))` (the bounded "safe" gate:
`exp(g)` in `[e^-5, 1)`); `beta = sigmoid(x W_b)` a head. A head's state is
a MATRIX `S` (d x d), float32:

    S <- diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t

and the layer's output `(RMSNorm_head(o_t) * sigmoid(x W_g)) W_o`. One
token is `ops/pallas/kda.kda_state_update` on the stored state; a sequence
is `hybrid.delta_chunked`, exact against the recurrence.

**MLA** (DeepSeek-V2's, no query compression): `q = x W_q` -> H x (128 nope
+ 64 rope); `[c, k_r] = x W_kva` (512 + 64), `c <- RMSNorm(c)`; a head's key
is `[c W_k^h | k_r]` and its value `c W_v^h`; `q` is RMS-normalised a head
and `k_r` once, then the rope parts are rotated; softmax scale `192^-0.5`; a
head's output times `sigmoid(x W_gate)[head]`, then `W_o`. The cache keeps
`[c | rotated k_r]`, 576 values a token (`kv_cache.LatentCache`). PREFILL
expands its own tokens' keys and values; DECODE is the absorbed form over
the latents (`ops/pallas/mla.mla_latent_decode`), the step's token staged
and landed once.

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(the grouped expert GEMM under a scan would copy a layer's experts every
step). The chip may hold a SHARE of the model: `num_experts` of
`router_experts` from `expert_offset` on (whole groups), a slice of the
vocabulary, and of the depth the layers `published_layers` names
(`perfbench/configs/ling3-flash-l6-ep4.json` has the deployment; its
`assumed` lists what the catalog's config does not settle).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.llama import RMSNorm, _dense

F32 = jnp.float32
# Tokens of a prefill that walk the layers together (`hybrid.row_groups`): at 8 x
# 1024 the chunked KDA form's float32 operands are 0.13 GB each, the experts'
# sorted rows 0.34 GB, the expanded latent attention's keys 0.1 GB, beside
# 8.8 GB of weights and 1.7 GB of cache.
PREFILL_TOKENS = 8192


@dataclasses.dataclass(frozen=True)
class LingLinearConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    intermediate_size: int = 6144
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    # the published indices of the layers held (None: all of them, 0 .. L-1):
    # a layer's mixer is decided by its PUBLISHED index
    published_layers: Optional[Tuple[int, ...]] = None
    num_attention_heads: int = 32
    head_dim: int = 128
    # KDA
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # experts: `num_experts` are HELD here, of the `router_experts` the router
    # scores (None: all of them are held), from `expert_offset` on
    num_experts: int = 512
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 8
    topk_group: int = 4
    # seeded router: the scale of the selection bias drawn at init (a zero
    # one would hide a dropped term; `models/nemotron_h.py` has the readings)
    router_bias_scale: float = 0.01
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    dispatch_impl: str = "auto"

    def __post_init__(self):
        pub = self.published_layers
        if pub is not None:
            object.__setattr__(self, "published_layers", tuple(pub))
            if len(pub) != self.num_hidden_layers or list(pub) != sorted(set(pub)):
                raise ValueError(
                    f"published_layers {pub}: the {self.num_hidden_layers} "
                    "held layers' published indices, ascending")
        scored = self.router_experts or self.num_experts
        size = max(scored // self.n_group, 1)
        if self.n_group > 1 and (scored % self.n_group
                                 or self.expert_offset % size
                                 or self.num_experts % size):
            raise ValueError(
                f"experts {self.expert_offset} .. +{self.num_experts} of "
                f"{scored} in {self.n_group} groups: a share is whole groups")

    # ---- the walk
    @property
    def kinds(self) -> str:
        """A layer's mixer, by its place here: `K` KDA, `A` latent attention."""
        pub = self.published_layers or range(self.num_hidden_layers)
        return "".join("A" if (p + 1) % self.layer_group_size == 0 else "K"
                       for p in pub)

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep a cache that grows with the sequence."""
        return self.kinds.count("A")

    # ---- KDA sizes
    @property
    def d_inner(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.d_inner                 # q, k and v side by side

    @property
    def kda_state_shape(self) -> tuple:
        """One sequence's state in one KDA layer: a head's `S`, d_k x d_v."""
        return (self.num_attention_heads, self.head_dim, self.head_dim)

    # ---- MLA sizes
    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # ---- bytes, by kind (host arithmetic: telemetry, serve-mode accounting)
    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        from deepspeed_tpu.inference.kv_cache import LatentCache
        return {"latent_kv_bytes": LatentCache.nbytes(
            self.num_kv_layers, batch, max_len, self.latent_width,
            dtype or self.dtype)}

    def recurrent_state_bytes(self, batch: int, dtype=None) -> int:
        from deepspeed_tpu.inference.kv_cache import RecurrentState
        return RecurrentState.nbytes(
            self.kinds.count("K"), batch, self.kda_state_shape,
            self.short_conv_kernel_size, self.conv_dim, dtype or self.dtype)


# ---------------------------------------------------------------------- KDA


class KDAMixer(nn.Module):
    cfg: LingLinearConfig

    @nn.compact
    def __call__(self, x, state=None, slot=None):
        """x (B, S, D). `state`: None (a plain forward from a zero state), or
        the model's stacked `RecurrentState` with this layer's `slot` in it:
        S == 1 is a decode step on the stored state, S > 1 continues from it
        by the chunked form. Returns (out, state)."""
        cfg = self.cfg
        nh, d, di = cfg.num_attention_heads, cfg.head_dim, cfg.d_inner
        kw = cfg.short_conv_kernel_size
        b, s, _ = x.shape
        qkv = _dense(3 * di, ("embed", "heads"), cfg.dtype, "qkv_proj")(x)
        f, gate = jnp.split(_dense(2 * di, ("embed", "heads"), cfg.dtype,
                                   "fg_proj")(x), 2, axis=-1)
        beta = jax.nn.sigmoid(_dense(nh, ("embed", None), cfg.dtype,
                                     "b_proj")(x).astype(F32))  # (B, S, H)
        bound = 1.0 / math.sqrt(kw)
        conv_w = self.param(
            "conv_kernel", lambda key, sh, dt=F32: jax.random.uniform(
                key, sh, dt, -bound, bound), (kw, 3 * di), F32).astype(F32)
        a = jnp.exp(self.param("A_log", hybrid.a_log_init, (nh,),
                               F32).astype(F32))
        dt_bias = self.param("dt_bias", hybrid.delta_dt_bias_init, (di,), F32)
        norm_w = self.param("norm_weight", nn.initializers.ones_init(), (d,),
                            F32)
        # the bounded gate: a log-decay a channel in (kda_lower_bound, 0)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            a[:, None] * (f.astype(F32) + dt_bias.astype(F32)
                          ).reshape(b, s, nh, d))

        tail = (jnp.zeros((b, kw - 1, 3 * di), qkv.dtype) if state is None
                else state.conv[slot])
        window = jnp.concatenate([tail, qkv], axis=1)      # (B, S + K - 1, C)
        conv = hybrid.short_conv(conv_w, window, s)
        q, k, v = (t.reshape(b, s, nh, d) for t in
                   jnp.split(jax.nn.silu(conv), 3, axis=-1))
        if state is not None and s > 1:
            # a cached prefill, between the convolution and the gate in one
            # call: the keys' norms, the rule on the stored state, the
            # heads' norm
            o, last = hybrid.delta_prefill(
                q, k, v, g, beta, state.ssm[slot], hybrid.DELTA_CHUNK,
                norm_w, cfg.rms_norm_eps)
            ssm = jax.lax.dynamic_update_index_in_dim(state.ssm, last, slot,
                                                      0)
        else:
            q, k = hybrid.recurrence_keys(q, k)
            if state is not None:
                from deepspeed_tpu.ops.attention import kda_update
                o, ssm = kda_update(state.ssm, slot, q[:, 0], k[:, 0],
                                    v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
            else:       # the plain forward, which is differentiated
                o, _ = hybrid.delta_chunked(
                    q, k, v, g, beta, jnp.zeros((b, nh, d, d), F32))
            # the norm over each head's d
            o = hybrid.head_norm(o, norm_w, cfg.rms_norm_eps)
        if state is not None:
            state = state.replace(
                ssm=ssm, conv=jax.lax.dynamic_update_index_in_dim(
                    state.conv, window[:, -(kw - 1):].astype(state.conv.dtype),
                    slot, 0))
        # the gate (sigmoid, full rank)
        o = o.reshape(b, s, di) * jax.nn.sigmoid(gate.astype(F32))
        return _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                      "o_proj")(o.astype(cfg.dtype)), state


# ---------------------------------------------------------------------- MLA


class MLAMixer(nn.Module):
    cfg: LingLinearConfig

    @nn.compact
    def __call__(self, x, latent=None, slot=None):
        """x (B, S, D). `latent`: None (a plain causal pass), or the model's
        `LatentCache` with this layer's `slot` in it. S > 1 is a PREFILL
        FROM THE EMPTY CACHE (the v1 `generate` program's only use): the new
        tokens attend each other in the expanded form and their latents are
        written. S == 1 is a decode step in the absorbed form, its token
        staged. Returns (out, the cache written, or the staged (B, W) row)."""
        from deepspeed_tpu.ops.attention import apply_rotary_emb, rope_cos_sin
        cfg = self.cfg
        nh, dn, dr, dv, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                                cfg.qk_rope_head_dim, cfg.v_head_dim,
                                cfg.kv_lora_rank)
        b, s, _ = x.shape
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        q = _dense(nh * cfg.qk_head_dim, ("embed", "heads"), cfg.dtype,
                   "q_proj")(x).reshape(b, s, nh, cfg.qk_head_dim)
        c, k_r = jnp.split(_dense(rank + dr, ("embed", None), cfg.dtype,
                                  "kv_a_proj")(x), [rank], axis=-1)
        c = norm("kv_a_norm")(c)
        w_kvb = self.param("kv_b_proj", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (None, "heads")),
            (rank, nh * (dn + dv)), F32).astype(cfg.dtype).reshape(
                rank, nh, dn + dv)
        gate = jax.nn.sigmoid(_dense(nh, ("embed", None), cfg.dtype,
                                     "g_proj")(x).astype(F32))  # (B, S, H)
        # the norms before the rotation: a head's whole query, the shared
        # rope key (the nope key is a map of the already normalised latent)
        q, k_r = norm("q_norm")(q), norm("k_norm")(k_r)
        index = jnp.zeros((b,), jnp.int32) if latent is None else latent.index
        cos, sin = rope_cos_sin(index[:, None] + jnp.arange(s)[None, :], dr,
                                cfg.rope_theta, cfg.dtype)
        q_nope, q_rope = q[..., :dn], apply_rotary_emb(q[..., dn:], cos, sin)
        k_r = apply_rotary_emb(k_r[:, :, None], cos, sin)[:, :, 0]
        row = jnp.concatenate([c, k_r], axis=-1)               # (B, S, W)
        scale = cfg.qk_head_dim ** -0.5

        if latent is not None and s == 1:
            from deepspeed_tpu.ops.attention import latent_decode
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kvb[..., :dn])
            o_lat = latent_decode(q_lat, q_rope[:, 0],
                                  latent.c.replace(layer=slot), index + 1,
                                  scale, new=row[:, 0], slots=index)
            o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(cfg.dtype),
                           w_kvb[..., dn:])[:, None]
            made = row[:, 0]
        else:
            from deepspeed_tpu.ops.attention import attention
            kv = jnp.einsum("bsr,rhn->bshn", c, w_kvb)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None],
                                                (b, s, nh, dr))], axis=-1)
            # one head width for q, k and v, a multiple of the lanes: zeros
            # add nothing to a score and the value's are cut off again
            wide = -(-cfg.qk_head_dim // 128) * 128
            fill = lambda t: jnp.pad(  # noqa: E731
                t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))
            o = attention(fill(jnp.concatenate([q_nope, q_rope], axis=-1)),
                          fill(k), fill(kv[..., dn:]), causal=True,
                          softmax_scale=scale, impl=cfg.attn_impl)[..., :dv]
            made = None if latent is None else latent.write_rows(slot, row)
        o = (o.astype(F32) * gate[..., None]).astype(cfg.dtype)
        return _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                      "o_proj")(o.reshape(b, s, nh * dv)), made


# ------------------------------------------------------------------- layers


class Layers(nn.Module):
    """The walk over the layers: one loop over `cfg.kinds`, each layer's
    mixer (`layer_<i>`) built by its kind and handed its slab of its kind's
    stacked buffer, then its FFN (`layer_<i>_mlp`)."""
    cfg: LingLinearConfig

    @nn.compact
    def __call__(self, h, cache=None):
        cfg = self.cfg
        state = None if cache is None else cache.state
        latent = None if cache is None else cache.latent
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        staged = []  # a decode step's new latent row of each MLA layer
        for i, kind in enumerate(cfg.kinds):
            slot = cfg.kinds[:i].count(kind)
            x = norm(f"layer_{i}_norm")(h)
            if kind == "K":
                out, state = KDAMixer(cfg, name=f"layer_{i}")(x, state, slot)
            else:
                out, made = MLAMixer(cfg, name=f"layer_{i}")(x, latent, slot)
                if made is not None and h.shape[1] == 1:
                    staged.append(made)
                elif made is not None:
                    latent = made
            h = h + out
            x = norm(f"layer_{i}_mlp_norm")(h)
            if i < cfg.first_k_dense_replace:
                h = h + hybrid.DenseFFN(cfg, name=f"layer_{i}_mlp")(x)
            else:
                # group-limited sigmoid experts beside a shared one
                h = h + hybrid.held_experts(
                    cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                    activation="silu", score_fn="sigmoid",
                    shared=cfg.moe_shared_expert_intermediate_size)(
                        x, train=False)
        if staged:  # the step's one write, every MLA layer's token
            latent = latent.land(jnp.stack(staged))
        if cache is not None:
            cache = cache.replace(state=state, latent=latent)
        return h, cache


class LingLinearForCausalLM(nn.Module):
    cfg: LingLinearConfig
    # what the expert layers count inside a serving program, summed over the
    # call by the engine (`serving` event)
    program_counters = hybrid.EXPERT_COUNTERS

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        return hybrid.causal_lm(self, Layers, input_ids, labels, cache,
                                eps=self.cfg.rms_norm_eps,
                                prefill_tokens=PREFILL_TOKENS)

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions, by kind: the latent rows of the MLA layers, the
        KDA layers' matrix states and convolution tails, no K or V at all."""
        from deepspeed_tpu.inference.kv_cache import (HybridCache, LatentCache,
                                                      RecurrentState)
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        return HybridCache(
            kv=None,
            latent=LatentCache.create(cfg.num_kv_layers, batch, max_len,
                                      cfg.latent_width, dtype=dtype),
            state=RecurrentState.create(
                cfg.kinds.count("K"), batch, cfg.kda_state_shape,
                cfg.short_conv_kernel_size, cfg.conv_dim, dtype=dtype))


init_params_and_specs, materialize_params, ling_linear_loss_fn = \
    hybrid.entry_points(LingLinearForCausalLM)
