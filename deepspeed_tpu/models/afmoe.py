"""Arcee Trinity (HF `model_type: afmoe` config keys; Trinity-Mini 26B-A3B,
Trinity-Nano): WINDOW and FULL softmax attention mixed by `layer_types`
(three `sliding_attention` layers to one `full_attention`), the window
layers with rotary position and the full ones with NONE (NoPE), grouped
queries with a norm on every query and key head and a sigmoid GATE on the
attention's output, four RMSNorms a layer, and sigmoid-routed SwiGLU experts
behind `num_dense_layers` leading dense layers. With each `N` an RMSNorm of
its own weight (eps `rms_norm_eps`), D `hidden_size`, a layer is

    x0 = Embed[ids] * sqrt(D)                       (`mup_enabled`)
    a  = N_in(x)
    q = W_q a   k = W_k a   v = W_v a   g = W_g a   (heads of `head_dim`; no bias)
    q = N_q(q), k = N_k(k)                          over a head's values, one weight each
    sliding: q, k = RoPE(q, k; rope_theta, position t), halves (i, i + head_dim / 2)
    o_t = sum_j softmax_j(q_t . k_j / sqrt(head_dim)) v_j   over j <= t, and
                                                    for sliding t - j < sliding_window
    x  = x + N_post_attn(W_o (o * sigmoid(g)))
    f  = FFN(N_pre_mlp(x));   x = x + N_post_mlp(f)

the post norms on a sub-layer's OUTPUT, as `models/openpangu.py`'s. The FFN
is a dense SwiGLU of `intermediate_size` in the first `num_dense_layers`
layers and after them `router_experts` sigmoid-scored experts: the
`num_experts_per_tok` largest of `s + b` (`b` the selection bias, in the
CHOICE only; no groups), weights `s` over their sum (`route_norm`) times
`route_scale`, beside `num_shared_experts` shared ones, unweighted
(`moe/layer.MoE` as `hybrid.held_experts` builds it). Then a final
RMSNorm and an untied head.

THE CACHE (`make_cache`; `inference/kv_cache.HybridCache`): `kv`, full-length
rows of K and V over the FULL layers alone, and `window`, a RING of
`sliding_window` slots a row over the window layers (position p in slot p
mod the window), no `state`. A DECODE step stages its token a layer and
lands each kind ONCE (`KVCache.land`); both kinds are read by the dense
decode kernel, a ring by a count of live slots and the staged token's slot
(`ops.attention.cached_attention`: `self_attn_ring_decode`; a window key is
rotated BEFORE it is cached, so its slot need say nothing of its position).
A pass of S > 1 over a cache is a PREFILL FROM THE EMPTY CACHE: whole rows
through the flash forward, BANDED in the window layers
(`ops.attention.banded_prefill`), a few rows of the batch at a time through
all the layers (`hybrid.row_groups`), and returns logits `(B, 1, V)`: the head at
each row's last position only.

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(the grouped expert GEMM under a scan would copy a layer's experts every
step). The chip may hold a SHARE of the model: `num_experts` of
`router_experts` from `expert_offset` on, and of the depth as many layers as
`num_hidden_layers` says (`perfbench/configs/trinity-mini-l16-ep8.json` has
the deployment; its `assumed` lists what the published config does not
settle). No HF converter and no pipeline adapter for this family yet.
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
SLIDING, FULL = "sliding_attention", "full_attention"
# Tokens of a prefill that walk the layers together (`hybrid.row_groups`): two
# rows
# of 8,192. The widest temporaries are a dense layer's 2 x 6,144 a token (0.4
# GB at 16,384 tokens) and the gathered rows of the held experts.
PREFILL_TOKENS = 16384


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # a layer's kind; None: every `global_attn_every_n_layers`-th is full
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    # experts: `num_experts` are HELD here, of the `router_experts` the router
    # scores (None: all of them are held), from `expert_offset` on
    num_experts: int = 128
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    dispatch_impl: str = "auto"
    attn_impl: str = "auto"

    # the family's ONE router, as `hybrid.held_experts` reads it: the best
    # of ALL the biased scores at once. Constants of the class, not fields
    n_group = 1
    topk_group = 1
    router_bias_scale = 0.01    # the selection bias is seeded normal(0.01)

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            n = self.global_attn_every_n_layers
            kinds = tuple(FULL if (i + 1) % n == 0 else SLIDING
                          for i in range(self.num_hidden_layers))
        kinds = tuple(kinds)
        if len(kinds) != self.num_hidden_layers or set(kinds) - {SLIDING,
                                                                  FULL}:
            raise ValueError(
                f"afmoe: layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))}; {self.num_hidden_layers} of "
                f"{SLIDING!r} / {FULL!r} are walked")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("afmoe: query heads share KV heads in whole "
                             "groups")
        object.__setattr__(self, "layer_types", kinds)

    # ---- what `hybrid.held_experts` reads, under the names it reads
    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def moe_shared_expert_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    # ---- the walk
    @property
    def window_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    # ---- bytes, by kind (host arithmetic: telemetry, serve-mode accounting)
    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """K and V held for `batch` sequences of up to `max_len` positions,
        as `make_cache` holds them: the window layers' rings, whatever the
        length, and the full layers' rows."""
        slot = 2 * batch * self.num_key_value_heads * self.head_dim \
            * jnp.dtype(dtype or self.dtype).itemsize
        return {"window_kv_bytes": self.window_layers * self.sliding_window
                * slot,
                "full_kv_bytes": self.full_layers * max_len * slot}


class HeadNorm(nn.Module):
    """RMSNorm over the values of ONE head, the same weight for every head
    (`q_norm`, `k_norm`)."""
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones_init(),
                       (x.shape[-1],), F32)
        x = x.astype(F32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + self.eps) * w).astype(self.dtype)


def _rotated(cfg: AfmoeConfig, q, k, positions, sliding: bool):
    """A layer's positional embedding: a WINDOW layer's q and k rotated at
    `positions` (B, S) or (S,); a full layer's as they are (NoPE)."""
    if not sliding:
        return q, k
    from deepspeed_tpu.ops.attention import apply_rotary_emb, rope_cos_sin
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, F32)
    return apply_rotary_emb(q, cos, sin), apply_rotary_emb(k, cos, sin)


def _gated(o, g, dtype):
    """The attention's output under its sigmoid gate, elementwise."""
    return (o.astype(F32) * jax.nn.sigmoid(g.astype(F32))).astype(dtype)


class GatedAttention(nn.Module):
    """Grouped-query causal attention with head norms and an output gate;
    `sliding`: under the window, with rotary position (a full layer has no
    positional embedding)."""
    cfg: AfmoeConfig
    sliding: bool

    @nn.compact
    def __call__(self, x, kv=None, slot=None):
        """x (B, S, hidden). `kv` None: a plain causal pass over the tokens
        themselves. With this KIND's `KVCache` (the rings for a window
        layer, the full-length rows else) and this layer's `slot` in it: S
        == 1 is a decode step, its token STAGED, returning (out, the staged
        (k, v) (B, Hkv, D)); S > 1 is a prefill from the empty cache, which
        attends its own tokens and writes them, returning (out, the cache
        of this kind)."""
        from deepspeed_tpu.inference.kv_cache import decode_mask
        from deepspeed_tpu.ops import attention as ops
        cfg = self.cfg
        hd, nh, nkv = (cfg.head_dim, cfg.num_attention_heads,
                       cfg.num_key_value_heads)
        b, s, _ = x.shape
        proj = lambda n, axes, name: _dense(  # noqa: E731
            n * hd, ("embed", axes), cfg.dtype, name)(x)
        q = proj(nh, "heads", "q_proj").reshape(b, s, nh, hd)
        k = proj(nkv, "kv_heads", "k_proj").reshape(b, s, nkv, hd)
        v = proj(nkv, "kv_heads", "v_proj").reshape(b, s, nkv, hd)
        g = proj(nh, "heads", "gate_proj")
        q = HeadNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = HeadNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        q, k = _rotated(cfg, q, k, jnp.arange(s) if kv is None
                        else kv.index[:, None] + jnp.arange(s)[None, :],
                        self.sliding)
        window = cfg.sliding_window if self.sliding else None

        made = None
        if kv is not None and s == 1:
            views = tuple(c.replace(stage=new[:, 0].astype(c.stack.dtype))
                          for c, new in zip(kv.layer_views(slot, staged=True),
                                            (k, v)))
            # a ring's reader makes its own count; the full rows' prefix
            mask = None if kv.ring else decode_mask(kv.index[:, None],
                                                    kv.max_len)
            o = ops.cached_attention(q, *views, kv.index, mask,
                                     impl=cfg.attn_impl)
            made = (views[0].stage, views[1].stage)
        else:
            if window is not None:
                o = ops.banded_prefill(q, k, v, window)
            else:
                o = ops.attention(q, k, v, causal=True, impl=cfg.attn_impl)
            if kv is not None:
                made = kv.write_prefill(slot, k, v)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj")(_gated(o.reshape(b, s, nh * hd), g, cfg.dtype))
        return out, made


class Layers(nn.Module):
    """The walk over `layer_types`: `layer_<i>` the attention between
    `layer_<i>_norm` and `layer_<i>_post_attn_norm`, `layer_<i>_mlp` the
    dense FFN or the experts between `layer_<i>_mlp_norm` and
    `layer_<i>_post_mlp_norm`. A layer's slot in its kind's cache is its
    rank among the layers of its kind."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, h, cache=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        kinds = cfg.layer_types
        held = None if cache is None else {SLIDING: cache.window,
                                           FULL: cache.kv}
        decode = cache is not None and h.shape[1] == 1
        staged = {SLIDING: [], FULL: []}    # a decode step's (k, v) a layer
        for i, kind in enumerate(kinds):
            out, made = GatedAttention(cfg, kind == SLIDING,
                                       name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(h),
                None if held is None else held[kind], kinds[:i].count(kind))
            if decode:
                staged[kind].append(made)
            elif made is not None:
                held[kind] = made
            h = h + norm(f"layer_{i}_post_attn_norm")(out)
            x = norm(f"layer_{i}_mlp_norm")(h)
            if i < cfg.num_dense_layers:
                out = hybrid.DenseFFN(cfg, name=f"layer_{i}_mlp")(x)
            else:
                out = hybrid.held_experts(
                    cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                    activation="silu", score_fn="sigmoid",
                    shared=cfg.moe_shared_expert_intermediate_size)(
                        x, train=False)
            h = h + norm(f"layer_{i}_post_mlp_norm")(out)
        if decode:      # the step's one write a kind, every layer's token
            for kind, pairs in staged.items():
                if pairs:
                    held[kind] = held[kind].land(
                        *(jnp.stack(side) for side in zip(*pairs)))
            self._count_attended(cache)
        if cache is not None:
            cache = cache.replace(window=held[SLIDING], kv=held[FULL])
        return h, cache

    def _count_attended(self, cache):
        """What a decode step's attention reads, in cached positions summed
        over rows and layers: every kind's (`kv_positions_attended`) and the
        window layers' share of it (`kv_positions_window`), whose rings hold
        the window's slots at most."""
        from deepspeed_tpu.ops.attention import ring_live
        cfg = self.cfg
        ring = cfg.window_layers * jnp.sum(
            ring_live(cache.index, cfg.sliding_window)[0])
        for name, count in (
                ("kv_positions_window", ring),
                ("kv_positions_attended",
                 ring + cfg.full_layers * jnp.sum(cache.index + 1))):
            self.sow("counters", name, count.astype(jnp.int32),
                     init_fn=lambda: jnp.zeros([], jnp.int32),
                     reduce_fn=lambda a, b_: a + b_)


class AfmoeForCausalLM(nn.Module):
    cfg: AfmoeConfig
    # what the layers count inside a serving program, summed over the call by
    # the engine (`serving` event)
    program_counters = hybrid.EXPERT_COUNTERS + (
        "kv_positions_window", "kv_positions_attended")

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        return hybrid.causal_lm(self, Layers, input_ids, labels, cache,
                                eps=self.cfg.rms_norm_eps,
                                prefill_tokens=PREFILL_TOKENS)

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions, by kind: the full layers' full-length rows and
        the window layers' rings of `sliding_window` slots."""
        from deepspeed_tpu.inference.kv_cache import HybridCache, KVCache
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        stacked = lambda layers, slots, ring: KVCache.create_stacked(  # noqa: E731
            layers, batch, slots, cfg.num_key_value_heads, cfg.head_dim,
            dtype=dtype, ring=ring)
        return HybridCache(
            kv=stacked(cfg.full_layers, max_len, False),
            window=stacked(cfg.window_layers, cfg.sliding_window, True))


init_params_and_specs, materialize_params, afmoe_loss_fn = \
    hybrid.entry_points(AfmoeForCausalLM)
