"""Qwen3-Next (HF `model_type: qwen3_next` config keys; Qwen3-Next-80B-A3B):
a hybrid of LINEAR attention (Gated DeltaNet, arXiv:2412.06464) and gated
softmax attention, three to one, over softmax-routed experts beside a gated
shared one. With `N(x) = x / rms(x) * (1 + w)` (every hidden-size norm and
the query / key head norms; eps `rms_norm_eps`), D `hidden_size`, a layer of
PUBLISHED index p is

    a = N_in(x);   x = x + Mixer(a);   m = N_post(x);   x = x + Experts(m)

where the mixer is FULL attention where `(p + 1) % full_attention_interval
== 0` and Gated DeltaNet (GDN) elsewhere.

**GDN**, `linear_num_key_heads` key heads serving `linear_num_value_heads`
value heads (key head j serves value heads 2j, 2j + 1 at the published 16 /
32), both of width 128:

    [q | k | v | z] = W_qkvz a        [b | al] = W_ba a            (no bias)
    [q | k | v] = silu(conv4([q | k | v]))      causal depthwise, no bias
    beta = sigmoid(b);   g = -exp(A_log) * softplus(al + dt_bias)   a value HEAD
    q = l2(q) * d^-0.5,  k = l2(k)                                a head
    S <- exp(g_t) S;   u = beta_t (v_t - S^T k_t);   S <- S + k_t u^T;   o_t = S^T q_t
    y = W_out (w_n * o / rms_head(o) * silu(z))                  a PLAIN weight

a value head's `S` a float32 matrix d_k x d_v. One token is
`ops.attention.kda_update` with a decay a head (`gdn_state_update`, in place
on the stacked state); a sequence is `hybrid.delta_chunked`, exact against
the recurrence, continued from the stored state and convolution tail.

**FULL** attention, `num_attention_heads` query heads on
`num_key_value_heads` KV heads of `head_dim` (16 on 2 of 256):

    [q | gate] = W_q a  (a head: d + d);   k = W_k a;   v = W_v a   (no bias)
    q = N_q(q), k = N_k(k)   over a head;   RoPE on the FIRST `rotary_dim`
    values of a head (`partial_rotary_factor`), halves (i, i + rotary_dim / 2)
    o_t = sum_{j <= t} softmax_j(q_t . k_j / sqrt(d)) v_j;   y = W_o (o * sigmoid(gate))

**Experts**: `p = softmax(W_r m)` in float32 over every expert the router
scores, the `num_experts_per_tok` largest, weights over their sum
(`norm_topk_prob`), plus `sigmoid(w_sg . m) * Shared(m)`; all SwiGLU
(`moe/layer.MoE` as `hybrid.held_experts` builds it, `shared_gate`). Then
`N_f`, an untied head. The multi-token-prediction layer is not built.

THE CACHE (`make_cache`): `HybridCache(kv=<the full layers' K and V, stacked>,
state=RecurrentState(<the GDN layers' states and convolution tails>))`. A
DECODE step updates each GDN layer's state in place and stages each full
layer's token, landed ONCE a step (`KVCache.land`), read by the dense decode
kernel. A pass of S > 1 over a cache is a PREFILL FROM THE EMPTY CACHE,
walked a row and a chunk at a time through all the layers
(`hybrid.prefill_walk`; the chunk DIVIDES the prompt, `hybrid.dividing_chunk`:
a recurrent layer cannot walk a position twice): a GDN layer continues from
the row's stored state, a full layer writes its chunk and attends the row so
far through the flash forward (`ops.attention.chunk_prefill`); the head at
each row's last position only.

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(the grouped expert GEMM under a scan would copy a layer's experts every
step). The chip may hold a SHARE of the model: `num_experts` of
`router_experts` from `expert_offset` on, and of the depth the layers
`published_layers` names (`perfbench/configs/qwen3-next-80b-l12-ep8.json` has
the deployment; its `assumed` lists what the catalog's config does not
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
from deepspeed_tpu.models.llama import _dense

F32 = jnp.float32
# Queries of one row that walk the layers together in a prefill: at 2,048 the
# chunked delta rule's float32 operands are 34 MB each (0.54 GB a whole
# 32,768-token row), the held experts' sorted rows 10 MB, and the weights are
# read 16 times a 32,768-token row.
PREFILL_CHUNK = 2048
GDN_CHUNK = 64      # positions a block of the chunked delta rule


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # the published indices of the layers held (None: all of them, 0 .. L-1):
    # a layer's mixer is decided by its PUBLISHED index
    published_layers: Optional[Tuple[int, ...]] = None
    # full attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    # experts: `num_experts` are HELD here, of the `router_experts` the router
    # scores (None: all of them are held), from `expert_offset` on
    num_experts: int = 512
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
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
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("qwen3_next: query heads share KV heads, and "
                             "value heads key heads, in whole groups")
        if self.rotary_dim % 2:
            raise ValueError(f"qwen3_next: a rotary width of "
                             f"{self.rotary_dim} is no whole pairs")

    # ---- the walk
    @property
    def kinds(self) -> str:
        """A layer's mixer, by its place here: `G` Gated DeltaNet, `A` full
        attention."""
        pub = self.published_layers or range(self.num_hidden_layers)
        return "".join("A" if (p + 1) % self.full_attention_interval == 0
                       else "G" for p in pub)

    @property
    def full_layers(self) -> int:
        return self.kinds.count("A")

    @property
    def gdn_layers(self) -> int:
        return self.kinds.count("G")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    # ---- GDN sizes
    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim    # q, k and v side by side

    @property
    def gdn_state_shape(self) -> tuple:
        """One sequence's state in one GDN layer: a value head's `S`."""
        return (self.linear_num_value_heads, self.linear_key_head_dim,
                self.linear_value_head_dim)

    # ---- bytes, by kind (host arithmetic: telemetry, serve-mode accounting)
    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """K and V held for `batch` sequences of up to `max_len` positions:
        the full layers' rows (a GDN layer keeps none)."""
        return {"full_kv_bytes": 2 * self.full_layers * batch * max_len
                * self.num_key_value_heads * self.head_dim
                * jnp.dtype(dtype or self.dtype).itemsize}

    def recurrent_state_bytes(self, batch: int, dtype=None) -> int:
        from deepspeed_tpu.inference.kv_cache import RecurrentState
        return RecurrentState.nbytes(
            self.gdn_layers, batch, self.gdn_state_shape,
            self.linear_conv_kernel_dim, self.conv_dim, dtype or self.dtype)


class OnePlusNorm(nn.Module):
    """`x / rms(x) * (1 + w)` over the last axis: the family's hidden-size
    norms and, one weight for every head, its query / key head norms. `w` is
    seeded normal(0.02), not zeros: a dropped `1 +` then shows."""
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.normal(0.02),
                       (x.shape[-1],), F32)
        x = x.astype(F32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + self.eps) * (1.0 + w)).astype(self.dtype)


def _sow(module, name, value):
    module.sow("counters", name, jnp.asarray(value, jnp.int32),
               init_fn=lambda: jnp.zeros([], jnp.int32),
               reduce_fn=lambda a, b_: a + b_)


class GatedDeltaNet(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, state=None, slot=None, row=None):
        """x (B, S, D). `state` None: a plain forward from a zero state. With
        the model's stacked `RecurrentState` and this layer's `slot`: S == 1
        is a decode step of every row on the stored state; S > 1 is a CHUNK
        of sequence `row` alone (B == 1), continued from that row's stored
        state and convolution tail by the chunked form. Returns (out,
        state)."""
        cfg = self.cfg
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        kw, cd = cfg.linear_conv_kernel_dim, cfg.conv_dim
        b, s, _ = x.shape
        qkv, z = jnp.split(_dense(cd + cfg.value_dim, ("embed", "heads"),
                                  cfg.dtype, "in_proj_qkvz")(x), [cd], axis=-1)
        beta, al = jnp.split(_dense(2 * nv, ("embed", None), cfg.dtype,
                                    "in_proj_ba")(x).astype(F32), 2, axis=-1)
        bound = 1.0 / math.sqrt(kw)
        conv_w = self.param(
            "conv_kernel", lambda key, sh, dt=F32: jax.random.uniform(
                key, sh, dt, -bound, bound), (kw, cd), F32).astype(F32)
        a = jnp.exp(self.param("A_log", hybrid.a_log_init, (nv,),
                               F32).astype(F32))
        dt_bias = self.param("dt_bias", hybrid.delta_dt_bias_init, (nv,), F32)
        norm_w = self.param("norm_weight", nn.initializers.ones_init(), (dv,),
                            F32)
        beta = jax.nn.sigmoid(beta)                             # (B, S, Hv)
        g = -a * jax.nn.softplus(al + dt_bias.astype(F32))      # a value head

        chunk = state is not None and s > 1
        if state is None:
            tail = jnp.zeros((b, kw - 1, cd), qkv.dtype)
        elif chunk:
            tail = jax.lax.dynamic_slice(
                state.conv, (slot, row, 0, 0), (1, 1, kw - 1, cd))[0]
        else:
            tail = state.conv[slot]
        window = jnp.concatenate([tail, qkv], axis=1)      # (B, S + K - 1, C)
        q, k, v = jnp.split(
            jax.nn.silu(hybrid.short_conv(conv_w, window, s)),
            [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
        q, k = (t.reshape(b, s, nk, dk) for t in (q, k))
        v = v.reshape(b, s, nv, dv)
        new_tail = window[:, -(kw - 1):]

        if chunk:
            # between the convolution and the gate in one call: the keys'
            # norms, the rule from the row's stored state, the heads' norm
            at = (slot, row, 0, 0, 0)
            s0 = jax.lax.dynamic_slice(
                state.ssm, at, (1, 1) + state.ssm.shape[2:])[0]
            with jax.named_scope("delta_prefill"):
                o, last = hybrid.delta_prefill(
                    q, k, v, g, beta, s0, GDN_CHUNK, norm_w,
                    cfg.rms_norm_eps)
            ssm = jax.lax.dynamic_update_slice(state.ssm, last[None], at)
            conv_state = jax.lax.dynamic_update_slice(
                state.conv, new_tail.astype(state.conv.dtype)[None],
                (slot, row, 0, 0))
            _sow(self, "delta_prefill_positions", s)
        else:
            # key head j serves value heads (nv / nk) j ..: each q / k
            # repeated
            q, k = (jnp.repeat(t, nv // nk, axis=2)
                    for t in hybrid.recurrence_keys(q, k))
            if state is not None:
                from deepspeed_tpu.ops.attention import kda_update
                o, ssm = kda_update(state.ssm, slot, q[:, 0], k[:, 0],
                                    v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
                conv_state = jax.lax.dynamic_update_index_in_dim(
                    state.conv, new_tail.astype(state.conv.dtype), slot, 0)
                _sow(self, "state_updates", b)
            else:       # the plain forward, which is differentiated
                with jax.named_scope("delta_prefill"):
                    o, _ = hybrid.delta_chunked(
                        q, k, v, g, beta, jnp.zeros((b, nv, dk, dv), F32),
                        GDN_CHUNK)
            # the norm over each value head's d_v (a PLAIN weight)
            o = hybrid.head_norm(o, norm_w, cfg.rms_norm_eps)
        if state is not None:
            state = state.replace(ssm=ssm, conv=conv_state)
        # the gate
        o = o.reshape(b, s, cfg.value_dim) * jax.nn.silu(z.astype(F32))
        return _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                      "out_proj")(o.astype(cfg.dtype)), state


class GatedAttention(nn.Module):
    """Grouped-query causal attention with `1 + w` head norms, rotary over
    the first `rotary_dim` values of a head and a sigmoid gate on the
    output, a gate a query head projected with the query."""
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, kv=None, slot=None, row=None):
        """x (B, S, hidden). `kv` None: a plain causal pass over the tokens
        themselves. With the full layers' `KVCache` and this layer's `slot`:
        S == 1 is a decode step, its token STAGED, returning (out, the
        staged (k, v) (B, Hkv, D)); S > 1 is a CHUNK of sequence `row` alone
        (B == 1) from that row's cursor on, written into the stacks and
        attended against the row so far, returning (out, the cache)."""
        from deepspeed_tpu.inference.kv_cache import decode_mask
        from deepspeed_tpu.ops import attention as ops
        cfg = self.cfg
        hd, nh, nkv = (cfg.head_dim, cfg.num_attention_heads,
                       cfg.num_key_value_heads)
        b, s, _ = x.shape
        q, gate = jnp.split(
            _dense(2 * nh * hd, ("embed", "heads"), cfg.dtype,
                   "q_proj")(x).reshape(b, s, nh, 2 * hd), 2, axis=-1)
        k, v = (_dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype,
                       name)(x).reshape(b, s, nkv, hd)
                for name in ("k_proj", "v_proj"))
        q = OnePlusNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = OnePlusNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        if kv is None:
            start = jnp.zeros((b,), jnp.int32)
        elif s == 1:
            start = kv.index
        else:
            start = jax.lax.dynamic_slice(kv.index, (row,), (1,))
        rd = cfg.rotary_dim
        cos, sin = ops.rope_cos_sin(start[:, None] + jnp.arange(s)[None, :],
                                    rd, cfg.rope_theta, F32)
        q, k = (jnp.concatenate(
            [ops.apply_rotary_emb(t[..., :rd], cos, sin), t[..., rd:]],
            axis=-1) for t in (q, k))

        made = None
        if kv is None:
            o = ops.attention(q, k, v, causal=True, impl=cfg.attn_impl)
        elif s == 1:
            views = tuple(c.replace(stage=new[:, 0].astype(c.stack.dtype))
                          for c, new in zip(kv.layer_views(slot, staged=True),
                                            (k, v)))
            o = ops.cached_attention(q, *views, kv.index,
                                     decode_mask(kv.index[:, None],
                                                 kv.max_len),
                                     impl=cfg.attn_impl)
            made = (views[0].stage, views[1].stage)
        else:
            made = kv.write_prefill(slot, k, v, row=row, start=start[0])
            o = ops.chunk_prefill(q[0], *made.layer_views(slot, staged=False),
                                  row, start[0])[None]
        o = (o.astype(F32).reshape(b, s, nh * hd) * jax.nn.sigmoid(
            gate.astype(F32).reshape(b, s, nh * hd))).astype(cfg.dtype)
        return _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                      "o_proj")(o), made


class Layers(nn.Module):
    """The walk over the layers: one loop over `cfg.kinds`, each layer's
    mixer (`layer_<i>`) built by its kind and handed its slot of its kind's
    stacked buffer, then its experts (`layer_<i>_mlp`)."""
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, h, cache=None, row=None):
        cfg = self.cfg
        state = None if cache is None else cache.state
        kv = None if cache is None else cache.kv
        norm = lambda name: OnePlusNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        staged = []     # a decode step's new (k, v) of each full layer
        for i, kind in enumerate(cfg.kinds):
            slot = cfg.kinds[:i].count(kind)
            x = norm(f"layer_{i}_norm")(h)
            if kind == "G":
                out, state = GatedDeltaNet(cfg, name=f"layer_{i}")(
                    x, state, slot, row)
            else:
                out, made = GatedAttention(cfg, name=f"layer_{i}")(
                    x, kv, slot, row)
                if isinstance(made, tuple):
                    staged.append(made)
                elif made is not None:
                    kv = made
            h = h + out
            h = h + hybrid.held_experts(
                cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                activation="silu", score_fn="softmax",
                shared=cfg.shared_expert_intermediate_size,
                shared_gate=True)(norm(f"layer_{i}_mlp_norm")(h), train=False)
        if staged:      # the step's one write, every full layer's token
            kv = kv.land(*(jnp.stack(side) for side in zip(*staged)))
            # what the step's attention reads, in cached positions summed
            # over rows and full layers (the staged token among them)
            _sow(self, "kv_positions_attended",
                 cfg.full_layers * jnp.sum(cache.index + 1))
        if cache is not None:
            cache = cache.replace(state=state, kv=kv)
        return h, cache


class Qwen3NextForCausalLM(nn.Module):
    cfg: Qwen3NextConfig
    # what the layers count inside a serving program, summed over the call by
    # the engine (`serving` event)
    program_counters = hybrid.EXPERT_COUNTERS + (
        "delta_prefill_positions", "state_updates", "kv_positions_attended")

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        return hybrid.causal_lm(
            self, Layers, input_ids, labels, cache,
            eps=self.cfg.rms_norm_eps, norm=OnePlusNorm,
            prefill_chunk=hybrid.dividing_chunk(input_ids.shape[1],
                                                PREFILL_CHUNK))

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions, by kind: the full layers' K and V in the
        stacked view, the GDN layers' matrix states and convolution tails."""
        from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                                      RecurrentState)
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        return HybridCache(
            kv=KVCache.create_stacked(
                cfg.full_layers, batch, max_len, cfg.num_key_value_heads,
                cfg.head_dim, dtype=dtype),
            state=RecurrentState.create(
                cfg.gdn_layers, batch, cfg.gdn_state_shape,
                cfg.linear_conv_kernel_dim, cfg.conv_dim, dtype=dtype))


init_params_and_specs, materialize_params, qwen3_next_loss_fn = \
    hybrid.entry_points(Qwen3NextForCausalLM)
