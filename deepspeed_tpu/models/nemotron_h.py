"""Nemotron-H / Nemotron-3 hybrid decoder (HF `model_type: nemotron_h`).

A string of single-mixer layers, `h = h + mixer(RMSNorm(h))`, whose kinds are
read from `hybrid_override_pattern`: `M` a Mamba-2 (SSD) mixer, `*` grouped-
query attention, `E` sigmoid-routed relu² experts beside a shared one. The
walk over the layers is configuration, not code: one loop over the pattern
string (`Layers`) builds each layer's mixer by its kind, `layers/layer_<i>`
with its norm `layers/layer_<i>_norm`. The layers are NOT stacked and scanned: a scan slices each
layer's weights out of the stack, and the grouped expert GEMM, a Pallas call
the slice cannot fuse into, would then copy a layer's 1.3 GB of experts
every decode step (read on the compiled program, PERF.md PR 41).

Each kind keeps another thing between tokens, so the model builds its own
cache (`make_cache`; `inference/kv_cache.HybridCache`): K and V for the
attention layers only, a float32 state and a convolution tail for the Mamba
layers, nothing for the experts. Each buffer is stacked over the layers of
its kind and a layer addresses its own slab by index, so a decode step's
state update is one read and one write in place
(`ops/pallas/ssm.ssm_state_update`).

Departures from the published modelling code (`modeling_nemotron_h.py`):
none in the mathematics. `attention_rotary` is False as published (the
attention layers apply no rotary embedding, the Mamba layers carry position)
and exists as a switch because the config carries `rope_theta`;
`time_step_limit` is unbounded; the expert layer may hold a contiguous share
of the routed experts (`n_routed_experts` of `router_experts`, from
`expert_offset`), in which case what the absent experts would add is left
out (`moe/layer.MoE.held_experts`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.llama import RMSNorm, _dense

F32 = jnp.float32
KINDS = "ME*"
# Tokens of a prefill that walk the layers together. A larger batch goes a
# few rows at a time (`hybrid.row_groups`): the SSD's decay matrices (rows x blocks x
# heads x 128 x 128 float32), attention's logits over the whole cache and the
# experts' sorted rows are gigabytes at 64 rows x 512 tokens, beside 9 GB of
# weights; the weights read once more a group cost a few ms each.
PREFILL_TOKENS = 2048


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0
    attention_rotary: bool = False
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts: `n_routed_experts` are HELD here, of the `router_experts` the
    # router scores (None: all of them are held), from `expert_offset` on
    n_routed_experts: int = 128
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    # seeded router: the scale of `e_score_correction_bias` drawn at init (a
    # trained model's is not zero, and a zero one would hide a dropped term;
    # a large one skews the load: at 0.1, 39 of 64 held experts see a token
    # in a decode step of 64 rows, at 0.01 60.7, a uniform router 61.0)
    router_bias_scale: float = 0.01
    norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    dispatch_impl: str = "auto"

    def __post_init__(self):
        pat = self.hybrid_override_pattern
        if len(pat) != self.num_hidden_layers or set(pat) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {pat!r}: {self.num_hidden_layers} "
                f"layers of kinds {KINDS!r} (M Mamba-2, E experts, * attention)")

    def count(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep K and V (what `_cache_dims` counts)."""
        return self.count("*")

    # ---- Mamba-2 sizes
    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def ssm_state_shape(self) -> tuple:
        """One sequence's state in one Mamba-2 layer: a head's is P x N."""
        return (self.mamba_num_heads, self.mamba_head_dim,
                self.ssm_state_size)

    def recurrent_state_bytes(self, batch: int, dtype=None) -> int:
        from deepspeed_tpu.inference.kv_cache import RecurrentState
        return RecurrentState.nbytes(
            self.count("M"), batch, self.ssm_state_shape, self.conv_kernel,
            self.conv_dim, dtype or self.dtype)


# ------------------------------------------------------------------ Mamba-2


def _segsum_decay(a_cum):
    """a_cum (..., Q) cumulative log-decay inside a block -> (..., Q, Q):
    exp(a_cum[i] - a_cum[j]) for i >= j, else 0."""
    q = a_cum.shape[-1]
    diff = a_cum[..., :, None] - a_cum[..., None, :]
    keep = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)


def ssd_chunked(x, dt, a, b, c, h0, chunk: int):
    """The Mamba-2 recurrence over a sequence as the chunked scan (SSD): a
    quadratic part inside each block of `chunk` positions, the state carried
    between blocks. x (B, S, H, P), dt (B, S, H) after the softplus, a (H,),
    b and c (B, S, G, N), h0 (B, H, P, N): float32, einsums at `highest`
    (the state-space part is under 3% of the layer's operations). Returns
    (y (B, S, H, P) without the `D x` term, the state after position S-1).
    Any S: the tail of the last block is padded with dt = 0, which leaves the
    state as it is."""
    bsz, s, nh, p = x.shape
    g, n = b.shape[2:]
    hb = nh // g
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    # (B, nc, Q, G, hb, ...): heads by their group
    x = x.reshape(bsz, nc, chunk, g, hb, p)
    dt = dt.reshape(bsz, nc, chunk, g, hb)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    la = dt * a.reshape(g, hb)                         # log-decay a position
    a_cum = jnp.cumsum(la, axis=2)                     # (B, nc, Q, G, hb)
    dtx = dt[..., None] * x
    with jax.default_matmul_precision("highest"):
        # inside a block: y_i += sum_{j<=i} (C_i . B_j) decay(i, j) dt_j x_j
        cb = jnp.einsum("bcign,bcjgn->bcgij", c, b)
        decay = _segsum_decay(jnp.moveaxis(a_cum, 2, -1))     # (B,nc,G,hb,Q,Q)
        y = jnp.einsum("bcgij,bcghij,bcjghp->bcighp", cb, decay, dtx)
        # what each block adds to the state by its end
        to_end = jnp.exp(a_cum[:, :, -1:] - a_cum)            # (B,nc,Q,G,hb)
        adds = jnp.einsum("bcjgh,bcjghp,bcjgn->bcghpn", to_end, dtx, b)
        whole = jnp.exp(a_cum[:, :, -1])                      # (B, nc, G, hb)

        def carry_state(h, blk):
            add, dec = blk
            return dec[..., None, None] * h + add, h          # h ENTERING it

        h_last, h_in = jax.lax.scan(
            carry_state, h0.reshape(bsz, g, hb, p, n),
            (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(whole, 1, 0)))
        h_in = jnp.moveaxis(h_in, 0, 1)                       # (B,nc,G,hb,P,N)
        # the carried state read at each position of the block
        y = y + jnp.einsum("bcign,bcghpn,bcigh->bcighp", c, h_in,
                           jnp.exp(a_cum))
    y = y.reshape(bsz, nc * chunk, nh, p)[:, :s]
    return y, h_last.reshape(bsz, nh, p, n)


class MambaMixer(nn.Module):
    """Mamba-2: `[z, xBC, dt] = in_proj(u)`; a causal depthwise convolution
    and silu over `xBC`; the recurrence; `y * silu(z)` THEN an RMS norm over
    each of the `n_groups` groups; `out_proj`."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u, state=None, slot=None):
        """u (B, S, D). `state`: None (a plain forward from a zero state), or
        the model's stacked `RecurrentState` with this layer's `slot` in it:
        S == 1 is a decode step on the stored state, S > 1 continues from it
        by the chunked scan. Returns (out, state)."""
        cfg = self.cfg
        nh, p, n, g = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size, cfg.n_groups)
        di, cd, kw = cfg.d_inner, cfg.conv_dim, cfg.conv_kernel
        bsz, s, _ = u.shape
        zxbcdt = _dense(2 * di + 2 * g * n + nh, ("embed", "mlp"), cfg.dtype,
                        "in_proj", cfg.mamba_proj_bias)(u)
        bound = 1.0 / math.sqrt(kw)
        conv_w = self.param(
            "conv_kernel", lambda k, sh, dt=F32: jax.random.uniform(
                k, sh, dt, -bound, bound), (kw, cd), F32).astype(F32)
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(), (cd,),
                            F32).astype(F32) if cfg.use_conv_bias else 0.0
        dt_bias = self.param("dt_bias", hybrid.dt_bias_init(cfg), (nh,), F32)
        a = -jnp.exp(self.param("A_log", hybrid.a_log_init, (nh,),
                                F32).astype(F32))
        d_skip = self.param("D", nn.initializers.ones_init(), (nh,), F32)
        norm_w = self.param("norm_weight", nn.initializers.ones_init(), (di,),
                            F32)

        z, xbc, dt = jnp.split(zxbcdt, [di, di + cd], axis=-1)
        dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))  # (B,S,H)
        tail = (jnp.zeros((bsz, kw - 1, cd), xbc.dtype) if state is None
                else state.conv[slot])
        window = jnp.concatenate([tail, xbc], axis=1)       # (B, S + K - 1, C)

        if state is not None and s == 1:
            from deepspeed_tpu.ops.pallas.ssm import ssm_state_update
            conv = jnp.einsum("kc,bkc->bc", conv_w, window.astype(F32)) + conv_b
            xs, bm, cm = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
            y, ssm = ssm_state_update(
                state.ssm, slot, xs.reshape(bsz, nh, p), dt[:, 0], a,
                bm.reshape(bsz, g, n), cm.reshape(bsz, g, n), d_skip)
            y = y[:, None]
        else:
            w32 = window.astype(F32)
            conv = sum(conv_w[j] * w32[:, j:j + s] for j in range(kw)) + conv_b
            xs, bm, cm = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
            xs = xs.reshape(bsz, s, nh, p)
            h0 = (jnp.zeros((bsz, nh, p, n), F32) if state is None
                  else state.ssm[slot])
            y, h = ssd_chunked(xs, dt, a, bm.reshape(bsz, s, g, n),
                               cm.reshape(bsz, s, g, n), h0, cfg.chunk_size)
            y = y + d_skip.astype(F32)[:, None] * xs
            ssm = None if state is None else \
                jax.lax.dynamic_update_index_in_dim(state.ssm, h, slot, 0)
        if state is not None:
            state = state.replace(
                ssm=ssm, conv=jax.lax.dynamic_update_index_in_dim(
                    state.conv, window[:, -(kw - 1):].astype(state.conv.dtype),
                    slot, 0))
        # gate first, then the norm over each group of d_inner / n_groups
        y = y.reshape(bsz, s, di) * jax.nn.silu(z.astype(F32))
        yg = y.reshape(bsz, s, g, di // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + cfg.norm_eps)
        y = (yg.reshape(bsz, s, di) * norm_w.astype(F32)).astype(cfg.dtype)
        return _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                      "out_proj", cfg.mamba_proj_bias)(y), state


# ---------------------------------------------------------------- attention


class Attention(nn.Module):
    """Grouped-query causal attention, no bias, scale head_dim^-0.5, and as
    published no rotary embedding (`attention_rotary`)."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h, kv=None, slot=None):
        cfg = self.cfg
        hd, nh, nkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads
        b, s, _ = h.shape
        q = _dense(nh * hd, ("embed", "heads"), cfg.dtype, "q_proj")(h)
        k = _dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "k_proj")(h)
        v = _dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "v_proj")(h)
        q, k, v = (q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd),
                   v.reshape(b, s, nkv, hd))
        index = None if kv is None else kv.index
        if cfg.attention_rotary:
            from deepspeed_tpu.ops.attention import (apply_rotary_emb,
                                                     rope_cos_sin)
            pos = jnp.arange(s) if kv is None else \
                index[:, None] + jnp.arange(s)[None, :]
            cos, sin = rope_cos_sin(pos, hd, cfg.rope_theta, cfg.dtype)
            q, k = apply_rotary_emb(q, cos, sin), apply_rotary_emb(k, cos, sin)
        views = None
        if kv is None:
            from deepspeed_tpu.ops.attention import attention
            ctx = attention(q, k, v, causal=True, impl=cfg.attn_impl)
        else:
            from deepspeed_tpu.inference.kv_cache import (decode_mask,
                                                          update_layer)
            from deepspeed_tpu.ops.attention import cached_attention
            # this layer's views of the stacked cache, by its slot: a
            # single token is staged (`Layers` lands the step's), a prefill
            # writes its rows' slots into the stack
            views = update_layer(*kv.layer_views(slot, staged=s == 1), k, v,
                                 index)
            pos = index[:, None] + jnp.arange(s)[None, :]
            ctx = cached_attention(q, *views, index,
                                   decode_mask(pos, kv.max_len),
                                   impl=cfg.attn_impl)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj")(ctx.reshape(b, s, nh * hd))
        return out, views


# ------------------------------------------------------------------- layers


class Layers(nn.Module):
    """The walk over the layers: one loop over the pattern string, each
    layer's mixer built by its kind (`layer_<i>`, its norm `layer_<i>_norm`)
    and handed its slab of its kind's stacked buffer."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h, cache=None):
        cfg = self.cfg
        state = None if cache is None else cache.state
        kv = None if cache is None else cache.kv
        pattern = cfg.hybrid_override_pattern
        staged = []  # a decode step's new (K, V) of each attention layer
        for i, kind in enumerate(pattern):
            slot = pattern[:i].count(kind)
            x = RMSNorm(cfg.norm_eps, cfg.dtype, name=f"layer_{i}_norm")(h)
            if kind == "M":
                out, state = MambaMixer(cfg, name=f"layer_{i}")(x, state, slot)
            elif kind == "*":
                out, views = Attention(cfg, name=f"layer_{i}")(x, kv, slot)
                if views is not None:
                    k_l, v_l = views
                    if k_l.stage is not None:
                        staged.append((k_l.stage, v_l.stage))
                    else:  # written: the stacks go on
                        kv = kv.replace(k=k_l.replace(layer=None),
                                        v=v_l.replace(layer=None))
            else:
                # sigmoid scores, the selection bias in the choice only,
                # relu² experts beside a shared one
                out = hybrid.held_experts(
                    cfg, f"layer_{i}", held=cfg.n_routed_experts,
                    activation="relu2", score_fn="sigmoid",
                    shared=cfg.moe_shared_expert_intermediate_size)(
                        x, train=False)
            h = h + out
        if staged:  # the step's one write, every attention layer's token
            kv = kv.land(*(jnp.stack(side) for side in zip(*staged)))
        if cache is not None:
            cache = cache.replace(state=state, kv=kv)
        return h, cache


class NemotronHForCausalLM(nn.Module):
    cfg: NemotronHConfig
    # what the expert layers count inside a serving program, summed over the
    # call by the engine (`serving` event: `assignments`, `held_assignments`,
    # `held_wide_calls`)
    program_counters = hybrid.EXPERT_COUNTERS

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        """`hybrid.causal_lm` but for two things this family's program
        depends on: the whole batch is embedded BEFORE the walk, and EVERY
        prefill position goes on to the head."""
        cfg = self.cfg
        h = hybrid.embedded(cfg, hybrid.embed_tokens(self), input_ids)
        b, s = input_ids.shape
        rows = hybrid.rows_a_group(b, s, PREFILL_TOKENS)
        if cache is not None and s > 1 and rows < b:
            cache, (h,) = hybrid.row_groups(Layers, cfg, cache, None, h, rows,
                                            every=True)
        else:
            h, cache = Layers(cfg, name="layers")(h, cache)
        logits = hybrid.lm_logits(self, h, cfg.norm_eps)
        return hybrid.lm_output(
            logits, input_ids, labels,
            None if cache is None else cache.advance(s))

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions (what the engine asks a model that keeps more
        than K and V): K and V of the attention layers only, the recurrent
        layers' state beside them."""
        from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                                      RecurrentState)
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        return HybridCache(
            kv=KVCache.create_stacked(cfg.count("*"), batch, max_len,
                                      cfg.num_key_value_heads, cfg.head_dim,
                                      dtype=dtype),
            state=RecurrentState.create(
                cfg.count("M"), batch, cfg.ssm_state_shape, cfg.conv_kernel,
                cfg.conv_dim, dtype=dtype))


init_params_and_specs, materialize_params, nemotron_h_loss_fn = \
    hybrid.entry_points(NemotronHForCausalLM)
