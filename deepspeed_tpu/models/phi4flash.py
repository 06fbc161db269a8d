"""Phi-4-mini-flash-reasoning (HF `model_type: phi4flash`; SambaY, arXiv:2507.06607):
a decoder-hybrid-decoder. Every layer is `h += Mixer(LN(h)); h += FFN(LN'(h))`,
LayerNorm with bias, a gated FFN, and no positional embedding anywhere: the
recurrent layers carry position. With L layers (32) and `half = L / 2` the
mixers are, by layer index i:

- i even, i <= half: **Mamba-1** (`Mamba1Mixer`); layer `half` also
  publishes its scan output `m` (before the gate, with the `D` skip);
- i odd, i < half: **differential attention** under a window of
  `sliding_window` (512) positions, a query at t sees keys t-511..t;
- i = half + 1: differential attention, full and causal: the ONE layer
  whose K and V the whole second half reads;
- i even, i > half: a **gated memory unit**, `(silu(u W_in) * m) W_out`;
- i odd, i > half + 1: **cross** differential attention: a query and an
  output projection only, over layer `half + 1`'s K and V.

The walk is two scans and a pair between them: `front` over the `half / 2`
pairs [Mamba, window attention], `mid` the pair [Mamba that publishes `m`,
full attention], `back` over the pairs [memory unit, cross attention]; so the
parameters of `front` and `back` are stacked over their pairs, and a decode
step is traced once a kind.

Each kind keeps another thing between tokens (`make_cache`;
`inference/kv_cache.HybridCache`): the window layers a RING of 512 slots a
sequence (slot = position mod 512: without a positional embedding a key's
place says nothing), layer `half + 1` one full-length slab that the cross
layers read and never write, the Mamba layers a float32 state `N x C` and a
convolution tail, the memory units nothing. K and V lie as the heads pair
up, `[k1 | k2]` and `[v1 | v2]`: `(L, B, G, M, 2 head_dim)`, 128 lanes.

A pass of more than one token over a cache is a PREFILL FROM THE EMPTY CACHE
(the v1 `generate` program's only use): every position walks `front` and
`mid`, a few sequences at a time, and only each sequence's LAST position
walks `back` and the head, because those layers write nothing a later token
reads. That is exact, and it returns logits `(B, 1, vocab)`.

Departures from the published modelling code (`modeling_phi4flash.py`): none
in the mathematics, as far as the catalog's config and the paper settle it
(`perfbench/configs/phi4-mini-flash.json` lists under `assumed` what they do
not). `A_log` is stored `(d_state, d_inner)`, the state's own order; the
published `(d_inner, d_state)` is its transpose.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.llama import _dense

F32 = jnp.float32
LAMBDA_STD = 0.1    # the four learned lambda vectors of a layer are N(0, .)
# Tokens of a prefill that walk `front` and `mid` together
# (`hybrid.row_groups`, which embeds a group's tokens inside its body):
# at 8 x 2048 the program's temporaries are 4.6 GB beside 7.7 GB of weights
# and 2.5 GB of cache (the widest, the FFN's 2 x 10240 and a window block's
# logits, 0.67 GB each), and a step of the Mamba scan, which is mostly the
# loop's own overhead, serves 8 sequences' states (2.6 MB) where 4 rows
# would take as long a step and twice as many.
PREFILL_TOKENS = 16384
_SCAN = dict(variable_axes={"params": 0}, split_rngs={"params": True},
             metadata_params={nn.meta.PARTITION_NAME: "layers"})


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    # sizes config.json does not give: the published modelling code's defaults
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # ceil(hidden_size / 16)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.mb_per_layer != 2 or n % 4 or n < 8:
            raise ValueError(
                f"phi4flash: {n} layers, mb_per_layer {self.mb_per_layer}: "
                "the walk is pairs [Mamba, attention] over the first half and "
                "[memory unit, cross attention] over the second (a multiple "
                "of 4 layers, at least 8, mb_per_layer 2)")
        if not self.tie_word_embeddings:
            raise ValueError("phi4flash: the output head is the embedding")
        if self.num_attention_heads % (2 * self.pair_groups):
            raise ValueError("phi4flash: heads pair up, and pairs of query "
                             "heads share a pair of KV heads")

    # ---- the walk
    @property
    def half(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def front_pairs(self) -> int:
        return self.half // 2

    @property
    def back_pairs(self) -> int:
        return (self.num_hidden_layers - self.half - 2) // 2

    @property
    def num_mamba_layers(self) -> int:
        return self.front_pairs + 1

    # ---- attention
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def pair_width(self) -> int:
        """A pair of heads side by side: the cache's last dimension."""
        return 2 * self.head_dim

    @property
    def pair_groups(self) -> int:
        """Pairs of KV heads: the cache's head dimension."""
        return self.num_key_value_heads // 2

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep K and V of their own."""
        return self.front_pairs + 1

    # ---- Mamba-1
    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def ssm_state_shape(self) -> tuple:
        """One sequence's state in one Mamba-1 layer, channels on the lanes."""
        return (self.mamba_d_state, self.d_inner)

    # ---- bytes, by kind (host arithmetic: telemetry, serve-mode accounting)
    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """K and V held for `batch` sequences of up to `max_len` positions:
        the window layers' rings and the one shared slab."""
        slot = 2 * batch * self.pair_groups * self.pair_width \
            * jnp.dtype(dtype or self.dtype).itemsize
        return {"window_kv_bytes": self.front_pairs * self.sliding_window * slot,
                "shared_kv_bytes": max_len * slot}

    def recurrent_state_bytes(self, batch: int, dtype=None) -> int:
        from deepspeed_tpu.inference.kv_cache import RecurrentState
        return RecurrentState.nbytes(
            self.num_mamba_layers, batch, self.ssm_state_shape,
            self.mamba_d_conv, self.d_inner, dtype or self.dtype)


def lambda_init(depth):
    """Differential attention's `lambda_init` of layer `depth` (traced or
    not): 0.8 - 0.6 exp(-0.3 depth)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, F32))


class LayerNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w = self.param("weight", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)), (d,), F32)
        b = self.param("bias", nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("embed",)), (d,), F32)
        x = x.astype(F32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps) * w + b).astype(self.dtype)


class FFN(nn.Module):
    """`[g, u] = x W_fc1; (u * silu(g)) W_fc2`: the first half is the gate."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        g, u = jnp.split(_dense(2 * cfg.intermediate_size, ("embed", "mlp"),
                                cfg.dtype, "fc1")(x), 2, axis=-1)
        return _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                      "fc2")(u * jax.nn.silu(g))


# ------------------------------------------------------------------ Mamba-1


def selective_scan(x, dt, a, b, c, h0):
    """The Mamba-1 recurrence over a sequence, a plain scan over POSITIONS
    with every sequence's state in one step (there is no chunked form: the
    decay differs by (channel, state) element). TIME-MAJOR, as a scan slices
    it: x, dt (S, B, C), dt after the softplus; a (N, C), negative; b, c
    (S, B, N); h0 (B, N, C): float32. Returns (y (S, B, C) without the
    `D x` term, the state after S - 1)."""
    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t[:, None, :] * a) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    # four positions a trip: a step is a handful of small fusions and the
    # loop's own overhead is most of it (PERF.md, PR 45: 26.4 ms a layer for
    # 8 x 2048 positions, 10.7 unrolled by 4, 10.6 by 16)
    h, y = jax.lax.scan(step, h0, (x, dt, b, c), unroll=4)
    return y, h


def _a_log_init(key, shape, dtype=F32):
    """`A = -(1 .. N)` for every channel (S4D-real), as `log`: (N, C)."""
    del key
    n = jnp.arange(1, shape[0] + 1, dtype=F32)[:, None]
    return jnp.log(jnp.broadcast_to(n, shape)).astype(dtype)


class Mamba1Mixer(nn.Module):
    """Mamba-1: `[x, z] = in_proj(u)`; a causal depthwise convolution and
    silu over `x`; `[r, B, C] = x_proj(x)`, `dt = softplus(dt_proj(r))` per
    channel; the recurrence; `out_proj(y * silu(z))`."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, state=None, slot=None):
        """u (B, S, D). `state`: None (a plain forward from a zero state), or
        `(ssm, conv)`, the model's stacked buffers with this layer's `slot`
        in them: S == 1 is a decode step on the stored state, S > 1 goes on
        from it. Returns (out, y, state): `y` (B, S, C) float32 is the scan's
        output with the `D` skip, before the gate."""
        cfg = self.cfg
        di, n, kw, rank = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                           cfg.dt_rank)
        bsz, s, _ = u.shape
        # time-major from here to the output projection: the convolution and
        # the scan slice positions, and a prefill's (B, S, C) float32 arrays
        # transposed for the scan were a gigabyte at 8 x 2048 tokens
        u = jnp.swapaxes(u, 0, 1)                           # (S, B, D)
        x, z = jnp.split(_dense(2 * di, ("embed", "mlp"), cfg.dtype,
                                "in_proj")(u), 2, axis=-1)
        bound = 1.0 / math.sqrt(kw)
        conv_w = self.param(
            "conv_kernel", lambda k, sh, dt=F32: jax.random.uniform(
                k, sh, dt, -bound, bound), (kw, di), F32).astype(F32)
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(), (di,),
                            F32).astype(F32)
        a = -jnp.exp(self.param("A_log", _a_log_init, (n, di), F32).astype(F32))
        d_skip = self.param("D", nn.initializers.ones_init(), (di,), F32)

        ssm, tails = (None, None) if state is None else state
        tail = jnp.zeros((kw - 1, bsz, di), x.dtype) if tails is None \
            else jnp.swapaxes(tails[slot], 0, 1)
        window = jnp.concatenate([tail, x], axis=0)         # (S + K - 1, B, C)
        w32 = window.astype(F32)
        x = jax.nn.silu(sum(conv_w[j] * w32[j:j + s] for j in range(kw))
                        + conv_b)                           # (S, B, C) float32
        r, bm, cm = jnp.split(
            _dense(rank + 2 * n, ("mlp_in", None), cfg.dtype, "x_proj")(
                x.astype(cfg.dtype)), [rank, rank + n], axis=-1)
        dt = jax.nn.softplus(nn.Dense(
            di, dtype=cfg.dtype, param_dtype=F32, name="dt_proj",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(rank ** -0.5), (None, "mlp")),
            bias_init=hybrid.dt_bias_init(cfg))(r).astype(F32))   # (S, B, C)

        if ssm is not None and s == 1:
            from deepspeed_tpu.ops.pallas.ssm import ssm_state_update_m1
            y, ssm = ssm_state_update_m1(ssm, slot, x[0], dt[0], a, bm[0],
                                         cm[0], d_skip)
            y = y[None]
        else:
            h0 = jnp.zeros((bsz, n, di), F32) if ssm is None else ssm[slot]
            y, h = selective_scan(x, dt, a, bm.astype(F32), cm.astype(F32), h0)
            y = y + d_skip.astype(F32) * x
            if ssm is not None:
                ssm = jax.lax.dynamic_update_index_in_dim(ssm, h, slot, 0)
        if tails is not None:
            tails = jax.lax.dynamic_update_index_in_dim(
                tails, jnp.swapaxes(window[-(kw - 1):], 0, 1).astype(
                    tails.dtype), slot, 0)
        out = _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                     "out_proj")((y * jax.nn.silu(z.astype(F32))
                                  ).astype(cfg.dtype))
        return jnp.swapaxes(out, 0, 1), jnp.swapaxes(y, 0, 1), \
            None if state is None else (ssm, tails)


class MemoryUnit(nn.Module):
    """The gated memory unit: `(silu(u W_in) * m) W_out`, `m` the memory the
    last Mamba layer published at the same position."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, m):
        cfg = self.cfg
        gate = _dense(cfg.d_inner, ("embed", "mlp"), cfg.dtype, "in_proj")(u)
        return _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                      "out_proj")((jax.nn.silu(gate.astype(F32)) * m
                                   ).astype(cfg.dtype))


# ---------------------------------------------------- differential attention


class DiffAttention(nn.Module):
    """Differential attention over pairs of heads. Heads 2p and 2p + 1 are
    pair p's `(q1, q2)`, KV heads 2g and 2g + 1 group g's `(k1, k2)` and
    `[v1 | v2]`, pair p reads group `p // (pairs / groups)`:

        a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2],  a2 likewise of q2, k2
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(depth)
        o_p = RMSNorm_2d(a1 - lam a2) w (1 - lambda_init(depth))

    `cross`: the layer has a query and an output projection only and reads
    what `shared` holds. `window`: a query sees that many positions, itself
    the last."""
    cfg: Phi4FlashConfig
    cross: bool = False
    window: Optional[int] = None

    @nn.compact
    def __call__(self, u, depth, views=None, shared=None, lengths=None,
                 slots=None):
        """u (B, S, D); `depth`, the layer's index (may be traced).

        Without a cache (`views` and `lengths` None) a plain causal pass over
        the S positions: of its own K and V, or for a cross layer of `shared`,
        the arrays `(k, v)` (B, S, G, W) of the layer it reads. With one,
        `views` are this layer's `DenseLayer` views of the stack it WRITES
        (its ring, or the slab): S > 1 attends the new tokens alone (the
        cache was empty) and writes them, S == 1 stages its token, which
        stands in slot `slots[b]` of the `lengths[b]` valid ones. A cross
        layer's `shared` are then views of the slab it reads, one query a
        sequence over `lengths[b]` slots.

        Returns (out, what it made: the views, or its fresh `(k, v)`)."""
        cfg = self.cfg
        hd, nh, g, w = (cfg.head_dim, cfg.num_attention_heads,
                        cfg.pair_groups, cfg.pair_width)
        r = nh // 2 // g                      # pairs of query heads a group
        b, s, _ = u.shape
        cols = nh * hd if self.cross else (nh + 2 * cfg.num_key_value_heads) * hd
        qkv = _dense(cols, ("embed", "heads"), cfg.dtype, "Wqkv", True)(u)
        vec = lambda name: self.param(  # noqa: E731
            name, nn.initializers.normal(LAMBDA_STD), (hd,), F32)
        li = lambda_init(depth)
        lam = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1"))) \
            - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + li
        subln = self.param("subln_weight", nn.initializers.ones_init(), (w,),
                           F32)

        # a group's rows: its r pairs' [q1 | 0], then their [0 | q2]
        q = qkv[..., :nh * hd].reshape(b, s, g, r, 2, hd)
        zero = jnp.zeros_like(q[..., 0, :])
        q = jnp.concatenate(
            [jnp.concatenate([q[..., 0, :], zero], axis=-1),
             jnp.concatenate([zero, q[..., 1, :]], axis=-1)], axis=3)
        made = None
        if not self.cross:
            k, v = (t.reshape(b, s, g, w) for t in
                    jnp.split(qkv[..., nh * hd:], 2, axis=-1))
            made = (k, v)
        scale = hd ** -0.5

        if s > 1 or lengths is None:
            # a plain pass over the new positions alone
            from deepspeed_tpu.ops.attention import (attention,
                                                     banded_attention)
            k, v = made if not self.cross else shared
            q4 = q.reshape(b, s, g * 2 * r, w)
            if self.window is not None and s > self.window:
                a = banded_attention(q4, k, v, self.window, scale)
            else:
                a = attention(q4, k, v, causal=True, softmax_scale=scale,
                              impl=cfg.attn_impl)
            a = a.reshape(b, s, g, 2 * r, w).astype(F32)
            diff = a[:, :, :, :r] - lam * a[:, :, :, r:]
            o = diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1,
                                              keepdims=True)
                                     + cfg.layer_norm_eps)
            if views is not None:   # a prefill: the empty cache takes them
                from deepspeed_tpu.inference.kv_cache import (
                    write_prefill_rows)
                ring = self.window is not None
                made = tuple(c.replace(stack=write_prefill_rows(
                    c.stack, c.layer, new, ring))
                    for c, new in zip(views, made))
        else:
            from deepspeed_tpu.ops.attention import diff_decode
            k_new = v_new = None
            if not self.cross:
                made = tuple(c.replace(stage=new[:, 0].astype(c.stack.dtype))
                             for c, new in zip(views, made))
                k_new, v_new = made[0].stage, made[1].stage
            k_view, v_view = shared if self.cross else views
            o = diff_decode(q[:, 0], k_view, v_view, lengths, lam, scale,
                            cfg.layer_norm_eps, k_new=k_new, v_new=v_new,
                            slots=slots, ring=self.window is not None)[:, None]
        o = (o * subln * (1.0 - li)).astype(cfg.dtype)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "out_proj", True)(o.reshape(b, s, nh * hd))
        return out, made


# ------------------------------------------------------------------- layers


def _residual(cfg: Phi4FlashConfig, name: str, h, mixer):
    """`h += mixer(LN(h)); h += FFN(LN'(h))`: one layer around its mixer
    (called from a pair's compact method, whose submodules these are).
    Returns (h, whatever else the mixer returned)."""
    norm = functools.partial(LayerNorm, cfg.layer_norm_eps, cfg.dtype)
    out, *rest = mixer(norm(name=f"{name}_norm")(h))
    h = h + out
    h = h + FFN(cfg, name=f"{name}_mlp")(norm(name=f"{name}_mlp_norm")(h))
    return (h, *rest)


class _MambaAttention(nn.Module):
    """One pair [Mamba-1, differential attention] of `front` (under a
    window, its K and V in ring `pair` of the window stack) or `mid` (full,
    its K and V the shared slab; its Mamba layer publishes `m`)."""
    cfg: Phi4FlashConfig
    window: Optional[int]

    @nn.compact
    def __call__(self, carry, consts, pair):
        """carry `(h, state, stacks)`: the Mamba buffers `(ssm, conv)` and
        the `(k, v)` stacks a pass that writes them carries; consts
        `(stacks, lengths, slots)`: the stacks of a decode step, which
        stages, and where its token stands. `pair`: this pair's slot in the
        stacks and, doubled, its first layer's index. Returns the carry and
        `(m, made)`: `mid`'s memory, and a decode step's staged `(k, v)` or
        `mid`'s fresh ones in a pass without a cache."""
        from deepspeed_tpu.inference.kv_cache import DenseLayer
        cfg = self.cfg
        h, state, stacks = carry
        held, lengths, slots = consts
        mid = self.window is None
        depth = cfg.half if mid else 2 * pair
        mamba = Mamba1Mixer(cfg, name="mamba")
        h, m, state = _residual(cfg, "mamba", h,
                                lambda u: mamba(u, state, pair))
        views = None
        if stacks is not None or held is not None:
            views = tuple(DenseLayer(t, 0 if mid else pair)
                          for t in (stacks or held))
        attn = DiffAttention(cfg, window=self.window, name="attn")
        h, made = _residual(cfg, "attn", h, lambda u: attn(
            u, depth + 1, views, lengths=lengths, slots=slots))
        if stacks is not None:          # written: the stacks go on
            stacks, made = tuple(c.stack for c in made), None
        elif held is not None:          # staged: the caller lands them
            made = tuple(c.stage for c in made)
        elif not mid:
            made = None
        return (h, state, stacks), (m if mid else None, made)


class _MemoryCross(nn.Module):
    """One pair [gated memory unit, cross differential attention] of
    `back`."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, h, consts, pair):
        """consts `(m, shared, lengths)`: the memory, and what the cross
        layer reads: layer `half + 1`'s fresh `(k, v)` in a plain pass
        (`lengths` None), else `DenseLayer` views of the slab."""
        cfg = self.cfg
        m, shared, lengths = consts
        depth = cfg.half + 2 + 2 * pair
        unit = MemoryUnit(cfg, name="gmu")
        h, = _residual(cfg, "gmu", h, lambda u: (unit(u, m),))
        attn = DiffAttention(cfg, cross=True, name="attn")
        h, _ = _residual(cfg, "attn", h, lambda u: attn(
            u, depth + 1, shared=shared, lengths=lengths))
        return h, None


class _FrontMid(nn.Module):
    """Layers 0 .. half + 1 over every position of `h`: `front`, then `mid`.
    Returns (h, m of the mid Mamba layer, the mid attention's fresh (k, v)
    or None, cache)."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, h, cache=None):
        cfg = self.cfg
        s = h.shape[1]
        n = cfg.front_pairs
        front = nn.scan(_MambaAttention, in_axes=(nn.broadcast, 0), out_axes=0,
                        length=n, **_SCAN)(cfg, cfg.sliding_window,
                                           name="front")
        mid = _MambaAttention(cfg, None, name="mid")
        pairs = jnp.arange(n, dtype=jnp.int32)
        if cache is None:
            (h, _, _), _ = front((h, None, None), (None, None, None), pairs)
            (h, _, _), (m, fresh) = mid((h, None, None), (None, None, None), n)
            return h, m, fresh, None
        from deepspeed_tpu.inference.kv_cache import DenseLayer
        state = (cache.state.ssm, cache.state.conv)
        ring = (cache.window.k.stack, cache.window.v.stack)
        slab = (cache.kv.k.stack, cache.kv.v.stack)
        index, kv, window = cache.index, cache.kv, cache.window
        if s > 1:   # a prefill: the stacks are the carry, written whole
            (h, state, ring), _ = front((h, state, ring), (None, None, None),
                                        pairs)
            (h, state, slab), (m, _) = mid((h, state, slab),
                                           (None, None, None), n)
            window = window.replace(k=DenseLayer(ring[0]),
                                    v=DenseLayer(ring[1]))
            kv = kv.replace(k=DenseLayer(slab[0]), v=DenseLayer(slab[1]))
        else:       # a decode step: each layer stages, one write a kind
            slots = window.max_len
            (h, state, _), (_, staged) = front(
                (h, state, None),
                (ring, jnp.minimum(index + 1, slots), index % slots), pairs)
            window = window.land(*staged)
            (h, state, _), (m, staged) = mid(
                (h, state, None), (slab, index + 1, index), n)
            kv = kv.land(*(t[None] for t in staged))
        return h, m, None, cache.replace(
            kv=kv, window=window,
            state=cache.state.replace(ssm=state[0], conv=state[1]))


class Phi4FlashForCausalLM(nn.Module):
    cfg: Phi4FlashConfig
    # counted inside a serving program and summed over the call by the engine
    # (`serving` event): the positions its prefill took in, and those of them
    # that walked the cross decoder (layers half + 2 on): one a sequence
    program_counters = ("prompt_positions", "cross_prefill_positions")

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        cfg = self.cfg
        embed = hybrid.embed_tokens(self)
        b, s = input_ids.shape
        rows = hybrid.rows_a_group(b, s, PREFILL_TOKENS)
        fresh = lengths = None
        if cache is not None and s > 1 and rows < b:
            # `_FrontMid` a few rows at a time: both streams' last positions
            # go on (its third result, a plain pass's fresh K and V, is None
            # under a cache)
            cache, (h, m) = hybrid.row_groups(
                _FrontMid, cfg, cache, embed, input_ids, rows, name="decoder")
        else:
            h, m, fresh, cache = _FrontMid(cfg, name="decoder")(
                hybrid.embedded(cfg, embed, input_ids), cache)
            if cache is not None:
                h, m = h[:, -1:], m[:, -1:]
        if cache is not None:
            cache = cache.advance(s)
            fresh = cache.kv.layer_views(0, staged=False)
            lengths = cache.index
            if s > 1:
                for name, count in (("prompt_positions", b * s),
                                    ("cross_prefill_positions", b)):
                    self.sow("counters", name, jnp.asarray(count, jnp.int32),
                             init_fn=lambda: jnp.zeros([], jnp.int32),
                             reduce_fn=lambda a, b_: a + b_)
        back = nn.scan(_MemoryCross, in_axes=(nn.broadcast, 0), out_axes=0,
                       length=cfg.back_pairs, **_SCAN)(cfg, name="back")
        h, _ = back(h, (m, fresh, lengths),
                    jnp.arange(cfg.back_pairs, dtype=jnp.int32))
        h = LayerNorm(cfg.layer_norm_eps, cfg.dtype, name="final_layernorm")(h)
        return hybrid.lm_output(h @ embed.astype(cfg.dtype).T, input_ids,
                                labels, cache)

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions, by kind: the window layers' rings, the one
        full-length slab, the Mamba-1 states and convolution tails."""
        from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                                      RecurrentState)
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        return HybridCache(
            kv=KVCache.create_stacked(1, batch, max_len, cfg.pair_groups,
                                      cfg.pair_width, dtype=dtype),
            window=KVCache.create_stacked(
                cfg.front_pairs, batch, cfg.sliding_window, cfg.pair_groups,
                cfg.pair_width, dtype=dtype, ring=True),
            state=RecurrentState.create(
                cfg.num_mamba_layers, batch, cfg.ssm_state_shape,
                cfg.mamba_d_conv, cfg.d_inner, dtype=dtype))


init_params_and_specs, materialize_params, phi4flash_loss_fn = \
    hybrid.entry_points(Phi4FlashForCausalLM)
