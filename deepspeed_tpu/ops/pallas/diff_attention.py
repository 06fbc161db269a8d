"""Pallas TPU decode kernel for DIFFERENTIAL attention over a stacked cache
whose heads lie in PAIRS (Phi-4-mini-flash, `models/phi4flash.py`).

Differential attention (Ye et al. 2024) runs two softmaxes a pair of heads
and subtracts them: with head width `d` (64), a pair's queries `(q1, q2)`,
its group's keys `(k1, k2)` and the group's value `[v1 | v2]` (2d wide),

    a1 = softmax(q1 k1^T s) [v1 | v2]      a2 = softmax(q2 k2^T s) [v1 | v2]
    o  = RMSNorm_2d(a1 - lam a2)           (no weight: the caller's)

The cache keeps a group's two key heads side by side, `[k1 | k2]`, and its
value as it is used: `(L, B, G, M, 2d)`, 128 lanes at d 64, the kernel's
order as the dense stack's is (`inference/kv_cache.DenseLayer`). A query
arrives padded to that width, `[q1 | 0]` and `[0 | q2]`, so each of the two
softmaxes is one row of a plain `(rows, 2d) x (slots, 2d)` product, and a
group's rows, `r` pairs' first queries and then their second, ride one tile.
The subtraction and the norm are done on the float32 accumulators before
anything is rounded.

One kernel, two names in a trace (`pallas_call(name=)`), by what it reads:

- `diff_attn_shared_decode`: a full-length slab, `lengths[b]` slots valid
  from 0; blocks past a row's length are neither fetched nor computed (the
  dense decode kernel's clamp);
- `diff_attn_window_decode`: a RING of `M` = window slots, position p in
  slot p mod M. Attention without a positional embedding does not ask where
  a key lies, so a ring is read as a cache of `min(position + 1, M)` slots.

A decode step's own token is STAGED (`k_new`, `v_new`: not in the cache yet)
and takes slot `slots[b]`'s place in the tile the kernel fetched, the written
token's arithmetic bit for bit; `KVCache.land` writes it after the layers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF

SHARED_NAME = "diff_attn_shared_decode"
WINDOW_NAME = "diff_attn_window_decode"
# K and V blocks of one grid step, double-buffered, under the 16 MB a kernel
# may use of VMEM by default
_BLOCK_BYTES = 2 * 1024 * 1024


def _kernel(lengths_ref, slots_ref, layer_ref, q_ref, k_ref, v_ref, lam_ref,
            *rest, scale, blk_k, nk, gb, pairs, eps, staged):
    del layer_ref  # the index maps read it
    if staged:
        kn_ref, vn_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    rows = 2 * pairs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]

    @pl.when(j * blk_k < length)  # skip fully-invalid blocks
    def _compute():
        cols = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (rows, blk_k), 1)
        valid = cols < length
        if staged:
            hit = jax.lax.broadcasted_iota(jnp.int32, (blk_k, 1), 0) \
                == slots_ref[b] - j * blk_k
        for g in range(gb):
            q, k, v = q_ref[g], k_ref[g], v_ref[g]   # (rows, W), (blk_k, W) x 2
            if staged:
                k = jnp.where(hit, kn_ref[g], k)
                v = jnp.where(hit, vn_ref[g], v)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = jnp.broadcast_to(
                l_scr[g][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
                l_scr.shape[1:])
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(j == nk - 1)
    def _finalize():
        lam = lam_ref[...]                           # (1, W), one value
        for g in range(gb):
            l = l_scr[g][:, :1]
            a = acc_scr[g] / jnp.where(l == 0.0, 1.0, l)      # (rows, W) f32
            diff = a[:pairs] - lam * a[pairs:]
            o_ref[g] = diff * jax.lax.rsqrt(
                jnp.mean(diff * diff, axis=-1, keepdims=True) + eps)


def _blocks(groups: int, m: int, width: int, itemsize: int):
    """(groups a grid step, slots a block): whole groups of a row where a
    block of all of them stays under `_BLOCK_BYTES`, since a grid step costs
    what 0.3 MB cost to fetch and a group's block of a ring is 0.13 MB."""
    blk_k = m
    while groups * blk_k * width * itemsize > _BLOCK_BYTES and blk_k % 2 == 0 \
            and blk_k > 128:
        blk_k //= 2
    gb = groups
    while gb * blk_k * width * itemsize > _BLOCK_BYTES and gb > 1:
        gb = next(d for d in range(gb - 1, 0, -1) if groups % d == 0)
    return gb, blk_k


def diff_decode_attention(q: jnp.ndarray, k_stack: jnp.ndarray,
                          v_stack: jnp.ndarray, layer, lengths: jnp.ndarray,
                          lam: jnp.ndarray, softmax_scale: float,
                          eps: float = 1e-5,
                          k_new: Optional[jnp.ndarray] = None,
                          v_new: Optional[jnp.ndarray] = None,
                          slots: Optional[jnp.ndarray] = None,
                          ring: bool = False) -> jnp.ndarray:
    """q (B, G, 2r, W): a group's r pairs, the first queries `[q1 | 0]` of
    all r and then the second `[0 | q2]`; k/v_stack (L, B, G, M, W) and
    `layer` the layer to read; lengths (B,) valid slots a row; lam () or
    (1,) float32. With `k_new`/`v_new` (B, G, W) the row's staged token
    stands in slot `slots[b]` (a slot at or past M: nowhere). `ring` names
    the call in a trace. Returns (B, G, r, W) float32:
    `RMSNorm(a1 - lam a2)` without a weight."""
    b, g, rows, w = q.shape
    m = k_stack.shape[3]
    pairs = rows // 2
    gb, blk_k = _blocks(g, m, w, jnp.dtype(k_stack.dtype).itemsize)
    nk = m // blk_k
    staged = k_new is not None
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)
    slots = (jnp.full((b,), m, jnp.int32) if slots is None
             else slots.astype(jnp.int32))

    def row(b_, gi, j, L, S, Ly):
        return (b_, gi, 0, 0)

    def kv_index(b_, gi, j, L, S, Ly):
        # clamped to the row's last valid block: steps past it revisit that
        # block and Pallas elides their copies (`decode_attention.py`)
        last = jnp.maximum((L[b_] + blk_k - 1) // blk_k - 1, 0)
        return (Ly[0], b_, gi, jnp.minimum(j, last), 0)

    kv_spec = pl.BlockSpec((None, None, gb, blk_k, w), kv_index)
    in_specs = [pl.BlockSpec((None, gb, rows, w), row), kv_spec, kv_spec,
                pl.BlockSpec((1, w), lambda *_: (0, 0))]
    args = [lengths, slots, jnp.asarray(layer, jnp.int32).reshape(1),
            q, k_stack, v_stack,
            jnp.broadcast_to(jnp.asarray(lam, jnp.float32).reshape(1, 1),
                             (1, w))]
    if staged:
        in_specs += [pl.BlockSpec((None, gb, 1, w), row)] * 2
        args += [k_new.astype(k_stack.dtype)[:, :, None],
                 v_new.astype(v_stack.dtype)[:, :, None]]
    return pl.pallas_call(
        functools.partial(_kernel, scale=softmax_scale, blk_k=blk_k, nk=nk,
                          gb=gb, pairs=pairs, eps=eps, staged=staged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, g // gb, nk), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, gb, pairs, w), row),
            scratch_shapes=[pltpu.VMEM((gb, rows, 128), jnp.float32),
                            pltpu.VMEM((gb, rows, 128), jnp.float32),
                            pltpu.VMEM((gb, rows, w), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, g, pairs, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=WINDOW_NAME if ring else SHARED_NAME,
    )(*args)


def diff_decode_attention_reference(q, k_stack, v_stack, layer, lengths, lam,
                                    softmax_scale, eps=1e-5, k_new=None,
                                    v_new=None, slots=None, ring=False):
    """The same in plain `jax.numpy`, float32 (tests, `chip_smoke`, and the
    model's own path off the chip)."""
    del ring
    f32 = jnp.float32
    b, g, rows, w = q.shape
    m = k_stack.shape[3]
    k, v = (jax.lax.dynamic_index_in_dim(s, jnp.asarray(layer, jnp.int32), 0,
                                         keepdims=False) for s in
            (k_stack, v_stack))                               # (B, G, M, W)
    if k_new is not None:
        at = jnp.arange(b)
        k = k.at[at, :, slots].set(k_new.astype(k.dtype), mode="drop")
        v = v.at[at, :, slots].set(v_new.astype(v.dtype), mode="drop")
    s = jnp.einsum("bgrw,bgmw->bgrm", q.astype(f32), k.astype(f32),
                   precision="highest") * softmax_scale
    valid = jnp.arange(m)[None, :] < jnp.minimum(lengths, m)[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    a = jnp.einsum("bgrm,bgmw->bgrw", jax.nn.softmax(s, axis=-1),
                   v.astype(f32), precision="highest")
    diff = a[:, :, :rows // 2] - jnp.asarray(lam, f32).reshape(()) \
        * a[:, :, rows // 2:]
    return diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True)
                                + eps)
