"""Pallas TPU decode kernel for latent attention (MLA, DeepSeek-V2) in its
ABSORBED form, over a stacked LATENT cache.

A latent-attention layer caches, a token, ONE array all heads share: the
normalised KV latent `c` (`kv_lora_rank`, 512) and the rotated rope key
`k_r` (`qk_rope_head_dim`, 64) side by side, `(L, B, 1, M, 576)`
(`inference/kv_cache.HybridCache.latent`). A head's key is `[W_k^h c | k_r]`
and its value `W_v^h c`; at decode neither is formed. The query's nope part
is taken through `W_k^h` instead (`q_abs = q_nope W_k^h`, 512 wide), so

    score_h(t) = (q_abs_h . c_t + q_rope_h . k_r_t) * scale
    o_lat_h    = sum_t softmax(score_h)(t) c_t            (512 wide)

and the caller takes `o_lat` through `W_v^h`. All `H` heads of a sequence
read the SAME slab, so a grid step fetches a block of it once and serves
every head: `(H, 576) x (slots, 576)` scores, `(H, slots) x (slots, 512)`
values. The bytes a step must move are the slab once, `576 x 2` a cached
token, whatever the number of heads.

576 is no multiple of the 128 lanes. The slab is one array all the same (a
token's latent and rope key are written together, one write a step); the
kernel reads a fetched block through two static views, lanes 0..511 (the
latent: keys AND values) and 512..575 (the rope key), and the query arrives
as the two matching pieces, so no product contracts over a ragged width.

`lengths[b]` slots are valid from 0; blocks past a row's length are neither
fetched nor computed (the dense decode kernel's clamp). A decode step's own
token is STAGED (`new`: not in the cache yet) and takes slot `slots[b]`'s
place in the block the kernel fetched; `LatentCache.land` writes it after
the layers, through `latent_write_dense` below: the dense cache's writer
(`decode_attention.kv_write_dense`) moves its window of slots with a DMA it
slices by hand, and the chip's compiler refuses such a slice of an array
whose minor dimension is no multiple of 128 ("Slice shape along dimension 4
must be aligned to tiling (128), but is 576"), so the latent's writer
fetches and writes back the same window through block specs instead.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF
from deepspeed_tpu.ops.pallas.sparse_select import block_of

KERNEL_NAME = "mla_latent_decode"
WRITE_NAME = "latent_write_dense"
_BLOCK_SLOTS = 512      # 0.66 MB of latent a block (576 -> 640 lanes, bf16)
# Heads from which a block is WIDE, and its slots. A block costs its bytes or
# its operations, whichever is longer, and a grid step's fixed cost beside
# them. Under 128 heads the bytes are the longer (32 heads: a quarter of the
# operations) and 512 slots were read on the chip (PERF.md, PR 47). At 128
# heads the two meet (242 FLOP a byte against the chip's 240) and a row of
# 25,600 slots is 50 such steps a layer. Read on the chip at 8 rows of 24,704
# live slots and 5 layers (`tools/mla_dense_sweep.py`; PERF.md, PR 58), the
# least the mathematics allows 1.40 ms a step: 2.75 ms at 512 slots, 2.20 at
# 1,024, 2.09 at 1,280, 1.88 at 2,560 and 1.83 at 5,120. 2,560 is what every
# row `models/latent.cache_slots` gives divides (33,280 slots would fall to
# 3,328 under a cap of 5,120), and the last 3% are 0.1% of that cell's batch.
_WIDE_HEADS = 128
_WIDE_BLOCK_SLOTS = 2560


def decode_block(h: int, m: int) -> int:
    """Slots a grid step of `mla_latent_decode` fetches, from the shapes:
    `h` query heads over rows of `m` slots. ONE rule, the largest divisor of
    the row up to the cap its heads give."""
    return block_of(m, _WIDE_BLOCK_SLOTS if h >= _WIDE_HEADS
                    else _BLOCK_SLOTS)


def _kernel(lengths_ref, slots_ref, layer_ref, qc_ref, qr_ref, lat_ref,
            *rest, scale, blk, nk, rank, staged):
    del layer_ref  # the index maps read it
    if staged:
        new_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    heads = qc_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]

    @pl.when(j * blk < length)  # skip fully-invalid blocks
    def _compute():
        c = lat_ref[:, :rank]                        # (blk, rank) latent
        kr = lat_ref[:, rank:]                       # (blk, rope) rope key
        if staged:
            hit = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0) \
                == slots_ref[b] - j * blk
            c = jnp.where(hit, new_ref[:, :rank], c)
            kr = jnp.where(hit, new_ref[:, rank:], kr)
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qc_ref[...], c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[...], kr, nt,
                                   preferred_element_type=jnp.float32)) * scale
        cols = j * blk + jax.lax.broadcasted_iota(jnp.int32, (heads, blk), 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[...] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


def mla_latent_decode(q_lat: jnp.ndarray, q_rope: jnp.ndarray,
                      stack: jnp.ndarray, layer, lengths: jnp.ndarray,
                      softmax_scale: float,
                      new: Optional[jnp.ndarray] = None,
                      slots: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q_lat (B, H, rank): the queries' nope parts absorbed through the key
    half of the up-projection; q_rope (B, H, rope), rotated; stack (L, B, 1,
    M, rank + rope) and `layer` the layer to read; lengths (B,) valid slots
    a row. With `new` (B, rank + rope) the row's staged token stands in slot
    `slots[b]` (a slot at or past M: nowhere). Returns (B, H, rank) float32:
    the softmax-weighted sum of the cached LATENTS, to be taken through the
    value half of the up-projection."""
    b, h, rank = q_lat.shape
    m, width = stack.shape[3:]
    blk = decode_block(h, m)
    nk = m // blk
    staged = new is not None
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)
    slots = (jnp.full((b,), m, jnp.int32) if slots is None
             else slots.astype(jnp.int32))

    def row(b_, j, L, S, Ly):
        return (b_, 0, 0)

    def lat_index(b_, j, L, S, Ly):
        # clamped to the row's last valid block: steps past it revisit that
        # block and Pallas elides their copies (`decode_attention.py`)
        last = jnp.maximum((L[b_] + blk - 1) // blk - 1, 0)
        return (Ly[0], b_, 0, jnp.minimum(j, last), 0)

    in_specs = [pl.BlockSpec((None, h, rank), row),
                pl.BlockSpec((None, h, width - rank), row),
                pl.BlockSpec((None, None, None, blk, width), lat_index)]
    args = [lengths, slots, jnp.asarray(layer, jnp.int32).reshape(1),
            q_lat.astype(stack.dtype), q_rope.astype(stack.dtype), stack]
    if staged:
        in_specs.append(pl.BlockSpec((None, 1, width), row))
        args.append(new.astype(stack.dtype)[:, None])
    return pl.pallas_call(
        functools.partial(_kernel, scale=softmax_scale, blk=blk, nk=nk,
                          rank=rank, staged=staged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nk), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, rank), row),
            scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a wide block's tile, scores and probabilities outgrow the
            # default scoped limit; the narrow plan is compiled as it was
            vmem_limit_bytes=64 * 1024 * 1024 if blk > _BLOCK_SLOTS
            else None),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(*args)


def mla_latent_decode_reference(q_lat, q_rope, stack, layer, lengths,
                                softmax_scale, new=None, slots=None):
    """The same in plain `jax.numpy`, float32 (tests, `chip_smoke`, and the
    model's own path off the chip)."""
    f32 = jnp.float32
    b, _, rank = q_lat.shape
    m = stack.shape[3]
    lat = jax.lax.dynamic_index_in_dim(
        stack, jnp.asarray(layer, jnp.int32), 0, keepdims=False)[:, 0]
    if new is not None:                                        # (B, M, width)
        lat = lat.at[jnp.arange(b), slots].set(new.astype(lat.dtype),
                                               mode="drop")
    lat = lat.astype(f32)
    # the kernel's operands are the cache's type: round the queries as it does
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(stack.dtype)
    s = jnp.einsum("bhw,bmw->bhm", q.astype(f32), lat,
                   precision="highest") * softmax_scale
    valid = jnp.arange(m)[None, :] < jnp.minimum(lengths, m)[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    return jnp.einsum("bhm,bmr->bhr", jax.nn.softmax(s, axis=-1),
                      lat[..., :rank], precision="highest")


def _write_kernel(starts_ref, new_ref, in_ref, out_ref, *, m, w):
    start = starts_ref[pl.program_id(0)]
    # the window's slot that takes the row's token; none in a parked row,
    # whose (clamped) window is written back as it was
    hit = (jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) == start % w) \
        & (start >= 0) & (start < m)
    out_ref[...] = jnp.where(hit, new_ref[...], in_ref[...])


def latent_write_dense(stack: jnp.ndarray, new: jnp.ndarray,
                       starts: jnp.ndarray) -> jnp.ndarray:
    """Write `new` (L, B, W), a decode step's one latent row a sequence of
    every layer, into the stacked latent cache (L, B, 1, M, W) at `[:, b, 0,
    starts[b]]`, IN PLACE: the stack is aliased to the result and only the
    window of slots around each cursor is read and written back (the chip
    writes no single bf16 row of a tile), so the tiling stays the one
    `mla_latent_decode` reads. A row whose `starts` is at or past M (parked)
    or negative is dropped. One grid step a row."""
    l, b, _, m, width = stack.shape
    w = math.gcd(m, 32 // jnp.dtype(stack.dtype).itemsize)

    def window(b_, St):
        return (0, b_, 0, jnp.clip(St[b_], 0, m - 1) // w, 0)

    slab = pl.BlockSpec((l, None, None, w, width), window)
    return pl.pallas_call(
        functools.partial(_write_kernel, m=m, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[pl.BlockSpec((l, None, 1, width),
                                   lambda b_, St: (0, b_, 0, 0)), slab],
            out_specs=slab),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        input_output_aliases={2: 0},       # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name=WRITE_NAME,
    )(starts.astype(jnp.int32), new.astype(stack.dtype)[:, :, None], stack)
