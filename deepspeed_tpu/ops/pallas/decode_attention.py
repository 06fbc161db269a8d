"""Pallas TPU decode attention (single-query flash over a padded KV cache).

The `softmax_context` kernel slot (reference
`csrc/transformer/inference/csrc/pt_binding.cpp` softmax_context_fwd +
`transform.cu:727` KV-cache attention): one new query token per sequence
attends its cache row. Per-row valid lengths arrive via scalar prefetch and
KV blocks beyond a row's length are *skipped entirely* (block index clamped,
so Pallas elides their HBM copies) — decode is KV-bandwidth-bound, so a
200-token sequence in a 4096-slot cache reads 1/20th of the bytes the
masked XLA path touches.

WHOLE-GROUP steps (PR 49): the grid is (B / rb, M / blk_k) and a step carries
EVERY KV head of a group of `rb` rows over one block of slots: the stack is
(L, B, Hkv, M, D), a row's heads and a group's rows lie side by side, so one
block (rb, Hkv, blk_k, D) covers them. Inside the step each (row, head) pair
runs its own online softmax on its own float32 state, the n_rep = H/Hkv
query heads that share the KV head as one (n_rep, D) tile against the
(blk_k, D) KV tile (MHA degenerates to n_rep=1), each row masked at ITS OWN
length. The group fetches blocks up to its longest row's last live one.
Before PR 49 a step was one row's one KV head's one block, and a decode step
of a 36-layer model at 32 rows paid 3,072 steps of 0.35 us each, half the
kernel's time. What is left is a pair's update of a block, about 0.13 us +
0.57 ns a slot, beside 0.63 ns a slot of bytes: blocks are long
(`MAX_BLOCK_K`), and the sizes come from the shapes (`decode_plan`: one VMEM
budget for the double-buffered K and V tiles, at least `MIN_STEPS` steps a
call so that the first fetch, which nothing hides, stays a small share).

Layout: q (B, 1, H, D). The cache is the STACKED dense cache as it lies at
rest, (L, B, Hkv, M, D) (`inference/kv_cache.py:DenseLayer`), with the layer
to read as a second scalar-prefetch operand: a block is fetched from
`(layer, group, :, j)` of the stack, and no program cuts a layer out of it or
re-lays it first. One layer's own (B, M, Hkv, D) array, the per-layer view,
is re-laid here and goes in as a stack of one. KV-block axis sequential,
online-softmax state in VMEM scratch.

A RING (`KVCache.ring`, a window layer's cache: position p in slot p mod M)
is read by the same body under a second name (`RING_NAME`). Keys are rotated
BEFORE they are cached and a softmax does not care in which slot a key lies,
so the reader needs no position of any slot: only how many slots are live
(`min(index + 1, M)`, what `lengths` carries) and, GIVEN APART from that
count, the slot the step's staged token stands in (`slots`, `index mod M`:
once the ring has wrapped it is the oldest key's, the one that leaves the
window as this token enters it).

`kv_write_dense` is the stack's writer: a decode step's one new token a row
of every layer, in place (the stacks are aliased to the results).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF, lane_block

# VMEM for the double-buffered K and V tiles of one grid step (two tiles, two
# buffers each), of the 16 MB a kernel may use by default; the plan below
# sizes a step's work under it
KV_TILE_BUDGET = 8 * 1024 * 1024
# KV slots a block at most. Each (row, head) pair's online-softmax update of
# a block costs about 0.13 us whatever the block holds, beside 0.57 ns a slot
# (PERF.md, PR 49: 36 layers' calls at 32 rows and M 1280 take 4.16 ms in
# blocks of 256 slots and 2.31 in blocks of 640), so a block is as long as
# the cache allows up to this
MAX_BLOCK_K = 1024
# grid steps a call at least, where the batch allows: the first step's fetch
# waits for nothing to compute, so a call of S steps exposes 1 / S of its
# bytes (PERF.md, PR 49: at M 512, one block a row, 32 rows take 1.15 ms in
# four steps of 8 rows and 1.08 in eight of 4)
MIN_STEPS = 8
# the kernel's names on the device trace: full-length rows, and a ring
DENSE_NAME = "self_attn_dense_decode"
RING_NAME = "self_attn_ring_decode"


def _divisors_desc(n: int, cap: int):
    return (x for x in range(max(min(n, cap), 1), 0, -1) if n % x == 0)


def decode_plan(b: int, hkv: int, m: int, d: int, itemsize: int,
                block_k: Optional[int] = None) -> Tuple[int, int]:
    """(rows a grid step, KV slots a block) of `decode_attention` at these
    shapes: the kernel's ONE sizing rule, and what the engine's telemetry
    asks for the steps and slots a call costs (`plan_traffic`).

    A grid step carries every KV head of `rb` rows over `blk_k` slots. Its
    K and V tiles, double-buffered, stay under `KV_TILE_BUDGET`. `blk_k`
    divides M, at most `MAX_BLOCK_K` (or the caller's `block_k`) and what the
    budget leaves one row, in whole lane tiles of 128 where M has such a
    divisor; `rb` is the largest divisor of B that the budget and
    `MIN_STEPS` leave, 1 at worst."""
    per_slot = 4 * hkv * d * itemsize     # one row's one slot in the tiles
    cap = min(block_k or MAX_BLOCK_K, KV_TILE_BUDGET // per_slot)
    blk_k = lane_block(m, cap)
    rb = next(_divisors_desc(b, min(KV_TILE_BUDGET // (per_slot * blk_k),
                                    b * (m // blk_k) // MIN_STEPS)))
    return rb, blk_k


def plan_traffic(plan: Tuple[int, int], lengths, m: int):
    """(slots that hold tokens, slots fetched, grid steps) of the calls at
    `lengths` (..., B), one call a leading index, summed, on the host: a
    slot is one token's place in one row, all heads, K and V. A group of
    `rb` rows fetches whole blocks up to its longest row's last live one (at
    least one: a group of empty rows still fetches its first block), and
    every row of the group with them."""
    rb, blk_k = plan
    lengths = np.minimum(np.asarray(lengths, np.int64), m)
    longest = lengths.reshape(lengths.shape[:-1] + (-1, rb)).max(axis=-1)
    blocks = np.maximum(-(-longest // blk_k), 1)
    return (int(lengths.sum()), int(blocks.sum()) * blk_k * rb,
            longest.size * (m // blk_k))


def _decode_kernel(lengths_ref, layer_ref, *rest, scale, blk_k, nk, rb, hkv,
                   n_rep, quantized, staged, ring):
    """One grid step: `rb` rows' every KV head over one block of slots.
    Refs after the two scalars, in args order: a ring's staged slots (a
    third scalar), the query and the caches, the int8 scales, the staged
    pair, the output, then the online-softmax state m / l / acc, one
    (n_rep, .) tile a (row, head)."""
    del layer_ref  # the index maps read it
    rest = list(rest)
    slots_ref = rest.pop(0) if ring else None
    q_ref, k_ref, v_ref = rest.pop(0), rest.pop(0), rest.pop(0)
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quantized else (None, None)
    kn_ref, vn_ref = (rest.pop(0), rest.pop(0)) if staged else (None, None)
    o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(t, cols, slots):
        """The online softmax of pair t, row `t // hkv`'s head `t % hkv`,
        over this block, whose slots' numbers are `cols` (n_rep, blk_k) and
        `slots` (blk_k, 1)."""
        r, g = t // hkv, t % hkv
        length = lengths_ref[i * rb + r]     # each row at its OWN length
        # the staged token's slot: the last live one, or where a ring says
        valid = cols < length
        hit = slots == (slots_ref[i * rb + r] if ring else length - 1)
        q = q_ref[t]                         # (n_rep, D) — the GQA group
        k = k_ref[r, g]                      # (blk_k, D)
        v = v_ref[r, g]
        if staged:
            # staged token (kv_cache.DenseLayer.stage): the row's NEW key
            # and value are not in the stack yet; they take their slot's
            # place in the tile, so the arithmetic is the written token's
            k = jnp.where(hit, kn_ref[t], k)
            v = jnp.where(hit, vn_ref[t], v)
        if quantized:
            # int8 cache: fold the per-token K scale into the LOGIT columns
            # (token scales ride the lane axis, matching the logits' key
            # axis — the r6 scale-into-activation trick)
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s * ks_ref[t] * scale
        else:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[t][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with no valid slot yet has m = NEG_INF, where exp(s - m) is
        # 1 in every masked column: those are zeros, not probabilities
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[t, :, :1] = l_scr[t][:, :1] * alpha \
            + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # per-token V scale folds into the PROBABILITY columns
            pv = jax.lax.dot_general(
                p * vs_ref[t], v.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_scr[t] = acc_scr[t] * alpha + pv
        m_scr[t, :, :1] = m_new

    longest = functools.reduce(jnp.maximum,
                               [lengths_ref[i * rb + r] for r in range(rb)])

    # ONE branch a step, over the group: the pairs' updates are independent
    # and the scheduler may interleave them (a branch a row kept each row's
    # chain of two products and a softmax to itself: 4-8% slower, PERF.md,
    # PR 49). A row the block lies wholly past is masked to a no-op:
    # m and alpha stay, p is zero, l and acc are added nothing.
    @pl.when(j * blk_k < longest)
    def _group():
        cols = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (n_rep, blk_k), 1)
        slots = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_k, 1), 0)
        # the pair's update is traced ONCE and unrolled where it is lowered
        # (straight-line code, constant indices): a Python loop traced it
        # rb x Hkv times, 2 s more set-up a program of unrolled layers
        jax.lax.fori_loop(0, rb * hkv,
                          lambda t, _: attend(t, cols, slots), None,
                          unroll=True)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     softmax_scale: Optional[float] = None,
                     block_k: Optional[int] = None,
                     k_scales: Optional[jnp.ndarray] = None,
                     v_scales: Optional[jnp.ndarray] = None,
                     layer: Optional[jnp.ndarray] = None,
                     k_new: Optional[jnp.ndarray] = None,
                     v_new: Optional[jnp.ndarray] = None,
                     slots: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_cache: the stacked cache (L, B, Hkv, M, D) with
    `layer` () int32 the layer to read, or without one a layer's own
    (B, M, Hkv, D); lengths: (B,) valid tokens per row. With `k_new`/`v_new`
    (B, Hkv, D) the LAST valid token is the staged one, not yet in the
    cache: it takes its slot's place in the tile the kernel fetched, so the
    result is bit for bit the token written then attended (and a row with
    `lengths > M`, parked, has no slot: its token is dropped, as the writer
    drops it). Without them the new token's slot must already be written.
    `block_k` caps the KV slots a block (`decode_plan`). Returns
    (B, 1, H, D).

    `slots` (B,) marks a RING (the module text): `lengths` is then the COUNT
    of live slots, `min(index + 1, M)`, and the staged token stands in slot
    `slots[b]` (`index mod M`), which the count alone does not give once
    the ring has wrapped. Same body, traced as `RING_NAME`.

    `k_scales`/`v_scales` (B, M, Hkv) f32 mark an int8 cache (per-layer
    view only): the kernel folds the per-token scale into the logit /
    probability columns in-register (no dense bf16 cache form ever exists).
    With unit scales the quantized path is bitwise-identical to the
    unquantized kernel on the same cache values."""
    b, s, h, d = q.shape
    assert s == 1, "decode kernel is single-query; use flash_attention for prefill"
    if layer is None:
        # the per-layer view: re-laid (a copy of the layer) as a stack of one
        k_cache = jnp.swapaxes(k_cache, 1, 2)[None]
        v_cache = jnp.swapaxes(v_cache, 1, 2)[None]
        layer = 0
    hkv, m = k_cache.shape[2], k_cache.shape[3]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    rb, blk_k = decode_plan(b, hkv, m, d, jnp.dtype(k_cache.dtype).itemsize,
                            block_k)
    nk = m // blk_k
    staged = k_new is not None
    quantized = k_scales is not None
    ring = slots is not None
    assert staged or not ring, "a ring's reader is given the step's token"

    def pairs(i, j, *_):
        return (i, 0, 0)

    def kv_block(i, j, L):
        # Clamp the block index to the last valid block of the group's
        # LONGEST row: steps past it revisit the same block, so Pallas
        # elides their HBM copies — THIS is where the bandwidth saving
        # happens (the `pl.when` alone only skips compute, not the DMA).
        longest = functools.reduce(jnp.maximum,
                                   [L[i * rb + r] for r in range(rb)])
        return jnp.minimum(j, jnp.maximum((longest + blk_k - 1) // blk_k - 1, 0))

    def kv_index(i, j, L, Ly, *_):
        return (Ly[0], i, 0, kv_block(i, j, L), 0)

    # (B·Hkv, n_rep, D): row-major over heads means head g*n_rep+r of the
    # HF layout is group g, member r — exactly repeat_kv's grouping; a
    # step's block is its rb·Hkv (row, head) pairs
    def per_pair(*tail):
        return pl.BlockSpec((rb * hkv,) + tail, pairs)
    kv_spec = pl.BlockSpec((None, rb, hkv, blk_k, d), kv_index)
    in_specs = [per_pair(n_rep, d), kv_spec, kv_spec]
    scalars = [lengths.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1)]
    if ring:
        scalars.append(slots.astype(jnp.int32))
    args = scalars + [q.reshape(b * hkv, n_rep, d), k_cache, v_cache]
    if quantized:
        # (B, M, Hkv) → (B·Hkv, 1, M): token scales along lanes, one tile
        # per KV block beside its cache tile (same block). The unit
        # sublane dim is there for Mosaic: a block's second-to-last dim
        # must be a multiple of 8 or span the array's, and one row of
        # (B·Hkv, M) is neither.
        scale_spec = pl.BlockSpec(
            (rb * hkv, 1, blk_k),
            lambda i, j, L, *_: (i, 0, kv_block(i, j, L)))
        in_specs += [scale_spec, scale_spec]
        args += [jnp.swapaxes(k_scales, 1, 2).reshape(b * hkv, 1, m),
                 jnp.swapaxes(v_scales, 1, 2).reshape(b * hkv, 1, m)]
    if staged:  # one (1, D) row a pair: the unit dim spans its array's
        in_specs += [per_pair(1, d)] * 2
        args += [k_new.reshape(b * hkv, 1, d), v_new.reshape(b * hkv, 1, d)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b // rb, nk),
        in_specs=in_specs,
        out_specs=per_pair(n_rep, d),
        scratch_shapes=[pltpu.VMEM((rb * hkv, n_rep, 128), jnp.float32),
                        pltpu.VMEM((rb * hkv, n_rep, 128), jnp.float32),
                        pltpu.VMEM((rb * hkv, n_rep, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blk_k=blk_k, nk=nk,
                          rb=rb, hkv=hkv, n_rep=n_rep, quantized=quantized,
                          staged=staged, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=RING_NAME if ring else DENSE_NAME,
    )(*args)
    return out.reshape(b, 1, h, d)


def _kv_write_kernel(starts_ref, kn_ref, vn_ref, k_in, v_in, k_out, v_out,
                     k_buf, v_buf, sem, *, m, w):
    del k_in, v_in  # aliased to the outputs: one buffer each
    b = pl.program_id(0)
    start = starts_ref[b]

    @pl.when((start >= 0) & (start < m))  # a parked row has no slot: drop
    def _row():
        # the `w` slots around the row's cursor, of every layer and head:
        # the smallest window whose edges fall on the stack's tiles
        base = start // w * w
        there = [stack.at[:, b, :, pl.ds(base, w)] for stack in (k_out, v_out)]
        bufs = (k_buf, v_buf)

        def move(pairs):   # both in flight together, then both awaited
            copies = [pltpu.make_async_copy(src, dst, sem.at[i])
                      for i, (src, dst) in enumerate(pairs)]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        move(zip(there, bufs))
        hit = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) == start - base
        for buf, new_ref in ((k_buf, kn_ref), (v_buf, vn_ref)):
            # (L, Hkv, 1, D) broadcasts over the window's slots
            buf[...] = jnp.where(hit, new_ref[...], buf[...])
        move(zip(bufs, there))


def kv_write_dense(k_stack: jnp.ndarray, v_stack: jnp.ndarray,
                   k_new: jnp.ndarray, v_new: jnp.ndarray,
                   starts: jnp.ndarray):
    """Write `k_new`/`v_new` (L, B, Hkv, D), a decode step's one new token a
    row of every layer, into the stacked dense cache (L, B, Hkv, M, D) at
    `[:, b, :, starts[b]]`, IN PLACE: the stacks are aliased to the results,
    nothing else of them is read or written, and their tiling is the one
    `decode_attention` reads. A row whose `starts` is at or past M (parked)
    or negative is dropped, as the XLA scatter of `kv_cache.py` drops it.
    Returns `(k_stack, v_stack)`.

    One grid step a row: a read-modify-write of the window of slots the
    cursor falls in, for all layers and heads at once (a strided copy of
    L x Hkv pieces), since the chip writes no single bf16 row of a tile."""
    l, b, hkv, m, d = k_stack.shape
    w = math.gcd(m, 32 // jnp.dtype(k_stack.dtype).itemsize)
    new_spec = pl.BlockSpec((l, None, hkv, 1, d),
                            lambda b_, St: (0, b_, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    k_stack, v_stack = pl.pallas_call(
        functools.partial(_kv_write_kernel, m=m, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[new_spec, new_spec, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM((l, hkv, w, d), k_stack.dtype),
                            pltpu.VMEM((l, hkv, w, d), v_stack.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(k_stack.shape, k_stack.dtype),
                   jax.ShapeDtypeStruct(v_stack.shape, v_stack.dtype)],
        # scalar prefetch operands count as inputs
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="kv_write_dense",
    )(starts.astype(jnp.int32),
      k_new.astype(k_stack.dtype)[:, :, :, None],
      v_new.astype(v_stack.dtype)[:, :, :, None], k_stack, v_stack)
    return k_stack, v_stack
