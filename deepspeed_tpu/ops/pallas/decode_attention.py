"""Pallas TPU decode attention (single-query flash over a padded KV cache).

The `softmax_context` kernel slot (reference
`csrc/transformer/inference/csrc/pt_binding.cpp` softmax_context_fwd +
`transform.cu:727` KV-cache attention): one new query token per sequence
attends its cache row. Per-row valid lengths arrive via scalar prefetch and
KV blocks beyond a row's length are *skipped entirely* (block index clamped,
so Pallas elides their HBM copies) — decode is KV-bandwidth-bound, so a
200-token sequence in a 4096-slot cache reads 1/20th of the bytes the
masked XLA path touches.

HEAD-PACKED tiles: the grid is (B, Hkv, M/blk) and every step processes the
whole GQA group — the n_rep = H/Hkv query heads that share one KV head ride
one (n_rep, D) tile against the (blk_k, D) KV block, so a llama3-style
8-way group turns the former (1, D)·(blk_k, D) sliver into an MXU-shaped
(8, D)·(blk_k, D) matmul and cuts grid steps 8×. MHA degenerates to
n_rep=1 (the old layout).

Layout: q (B, 1, H, D). The cache is the STACKED dense cache as it lies at
rest, (L, B, Hkv, M, D) (`inference/kv_cache.py:DenseLayer`), with the layer
to read as a second scalar-prefetch operand: a block is fetched from
`(layer, b, g, j)` of the stack, and no program cuts a layer out of it or
re-lays it first. One layer's own (B, M, Hkv, D) array, the per-layer view,
is re-laid here and goes in as a stack of one. KV-block axis sequential,
online-softmax state in VMEM scratch.

`kv_write_dense` is the stack's writer: a decode step's one new token a row
of every layer, in place (the stacks are aliased to the results).
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

DEFAULT_BLOCK_K = 512
# the stacked cache's: a grid step costs about what 0.3 MB of K and V cost to
# fetch (0.35 us on v5e), so at 512 slots a step a short cache's kernel is
# mostly grid steps (PERF.md, PR 42: 36 layers' calls at 32 rows and M 1280
# take 5.06 ms in blocks of 320 slots and 3.31 ms in blocks of 640)
STACK_BLOCK_K = 1024


def _decode_kernel(lengths_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, blk_k, nk, n_rep,
                   ks_ref=None, vs_ref=None, kn_ref=None, vn_ref=None):
    del layer_ref  # the index maps read it
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]

    @pl.when(j * blk_k < length)  # skip fully-invalid blocks
    def _compute():
        q = q_ref[0]                         # (n_rep, D) — the GQA group
        k = k_ref[...]                       # (blk_k, D)
        v = v_ref[...]
        if kn_ref is not None:
            # staged token (kv_cache.DenseLayer.stage): the row's NEW key
            # and value are not in the stack yet; they take their slot's
            # place in the tile, so the arithmetic is the written token's
            slot = jax.lax.broadcasted_iota(jnp.int32, (blk_k, 1), 0)
            hit = slot == length - 1 - j * blk_k
            k = jnp.where(hit, kn_ref[...], k)
            v = jnp.where(hit, vn_ref[...], v)
        if ks_ref is not None:
            # int8 cache: fold the per-token K scale into the LOGIT columns
            # (token scales ride the lane axis, matching the logits' key
            # axis — the r6 scale-into-activation trick)
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s * ks_ref[0][None, :] * scale
        else:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        cols = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (n_rep, blk_k), 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if vs_ref is not None:
            # per-token V scale folds into the PROBABILITY columns
            pv = jax.lax.dot_general(
                p * vs_ref[0][None, :], v.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :1] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _mk_decode_kernel(quantized: bool, staged: bool):
    """Fixed-arity wrapper for one (quantized, staged) variant: pallas
    passes refs in args order (scales right after the caches, then the
    staged pair, then out + scratch)."""
    def wrapper(lengths_ref, layer_ref, q_ref, k_ref, v_ref, *rest, **kw):
        extra = list(rest[:-4])
        if quantized:
            kw["ks_ref"], kw["vs_ref"] = extra.pop(0), extra.pop(0)
        if staged:
            kw["kn_ref"], kw["vn_ref"] = extra.pop(0), extra.pop(0)
        _decode_kernel(lengths_ref, layer_ref, q_ref, k_ref, v_ref,
                       *rest[-4:], **kw)
    return wrapper


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     softmax_scale: Optional[float] = None,
                     block_k: Optional[int] = None,
                     k_scales: Optional[jnp.ndarray] = None,
                     v_scales: Optional[jnp.ndarray] = None,
                     layer: Optional[jnp.ndarray] = None,
                     k_new: Optional[jnp.ndarray] = None,
                     v_new: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_cache: the stacked cache (L, B, Hkv, M, D) with
    `layer` () int32 the layer to read, or without one a layer's own
    (B, M, Hkv, D); lengths: (B,) valid tokens per row. With `k_new`/`v_new`
    (B, Hkv, D) the LAST valid token is the staged one, not yet in the
    cache: it takes its slot's place in the tile the kernel fetched, so the
    result is bit for bit the token written then attended (and a row with
    `lengths > M`, parked, has no slot: its token is dropped, as the writer
    drops it). Without them the new token's slot must already be written.
    `block_k`: KV slots a grid step (`DEFAULT_BLOCK_K` for a per-layer
    view, `STACK_BLOCK_K` for the stack). Returns (B, 1, H, D).

    `k_scales`/`v_scales` (B, M, Hkv) f32 mark an int8 cache (per-layer
    view only): the kernel folds the per-token scale into the logit /
    probability columns in-register (no dense bf16 cache form ever exists).
    With unit scales the quantized path is bitwise-identical to the
    unquantized kernel on the same cache values."""
    b, s, h, d = q.shape
    assert s == 1, "decode kernel is single-query; use flash_attention for prefill"
    if block_k is None:
        block_k = DEFAULT_BLOCK_K if layer is None else STACK_BLOCK_K
    if layer is None:
        # the per-layer view: re-laid (a copy of the layer) as a stack of one
        k_cache = jnp.swapaxes(k_cache, 1, 2)[None]
        v_cache = jnp.swapaxes(v_cache, 1, 2)[None]
        layer = 0
    hkv, m = k_cache.shape[2], k_cache.shape[3]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    blk_k = min(block_k, m)
    while m % blk_k:
        blk_k -= 1
    nk = m // blk_k
    staged = k_new is not None

    # (B·Hkv, n_rep, D): row-major over heads means head g*n_rep+r of the
    # HF layout is group g, member r — exactly repeat_kv's grouping
    qt2 = jnp.swapaxes(q, 1, 2).reshape(b * hkv, n_rep, d)

    def row(b_, g, j, L, Ly):
        return (b_ * hkv + g, 0, 0)

    def kv_block(b_, j, L):
        # Clamp the block index to this row's last valid block: steps past
        # the row's length revisit the same block, so Pallas elides their
        # HBM copies — THIS is where the bandwidth saving happens (the
        # `pl.when` alone only skips compute, not the DMA).
        last = jnp.maximum((L[b_] + blk_k - 1) // blk_k - 1, 0)
        return jnp.minimum(j, last)

    def kv_index(b_, g, j, L, Ly):
        return (Ly[0], b_, g, kv_block(b_, j, L), 0)

    def kv_scale_index(b_, g, j, L, Ly):
        return (b_ * hkv + g, 0, kv_block(b_, j, L))

    in_specs = [
        pl.BlockSpec((1, n_rep, d), row),
        pl.BlockSpec((None, None, None, blk_k, d), kv_index),
        pl.BlockSpec((None, None, None, blk_k, d), kv_index),
    ]
    args = [lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
            qt2, k_cache, v_cache]
    quantized = k_scales is not None
    if quantized:
        # (B, M, Hkv) → (B·Hkv, 1, M): token scales along lanes, one tile
        # per KV block beside its pool tile (same block). The unit
        # sublane dim is there for Mosaic: a block's second-to-last dim
        # must be a multiple of 8 or span the array's, and one row of
        # (B·Hkv, M) is neither.
        ks2 = jnp.swapaxes(k_scales, 1, 2).reshape(b * hkv, 1, m)
        vs2 = jnp.swapaxes(v_scales, 1, 2).reshape(b * hkv, 1, m)
        in_specs += [pl.BlockSpec((None, 1, blk_k), kv_scale_index),
                     pl.BlockSpec((None, 1, blk_k), kv_scale_index)]
        args += [ks2, vs2]
    if staged:  # one (1, D) row a (b, g): the unit dim spans its array's
        in_specs += [pl.BlockSpec((None, 1, d), row)] * 2
        args += [k_new.reshape(b * hkv, 1, d), v_new.reshape(b * hkv, 1, d)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_rep, d), row),
        scratch_shapes=[pltpu.VMEM((n_rep, 128), jnp.float32),
                        pltpu.VMEM((n_rep, 128), jnp.float32),
                        pltpu.VMEM((n_rep, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_mk_decode_kernel(quantized, staged),
                          scale=scale, blk_k=blk_k, nk=nk, n_rep=n_rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="self_attn_dense_decode",
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
