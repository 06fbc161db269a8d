"""Pallas TPU flash attention (forward + backward).

This is the TPU-native replacement for the reference's attention kernel set:
`csrc/transformer/inference/csrc/softmax.cu` (triangular/causal softmax),
the flash-attn kernels linked by `inference/v2/kernels/ragged_ops/
blocked_flash`, and the training softmax in `csrc/transformer/softmax_kernels.cu`.

Design (standard flash attention 2 tiling, MXU-sized blocks):
- grid (B, H, Sq/blk_q, Sk/blk_k) with the KV block as the fastest
  (sequential) grid axis, online-softmax state (m, l, acc) in VMEM scratch
  carried across KV iterations; a step's blocks are (blk, D) of one head;
- the ORDER the operands lie in. Callers hand over (B, S, H, D), as the
  projections leave them. The FORWARD of a call that is not differentiated (a
  prefill's) reads them so, as (B, S, H x D) views in which a head is a
  column block of D lanes, and writes its result the same way, wherever D is
  a multiple of 128 (a block is then whole lane tiles, H tiles apart: a
  strided DMA); no transpose stands on either side of the kernel. A narrower
  head (64: half a lane tile), and every DIFFERENTIATED call (forward rule
  and backward), run head-major, (B, H, S, D), between transposes. The head
  width and the differentiation decide (`_flash_bshd`); no option does. The
  bodies are the same under both orders' block specs (leading dims squeezed),
  so the values are too, bit for bit. `_fwd` counts its calls by order on
  the telemetry hub (`flash_fwd/token_major`, `flash_fwd/head_major`);
- GQA handled in the kernel's BlockSpec index maps (KV head = q_head // n_rep)
  — no materialized `repeat_kv`;
- causal blocks are predicated out with `pl.when` (upper-triangular block
  tiles never touch the MXU);
- backward = ONE kernel from the saved logsumexp plus delta =
  rowsum(dO * O), the flash-2 recurrence: it walks the live block pairs kv
  block by kv block, computes a pair's s, p, dp and ds once, and sums all
  three gradients from them — dk/dv in a scratch a kv block, dq in a float32
  scratch that holds the (batch, head)'s WHOLE query length (0.5 MB at
  2048 x 64), zeroed at the walk's first step and written at its last. Five
  products a pair. Past `ONE_PASS_DQ_BYTES` of resident dq (long-sequence
  training) `_bwd` takes the two-pass form, separate dq and dk/dv kernels
  that each compute the score tile (seven products), and says so
  (`flash_bwd_two_pass`).

Forward returns logsumexp as a residual for the backward pass.

A static `window` BANDS the causal forward (a query at t sees keys t - window
+ 1 .. t: a prefill's window layers): a query block VISITS only the key
blocks its band touches (`_band_blocks`: the grid's last axis is as long as
the widest band, dead blocks are neither fetched nor computed), the blocks on
the band's two edges are masked, those between run the unmasked update. It is
traced as `BAND_NAME`. With `window=None` nothing here differs from the
kernel without a band, instruction for instruction. The BACKWARD under a
window does not exist (`_flash_bwd_rule` raises by name): no cell trains a
window family (ROADMAP.md, B6).

A CHUNK of one cached row (`flash_prefill_chunk`: a prefill that walks a row
a chunk at a time, `models/hybrid.prefill_walk`): the chunk's queries against
the row's K and V WHERE THEY LIE in the stacked dense cache, (L, B, Hkv, M,
D), keys 0 .. start + i for query i, `start` a traced scalar. It is
`_fwd_kernel`'s body with the causal offset read from a prefetched scalar
(the layer and the row beside it, for the index maps): key blocks past the
chunk's end are neither fetched nor computed. Same trace name (`FWD_NAME`).
A program that does not call it is what it was.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret

# 1024 sweeps ~6% faster than 512 on v5e at seq 2048 (bench block sweep);
# 2048 overflows VMEM with the fp32 (blk_q, blk_k) logits tile.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# The backward is ONE kernel while a (batch, head)'s whole dq, (sq, d)
# float32 with d padded to a lane row, fits this many bytes of VMEM beside
# the dk/dv walk: sq up to 16k at d <= 128. Past it `_bwd` takes the
# two-pass form. Compiled for a described v5e (PR 55): the walk's own tiles
# at blocks of 1024 take 10.3 MiB of the 16 MiB a kernel gets by default,
# and the resident dq costs its scratch and a double-buffered output block
# on top (8 bytes an element at bf16), so 4096 x 128 fits the default, 8192
# does not (18.3 MiB) and the one-pass call asks for SCOPED_VMEM_BYTES plus
# what it keeps. On the chip at 16384 x 128 (the scratch at this budget, a
# 32 MiB limit of the 128 MiB the core has): 17.8 ms a call against the
# two-pass form's 24.6, gradients bit for bit. Longer was not measured.
ONE_PASS_DQ_BYTES = 8 * 1024 * 1024
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
NEG_INF = -1e30
FWD_NAME = "self_attn_flash_fwd"
BAND_NAME = "self_attn_flash_fwd_band"      # the forward under a `window`
# Query / key block of the BANDED forward. A query block of `blk` rows meets
# `blk + window - 1` keys, which whole blocks cover with up to
# `window / blk + 1` of them: at window 2,048, 3 blocks of 1024 (3,072 key
# columns for 2,048 live ones a query) or 5 of 512 (2,560). The fewer columns
# lose: on the chip (PERF.md, PR 60, `tools/flash_band_sweep.py`: two rows of
# 8,192, 32 heads on 4 of 128) a call reads 6.53 ms at 1024 x 1024, 7.04 at
# 512 x 1024, 9.00 at 1024 x 512, 9.72 at 512 x 512 and 17.48 at 256 x 256,
# beside 9.80 for the plain causal kernel: a block pair's fixed cost and the
# narrower products outweigh the dead columns.
BAND_BLOCK = 1024
# The kernels work in the BASE-2 exponent domain: log2(e)·softmax_scale is
# folded into q once outside, p = exp2(s2 − m2), and the saved lse residual
# is base-2 (lse2 = m2 + log2(l)) — one fewer VPU multiply per element in
# the (blk_q, blk_k) tile, which is where this kernel's time goes at d=128.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def _tri_row(t, n):
    """Row-major lower-triangle enumeration: step t → (i, j), j ≤ i < n.
    Float sqrt with integer correction (exact for the grid sizes in play)."""
    tf = t.astype(jnp.float32)
    i = ((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    i = jnp.where(t < i * (i + 1) // 2, i - 1, i)
    i = jnp.where(t >= (i + 1) * (i + 2) // 2, i + 1, i)
    i = jnp.clip(i, 0, n - 1)
    return i, t - i * (i + 1) // 2


def _tri_col(t, n):
    """Column-major lower-triangle enumeration: step t → (i, j) with
    j ≤ i < n, j outer and i inner (the dk/dv accumulation order)."""
    tf = t.astype(jnp.float32)
    nf = float(n)
    j = (nf + 0.5 - jnp.sqrt((nf + 0.5) ** 2 - 2.0 * tf)).astype(jnp.int32)

    def base(jj):
        return jj * n - jj * (jj - 1) // 2
    j = jnp.where(t < base(j), j - 1, j)
    j = jnp.where(t >= base(j + 1), j + 1, j)
    j = jnp.clip(j, 0, n - 1)
    return j + (t - base(j)), j


def _apply_causal_mask(s, mask_ij, window=None):
    """Mask score block `s` to ki <= qi when `mask_ij` = (qi_base, ki_base),
    and under a `window` to qi - window < ki as well; identity when None.
    ONE definition — fwd and both bwd kernels must stay mask-consistent."""
    if mask_ij is None:
        return s
    qi_base, ki_base = mask_ij
    blk_q, blk_k = s.shape
    qi = qi_base + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    ki = ki_base + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    keep = ki <= qi
    if window is not None:
        keep = jnp.logical_and(keep, ki > qi - window)
    return jnp.where(keep, s, NEG_INF)


def _fwd_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, mask_ij=None,
                window=None):
    """One online-softmax step over the current (blk_q, blk_k) block pair.
    q arrives PRE-SCALED by log2(e)·softmax_scale; the whole recurrence
    runs in the base-2 domain. `mask_ij` = (qi_base, ki_base) applies the
    causal mask (and the `window`'s) — only edge blocks pay for
    iota+compare+select."""
    s = jax.lax.dot_general(q_ref[...], k_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = _apply_causal_mask(s, mask_ij, window)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp2(s - m_new)
    if window is not None and mask_ij is not None:
        # a band's edge block may hold NO key of a query's (its window
        # starts further on): the row's max is then NEG_INF and exp2(0)
        # would count every masked column
        p = jnp.where(s > NEG_INF, p, 0.0)
    alpha = jnp.exp2(m_prev - m_new)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:, :1] = m_new


def _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
    # base-2 lse residual: lse2 = m2 + log2(l); the bwd kernels consume it
    # with exp2 directly
    lse_ref[...] = m_scr[:, :1] + jnp.log2(safe_l)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, blk_q, blk_k, nk, offset=0):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    args = (q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr)
    if not causal:
        _fwd_update(*args)
    else:
        full = j * blk_k + blk_k - 1 <= i * blk_q + offset
        partial = jnp.logical_and(
            jnp.logical_not(full),
            j * blk_k <= i * blk_q + blk_q - 1 + offset)

        @pl.when(full)
        def _full():
            _fwd_update(*args)

        @pl.when(partial)
        def _partial():
            _fwd_update(*args, mask_ij=(offset + i * blk_q, j * blk_k))

    @pl.when(j == nk - 1)
    def _finalize():
        _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _band_blocks(i, blk_q, blk_k, window):
    """(first, last) key block that query block `i`'s band touches: keys
    `i * blk_q - window + 1 .. i * blk_q + blk_q - 1`, none before 0."""
    return (jnp.maximum(i * blk_q - window + 1, 0) // blk_k,
            (i * blk_q + blk_q - 1) // blk_k)


def _fwd_kernel_band(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, blk_q, blk_k, nb, window):
    """The causal forward under a `window` over (b, h, nq, nb): step `j` of
    query block `i` is key block `first + j` of its band, the steps past the
    band's last block are skipped (their index is clamped: no fetch)."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    first, last = _band_blocks(i, blk_q, blk_k, window)
    kb = first + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    args = (q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr)
    # every key of the block at or before the block's first query, and after
    # its last query's window's start: no mask
    full = jnp.logical_and(kb * blk_k + blk_k - 1 <= i * blk_q,
                           kb * blk_k > i * blk_q + blk_q - 1 - window)

    @pl.when(jnp.logical_and(kb <= last, full))
    def _full():
        _fwd_update(*args)

    @pl.when(jnp.logical_and(kb <= last, jnp.logical_not(full)))
    def _edge():
        _fwd_update(*args, mask_ij=(i * blk_q, kb * blk_k), window=window)

    @pl.when(j == nb - 1)
    def _finalize():
        _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _fwd_kernel_tri(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, blk, n):
    """Causal forward over a TRIANGULAR grid: the linear axis enumerates
    only the nq·(nq+1)/2 live block pairs (row-major), so causally-dead
    (i, j) pairs cost nothing — the rectangular causal grid spent ~45% of
    its steps on them. Requires blk_q == blk_k and sq == sk."""
    t = pl.program_id(2)
    i, j = _tri_row(t, n)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    args = (q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(j < i)
    def _interior():
        _fwd_update(*args)

    @pl.when(j == i)
    def _diag():
        _fwd_update(*args, mask_ij=(i * blk, j * blk))
        _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bwd_update(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
                dq_scr=None, dq_block=None, dk_scr=None, dv_scr=None,
                mask_ij=None, delta_from_o=False):
    """The backward of one block pair: s, p, dp and ds are computed ONCE
    and feed whichever float32 accumulators the kernel holds — `dq_scr`
    (all of it, or query block `dq_block`'s rows of a resident (sq, d)
    one), `dk_scr`, `dv_scr`. qs arrives pre-scaled (base-2 domain):
    p = exp2(s2 − lse2) is the exact softmax probability and ds_raw carries
    no scale, so dq takes softmax_scale and dk takes ln2
    (dL/dk = scale·ds_rawᵀ·q = ln2·ds_rawᵀ·qs) once, at finalize."""
    q, k = q_ref[0, 0], k_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = _apply_causal_mask(s, mask_ij)
    p = jnp.exp2(s - lse_ref[0, 0])  # (blk_q, blk_k)
    if dv_scr is not None:
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if delta_from_o:  # `delta_ref` is the forward's output block
        delta = jnp.sum(do * delta_ref[0, 0].astype(jnp.float32), axis=-1,
                        keepdims=True)
    else:
        delta = delta_ref[0, 0]
    ds = p * (dp - delta)
    if dk_scr is not None:
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if dq_scr is not None:
        rows = slice(None) if dq_block is None else pl.ds(
            pl.multiple_of(dq_block * q.shape[0], q.shape[0]), q.shape[0])
        dq_scr[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, scale, causal, blk_q, blk_k, nk, offset=0):
    """dq alone over (b, h, nq, nk): the first of the TWO-PASS backward's
    kernels, which `_bwd` keeps for query lengths whose dq no longer fits
    in VMEM beside the dk/dv walk."""
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    update = functools.partial(_bwd_update, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dq_scr=dq_scr)
    if not causal:
        update()
    else:
        full = j * blk_k + blk_k - 1 <= i * blk_q + offset
        partial = jnp.logical_and(
            jnp.logical_not(full),
            j * blk_k <= i * blk_q + blk_q - 1 + offset)

        @pl.when(full)
        def _full():
            update()

        @pl.when(partial)
        def _partial():
            update(mask_ij=(offset + i * blk_q, j * blk_k))

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dq_kernel_tri(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, blk, n):
    """Two-pass causal dq over the triangular grid (see _fwd_kernel_tri)."""
    t = pl.program_id(2)
    i, j = _tri_row(t, n)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    update = functools.partial(_bwd_update, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dq_scr=dq_scr)

    @pl.when(j < i)
    def _interior():
        update()

    @pl.when(j == i)
    def _diag():
        update(mask_ij=(i * blk, j * blk))
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _walk_refs(refs, one_pass):
    """(dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) of a dk/dv walk's
    outputs and scratch; the dq pair is None in the two-pass form."""
    return refs if one_pass else (None, *refs[:2], None, *refs[2:])


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, one_pass, causal, blk_q, blk_k, nq, nk, offset=0):
    """The dk/dv walk over (b, h, nk, nq), q blocks innermost. In the
    ONE-PASS backward dq joins it: a float32 scratch holds the whole query
    length of the (batch, head), zeroed at the walk's first step and
    written at its last; a block pair's `ds` feeds all three gradients, and
    `delta_ref` is the forward's output block. Else this is the second
    kernel of the two-pass form."""
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = _walk_refs(refs, one_pass)
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block (sequential axis)

    if one_pass:
        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    update = functools.partial(
        _bwd_update, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        dq_scr=dq_scr, dq_block=i, dk_scr=dk_scr, dv_scr=dv_scr,
        delta_from_o=one_pass)
    if not causal:
        update()
    else:
        # a kv block is fully unmasked for q block i when every qi in the
        # block is at or past the block's last key
        full = j * blk_k + blk_k - 1 <= i * blk_q + offset
        partial = jnp.logical_and(
            jnp.logical_not(full),
            i * blk_q + blk_q - 1 + offset >= j * blk_k)

        @pl.when(full)
        def _full():
            update()

        @pl.when(partial)
        def _partial():
            update(mask_ij=(offset + i * blk_q, j * blk_k))

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_scr[:] * LN2).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)

    if one_pass:
        @pl.when(jnp.logical_and(i == nq - 1, j == nk - 1))
        def _finalize_dq():
            dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_kernel_tri(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale, one_pass, blk, n):
    """The causal dk/dv walk over the triangular grid: column-major
    enumeration — for kv block j, q blocks i = j..n−1 (the diagonal block
    first). `one_pass` as in `_bwd_kernel`: dq joins the walk (a query
    block's dq sums its kv blocks in ascending j, the two-pass order)."""
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = _walk_refs(refs, one_pass)
    t = pl.program_id(2)
    i, j = _tri_col(t, n)

    if one_pass:
        @pl.when(t == 0)
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    update = functools.partial(
        _bwd_update, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        dq_scr=dq_scr, dq_block=i, dk_scr=dk_scr, dv_scr=dv_scr,
        delta_from_o=one_pass)

    @pl.when(i == j)
    def _init_and_diag():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        update(mask_ij=(i * blk, j * blk))

    @pl.when(i > j)
    def _interior():
        update()

    @pl.when(i == n - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_scr[:] * LN2).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)

    if one_pass:
        @pl.when(t == n * (n + 1) // 2 - 1)
        def _finalize_dq():
            dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _pick_blocks(sq, sk, blk_q, blk_k):
    def fit(s, blk):
        blk = min(blk, s)
        while s % blk:  # largest divisor of s not above blk
            blk -= 1
        return blk
    return fit(sq, blk_q), fit(sk, blk_k)


def _use_tri(causal, sq, sk, blk_q, blk_k):
    return causal and sq == sk and blk_q == blk_k


def _fwd(qs, k, v, causal, blk_q, blk_k, window=None, token_major=False):
    """qs is the pre-scaled query (log2(e)·softmax_scale folded in).
    Head-major: operands and result (B, H, S, D). `token_major`: (B, S, H, D)
    ones, the projections' own order, read through their (B, S, H x D) views,
    where a head is a column block of D lanes (D a multiple of 128: whole
    lane tiles, a strided DMA) and the result is written the same way. The
    lse is (B, H, Sq, 1) in both. The order changes the block specs and
    nothing else: one grid, one body, one arithmetic a form."""
    if token_major:
        b, sq, h, d = qs.shape
        sk, hkv = k.shape[1], k.shape[2]
        assert d % 128 == 0, d
        qs, k, v = (t.reshape(*t.shape[:2], -1) for t in (qs, k, v))
    else:
        b, h, sq, d = qs.shape
        hkv, sk = k.shape[1], k.shape[2]
    _count_forward(token_major)
    n_rep = h // hkv
    blk_q, blk_k = _pick_blocks(sq, sk, blk_q, blk_k)
    assert sq % blk_q == 0 and sk % blk_k == 0, (sq, sk, blk_q, blk_k)
    nq, nk = sq // blk_q, sk // blk_k
    offset = sk - sq
    name = FWD_NAME

    if window is not None:
        assert causal and sq == sk, "a window bands whole causal sequences"
        # the widest band, in key blocks: the grid's last axis
        nb = max((i * blk_q + blk_q - 1) // blk_k
                 - max(i * blk_q - window + 1, 0) // blk_k + 1
                 for i in range(nq))
        kernel = functools.partial(_fwd_kernel_band, blk_q=blk_q,
                                   blk_k=blk_k, nb=nb, window=window)
        grid, name = (b, h, nq, nb), BAND_NAME

        def q_at(b_, h_, i, j):
            return b_, h_, i

        def kv_at(b_, h_, i, j):
            first, last = _band_blocks(i, blk_q, blk_k, window)
            return b_, h_ // n_rep, jnp.minimum(first + j, last)
    elif _use_tri(causal, sq, sk, blk_q, blk_k):
        kernel = functools.partial(_fwd_kernel_tri, blk=blk_q, n=nq)
        grid = (b, h, nq * (nq + 1) // 2)

        def q_at(b_, h_, t):
            return b_, h_, _tri_row(t, nq)[0]

        def kv_at(b_, h_, t):
            return b_, h_ // n_rep, _tri_row(t, nq)[1]
    else:
        kernel = functools.partial(_fwd_kernel, causal=causal, blk_q=blk_q,
                                   blk_k=blk_k, nk=nk, offset=offset)
        grid = (b, h, nq, nk)

        def q_at(b_, h_, i, j):
            return b_, h_, i

        def kv_at(b_, h_, i, j):
            if causal:
                # clamp dead kv blocks to the diagonal one: the repeated
                # index makes Pallas elide their HBM copies — without it
                # every q row fetches the full KV length and HALF the DMA
                # traffic is causally dead
                hi = (i * blk_q + blk_q - 1 + offset) // blk_k
                j = jnp.minimum(j, hi)
            return b_, h_ // n_rep, j

    def rows_spec(rows, at):
        """`rows` rows of one head, `at` the grid's (batch, head, row
        block): the leading dims squeezed, so a body sees (rows, d)
        whichever order the operand lies in."""
        if token_major:
            def ix(*g):
                b_, h_, r = at(*g)
                return b_, r, h_
            return pl.BlockSpec((None, rows, d), ix)
        return pl.BlockSpec((None, None, rows, d), lambda *g: (*at(*g), 0))

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[rows_spec(blk_q, q_at), rows_spec(blk_k, kv_at),
                  rows_spec(blk_k, kv_at)],
        out_specs=[rows_spec(blk_q, q_at),
                   pl.BlockSpec((None, None, blk_q, 1),
                                lambda *g: (*q_at(*g), 0))],
        out_shape=[jax.ShapeDtypeStruct(qs.shape, qs.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk_q, 128), jnp.float32),
                        pltpu.VMEM((blk_q, 128), jnp.float32),
                        pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",)),
        interpret=_interpret(),
        name=name,
    )(qs, k, v)
    return (out.reshape(b, sq, h, d) if token_major else out), lse


def _fwd_kernel_chunk(at_ref, *refs, blk_q, blk_k, nk):
    """`_fwd_kernel` (causal) with the offset a prefetched scalar: `at_ref`
    holds (layer, row, start), the first two for the index maps."""
    _fwd_kernel(*refs, causal=True, blk_q=blk_q, blk_k=blk_k, nk=nk,
                offset=at_ref[2])


def lane_block(m: int, cap: int) -> int:
    """Key slots a block of a cached row of `m`: the largest divisor up to
    `cap`, in whole lane tiles of 128 where `m` has such a divisor."""
    fits = [x for x in range(min(m, cap), 0, -1) if m % x == 0]
    return next((x for x in fits if x % 128 == 0), fits[0])


def flash_prefill_chunk(q, k_stack, v_stack, layer, row, start,
                        softmax_scale: Optional[float] = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K) -> jnp.ndarray:
    """A chunk's causal attention over ONE row of the stacked dense cache.

    q (C, H, D), the queries of positions `start .. start + C - 1` of
    sequence `row`; k_stack / v_stack (L, B, Hkv, M, D), which hold that
    row's keys and values of layer `layer` up to the chunk's last position;
    `layer`, `row`, `start` ints or () int32. Query i sees slots 0 .. start
    + i. Returns (C, H, D). The stacks are read in place, a block (blk_k, D)
    of one KV head at a time; the queries and the result go head-major (a
    chunk's transposes, 17 MB at 2,048 x 16 x 256)."""
    c, h, d = q.shape
    hkv, m = k_stack.shape[2], k_stack.shape[3]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    blk_q = _pick_blocks(c, c, block_q, block_q)[0]
    blk_k = lane_block(m, block_k)
    nq, nk = c // blk_q, m // blk_k
    qs = jnp.swapaxes((q * (scale * LOG2E)).astype(q.dtype), 0, 1)
    at = jnp.stack([jnp.asarray(t, jnp.int32).reshape(())
                    for t in (layer, row, start)])

    # `_fwd_kernel`'s grid, (batch, head, query block, key block): a batch
    # of the one row
    def q_at(_, h_, i, j, at):
        return h_, i, 0

    def kv_at(_, h_, i, j, at):
        # dead blocks clamp to the chunk's last live one: no fetch
        hi = (i * blk_q + blk_q - 1 + at[2]) // blk_k
        return at[0], at[1], h_ // n_rep, jnp.minimum(j, hi), 0

    kv_spec = pl.BlockSpec((None, None, None, blk_k, d), kv_at)
    _count_forward(False)
    out, _ = pl.pallas_call(
        functools.partial(_fwd_kernel_chunk, blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1, h, nq, nk),
            in_specs=[pl.BlockSpec((None, blk_q, d), q_at), kv_spec, kv_spec],
            out_specs=[pl.BlockSpec((None, blk_q, d), q_at),
                       pl.BlockSpec((None, blk_q, 1), q_at)],
            scratch_shapes=[pltpu.VMEM((blk_q, 128), jnp.float32),
                            pltpu.VMEM((blk_q, 128), jnp.float32),
                            pltpu.VMEM((blk_q, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(qs.shape, qs.dtype),
                   jax.ShapeDtypeStruct((h, c, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=_interpret(),
        name=FWD_NAME,
    )(at, qs, k_stack, v_stack)
    return jnp.swapaxes(out, 0, 1)


def _count_forward(token_major):
    """Count, at trace time, the forward calls by the order their operands
    lay in (`flash_fwd/token_major`, `flash_fwd/head_major` on the telemetry
    hub), so a run can say which form its prefill or its step took. A count
    of TRACES: a body that `scan` or `jax.checkpoint` traces before it is
    differentiated traces the primal once and never lowers it, so a training
    step at a lane-wide head reads `token_major` 1 beside the `head_major`
    calls it runs."""
    from deepspeed_tpu.telemetry import get_hub
    get_hub().counter(
        "flash_fwd/" + ("token_major" if token_major else "head_major"))


def _announce_two_pass(sq, d):
    """The two-pass backward ran for length: say so once a shape (the
    shared `warn_once` registry) and on the telemetry hub, so a
    long-sequence user can see which form their step took."""
    from deepspeed_tpu.utils.logging import warn_once
    warn_once(("flash_bwd_two_pass", sq, d),
              f"flash attention backward: the float32 dq of {sq} queries x "
              f"{d} passes ONE_PASS_DQ_BYTES={ONE_PASS_DQ_BYTES} of VMEM; "
              "taking the two-pass form (separate dq and dk/dv kernels, the "
              "score tile computed twice)")
    try:
        from deepspeed_tpu.telemetry import get_hub
        hub = get_hub()
        if hub.enabled:
            hub.emit("flash_bwd_two_pass", sq=sq, d=d)
    except Exception:  # telemetry must never break a trace
        pass


def _bwd(qs, k, v, o, lse, do, scale, causal, blk_q, blk_k):
    """qs is the pre-scaled query (matches the saved forward residual)."""
    b, h, sq, d = qs.shape
    hkv, sk = k.shape[1], k.shape[2]
    n_rep = h // hkv
    blk_q, blk_k = _pick_blocks(sq, sk, blk_q, blk_k)
    nq, nk = sq // blk_q, sk // blk_k
    offset = sk - sq

    tri = _use_tri(causal, sq, sk, blk_q, blk_k)
    dq_lanes = sq * -(-d // 128) * 128  # VMEM pads the minor dim to a lane row
    one_pass = dq_lanes * 4 <= ONE_PASS_DQ_BYTES
    if one_pass:  # delta is made in the kernel, a pair at a time, from o
        operands = (qs, k, v, do, lse, o)
    else:
        _announce_two_pass(sq, d)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)  # (b,h,sq,1)
        operands = (qs, k, v, do, lse, delta)
    dq_shape = jax.ShapeDtypeStruct((b, h, sq, d), qs.dtype)

    def call(kernel, grid, in_specs, out_specs, out_shape, scratch,
             carried=1, vmem_limit=None):
        """`carried` trailing grid axes carry an accumulator (sequential)."""
        semantics = (("parallel",) * (len(grid) - carried)
                     + ("arbitrary",) * carried)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=vmem_limit),
            interpret=_interpret(), name="self_attn_flash_bwd")(*operands)

    def pair_specs(q_ix, kv_ix):
        """in_specs of `operands` for a walk's index maps."""
        q_spec = pl.BlockSpec((1, 1, blk_q, d), q_ix)
        kv_spec = pl.BlockSpec((1, 1, blk_k, d), kv_ix)
        row_spec = pl.BlockSpec((1, 1, blk_q, 1), q_ix)
        return [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                q_spec if one_pass else row_spec]

    if not one_pass:  # dq in a kernel of its own, q blocks outermost
        if tri:
            dq_kernel = functools.partial(_dq_kernel_tri, scale=scale,
                                          blk=blk_q, n=nq)
            dq_grid = (b, h, nq * (nq + 1) // 2)

            def q_ix(b_, h_, t):
                return (b_, h_, _tri_row(t, nq)[0], 0)

            def kv_ix(b_, h_, t):
                return (b_, h_ // n_rep, _tri_row(t, nq)[1], 0)
        else:
            dq_kernel = functools.partial(
                _dq_kernel, scale=scale, causal=causal, blk_q=blk_q,
                blk_k=blk_k, nk=nk, offset=offset)
            dq_grid = (b, h, nq, nk)

            def q_ix(b_, h_, i, j):
                return (b_, h_, i, 0)

            def kv_ix(b_, h_, i, j):
                if causal:  # elide causally-dead kv DMAs (see _fwd)
                    hi = (i * blk_q + blk_q - 1 + offset) // blk_k
                    j = jnp.minimum(j, hi)
                return (b_, h_ // n_rep, j, 0)
        dq = call(dq_kernel, dq_grid, pair_specs(q_ix, kv_ix),
                  pl.BlockSpec((1, 1, blk_q, d), q_ix), dq_shape,
                  [pltpu.VMEM((blk_q, d), jnp.float32)])

    # the dk/dv walk, kv blocks outermost: one (dk, dv) per *query* head,
    # summed over the GQA group outside; in the one-pass form dq rides it
    if tri:
        kernel = functools.partial(_bwd_kernel_tri, scale=scale,
                                   one_pass=one_pass, blk=blk_q, n=nq)
        grid = (b, h, nq * (nq + 1) // 2)

        def q_ix(b_, h_, t):
            return (b_, h_, _tri_col(t, nq)[0], 0)

        def kv_ix(b_, h_, t):
            return (b_, h_ // n_rep, _tri_col(t, nq)[1], 0)

        def kvout_ix(b_, h_, t):
            return (b_, h_, _tri_col(t, nq)[1], 0)
    else:
        kernel = functools.partial(
            _bwd_kernel, scale=scale, one_pass=one_pass, causal=causal,
            blk_q=blk_q, blk_k=blk_k, nq=nq, nk=nk, offset=offset)
        grid = (b, h, nk, nq)

        def q_ix(b_, h_, j, i):
            if causal:  # elide q/do/delta DMAs above the diagonal
                lo = jnp.maximum((j * blk_k - offset) // blk_q, 0)
                i = jnp.maximum(i, lo)
            return (b_, h_, i, 0)

        def kv_ix(b_, h_, j, i):
            return (b_, h_ // n_rep, j, 0)

        def kvout_ix(b_, h_, j, i):
            return (b_, h_, j, 0)
    kvout_spec = pl.BlockSpec((1, 1, blk_k, d), kvout_ix)
    out_specs = [kvout_spec, kvout_spec]
    out_shape = [jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)] * 2
    scratch = [pltpu.VMEM((blk_k, d), jnp.float32)] * 2
    if one_pass:
        # the whole query length's dq, resident for the (batch, head)'s walk
        out_specs.insert(0, pl.BlockSpec((1, 1, sq, d),
                                         lambda b_, h_, *_: (b_, h_, 0, 0)))
        out_shape.insert(0, dq_shape)
        scratch.insert(0, pltpu.VMEM((sq, d), jnp.float32))
    # dq sums over the kv blocks too: both axes of a rectangular walk carry
    outs = call(kernel, grid, pair_specs(q_ix, kv_ix), out_specs, out_shape,
                scratch, carried=2 if one_pass and not tri else 1,
                vmem_limit=(SCOPED_VMEM_BYTES + dq_lanes * (
                    4 + 2 * qs.dtype.itemsize)) if one_pass else None)
    if one_pass:
        dq, dk_full, dv_full = outs
    else:
        dk_full, dv_full = outs

    if n_rep > 1:
        dk = dk_full.reshape(b, hkv, n_rep, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(b, hkv, n_rep, sk, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full.astype(k.dtype), dv_full.astype(v.dtype)
    return dq, dk, dv


def _swap(t):
    """(B, S, H, D) <-> (B, H, S, D)."""
    return jnp.swapaxes(t, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bshd(q, k, v, scale, causal, blk_q, blk_k, window=None):
    """Flash attention of the token-major arrays, q (B, Sq, H, D), k and v
    (B, Sk, Hkv, D). This PRIMAL is the call that is not differentiated (a
    prefill): where a head is whole lane tiles the kernel takes the arrays
    as they lie, and no transpose stands on either side of it; a narrower
    head goes head-major. A differentiated call, and its rematerialised
    forward, run `_flash_fwd_rule`, which is head-major whatever the
    width."""
    if q.shape[-1] % 128:
        return _swap(_flash_bhsd_fwd(q, k, v, scale, causal, blk_q, blk_k,
                                     window)[0])
    # fold softmax scale AND the base-2 conversion into q once
    qs = (q * (scale * LOG2E)).astype(q.dtype)
    return _fwd(qs, k, v, causal, blk_q, blk_k, window, token_major=True)[0]


def _flash_bhsd_fwd(q, k, v, scale, causal, blk_q, blk_k, window=None):
    """The head-major forward of token-major arrays: (out, lse, qs, k, v),
    all (B, H, S, D) but the lse."""
    q, k, v = _swap(q), _swap(k), _swap(v)
    qs = (q * (scale * LOG2E)).astype(q.dtype)
    out, lse = _fwd(qs, k, v, causal, blk_q, blk_k, window)
    return out, lse, qs, k, v


def _flash_fwd_rule(q, k, v, scale, causal, blk_q, blk_k, window):
    from jax.ad_checkpoint import checkpoint_name
    if window is not None:
        raise NotImplementedError(
            "flash_attention(window=): the banded flash BACKWARD does not "
            "exist (the forward serves a prefill's window layers; train a "
            "window family through ops.attention.attention, whose window "
            "runs XLA's masked paths)")
    out, lse, qs, k, v = _flash_bhsd_fwd(q, k, v, scale, causal, blk_q, blk_k)
    # name the two residuals only the backward needs (one kernel; two past
    # ONE_PASS_DQ_BYTES of resident dq: module docstring), so remat
    # policies can save/offload them instead of re-running the fwd kernel
    # (models/llama.py::_remat_policy: 'flash_resid' [the big attention
    # output] offloads to pinned host under 'host_offload' and is kept in
    # HBM under 'checkpoint_dots', where it takes this kernel out of the
    # backward: 96 calls a step and not 192, 589.5 ms and not 615.5, on
    # one v5e, PR 44, seed 4400011001; 'flash_lse' [4 MB/layer at 128k]
    # always saves in HBM — offloading it trips an XLA host-offload
    # compiler bug on a reduce with 2 operands; qs/k/v regenerate from the
    # block input)
    out = checkpoint_name(out, "flash_resid")
    lse = checkpoint_name(lse, "flash_lse")
    return _swap(out), (qs, k, v, out, lse)


def _flash_bwd_rule(scale, causal, blk_q, blk_k, window, res, do):
    del window  # None here: the forward rule refuses a window by name
    qs, k, v, o, lse = res  # qs pre-scaled; _bwd rescales dq at finalize
    return tuple(_swap(g) for g in _bwd(qs, k, v, o, lse, _swap(do), scale,
                                        causal, blk_q, blk_k))


_flash_bshd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Flash attention. q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) → (B, Sq, H, D).

    The operands stay in that order, the projections' own, where the
    forward kernel can read them so: a head width that is whole lane tiles
    (D a multiple of 128) in a call that is not differentiated. A narrower
    head, and every differentiated call with its backward, run head-major
    between transposes (`_flash_bshd`). The shape and the differentiation
    decide; nothing else does, and the values are the same bit for bit.

    Block sizes: explicit args > DS_TPU_FLASH_BLOCK_Q/K env (bench sweeps) >
    defaults (`BAND_BLOCK` under a window).

    `window` (static; causal whole sequences, Sq == Sk): the BANDED forward
    (the module text); forward only. A window that covers the sequence bands
    nothing and is the plain causal kernel."""
    if window is not None and window >= q.shape[1]:
        window = None
    if window is not None and block_q is None and block_k is None:
        block_q = block_k = BAND_BLOCK
    if block_q is None:
        block_q = int(os.environ.get("DS_TPU_FLASH_BLOCK_Q", DEFAULT_BLOCK_Q))
    if block_k is None:
        block_k = int(os.environ.get("DS_TPU_FLASH_BLOCK_K", DEFAULT_BLOCK_K))
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    return _flash_bshd(q, k, v, scale, causal, block_q, block_k, window)
