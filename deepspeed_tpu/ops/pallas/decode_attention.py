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

Layout: q (B, 1, H, D); cache (B, M, Hkv, D) as stored by
`inference/kv_cache.py`. KV-block axis sequential, online-softmax state in
VMEM scratch.
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

DEFAULT_BLOCK_K = 512


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, blk_k, nk, n_rep,
                   ks_ref=None, vs_ref=None):
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
        k = k_ref[0]                         # (blk_k, D)
        v = v_ref[0]
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


def _decode_kernel_quant(lengths_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                         o_ref, m_scr, l_scr, acc_scr, **kw):
    _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, ks_ref=ks_ref, vs_ref=vs_ref, **kw)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     softmax_scale: Optional[float] = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     k_scales: Optional[jnp.ndarray] = None,
                     v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_cache: (B, M, Hkv, D); lengths: (B,) valid
    tokens per row (the new token's slot must already be written).
    Returns (B, 1, H, D).

    `k_scales`/`v_scales` (B, M, Hkv) f32 mark an int8 cache: the kernel
    folds the per-token scale into the logit / probability columns
    in-register (no dense bf16 cache form ever exists). With unit scales
    the quantized path is bitwise-identical to the unquantized kernel on
    the same cache values."""
    b, s, h, d = q.shape
    assert s == 1, "decode kernel is single-query; use flash_attention for prefill"
    m, hkv = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    blk_k = min(block_k, m)
    while m % blk_k:
        blk_k -= 1
    nk = m // blk_k

    # (B, Hkv, n_rep, D): row-major over heads means head g*n_rep+r of the
    # HF layout is group g, member r — exactly repeat_kv's grouping
    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, n_rep, d)
    kt = jnp.swapaxes(k_cache, 1, 2)  # (B, Hkv, M, D)
    vt = jnp.swapaxes(v_cache, 1, 2)

    # collapse (B, Hkv) so index maps stay gather-free
    qt2 = qt.reshape(b * hkv, n_rep, d)
    kt2 = kt.reshape(b * hkv, m, d)
    vt2 = vt.reshape(b * hkv, m, d)

    def kv_index(b_, g, j, L):
        # Clamp the block index to this row's last valid block: steps past
        # the row's length revisit the same block, so Pallas elides their
        # HBM copies — THIS is where the bandwidth saving happens (the
        # `pl.when` alone only skips compute, not the DMA).
        last = jnp.maximum((L[b_] + blk_k - 1) // blk_k - 1, 0)
        return (b_ * hkv + g, jnp.minimum(j, last), 0)

    def kv_scale_index(b_, g, j, L):
        row, blk, _ = kv_index(b_, g, j, L)
        return (row, 0, blk)

    in_specs = [
        pl.BlockSpec((1, n_rep, d), lambda b_, g, j, L: (b_ * hkv + g, 0, 0)),
        pl.BlockSpec((1, blk_k, d), kv_index),
        pl.BlockSpec((1, blk_k, d), kv_index),
    ]
    args = [lengths.astype(jnp.int32), qt2, kt2, vt2]
    quantized = k_scales is not None
    if quantized:
        # (B, M, Hkv) → (B·Hkv, 1, M): token scales along lanes, one tile
        # per KV block beside its pool tile (same index map). The unit
        # sublane dim is there for Mosaic: a block's second-to-last dim
        # must be a multiple of 8 or span the array's, and one row of
        # (B·Hkv, M) is neither.
        ks2 = jnp.swapaxes(k_scales, 1, 2).reshape(b * hkv, 1, m)
        vs2 = jnp.swapaxes(v_scales, 1, 2).reshape(b * hkv, 1, m)
        in_specs += [pl.BlockSpec((None, 1, blk_k), kv_scale_index),
                     pl.BlockSpec((None, 1, blk_k), kv_scale_index)]
        args += [ks2, vs2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_rep, d),
                               lambda b_, g, j, L: (b_ * hkv + g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((n_rep, 128), jnp.float32),
                        pltpu.VMEM((n_rep, 128), jnp.float32),
                        pltpu.VMEM((n_rep, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel_quant if quantized else _decode_kernel,
                          scale=scale, blk_k=blk_k, nk=nk, n_rep=n_rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="self_attn_dense_decode",
    )(*args)
    return out.reshape(b, 1, h, d)
