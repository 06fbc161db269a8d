"""Grouped (ragged) expert GEMM on the MXU.

TPU counterpart of the reference's CUTLASS MoE GEMM
(`csrc/inference/v2/kernels/cutlass_ops/moe_gemm/moe_gemm.cu`, surfaced as
`deepspeed/inference/v2/kernels/cutlass_ops/`): one kernel launch computes
`out[start_g:end_g] = lhs[start_g:end_g] @ rhs[g]` for every expert g over
token rows pre-sorted by expert id, so no (E, capacity) padded buffer is
materialized and no scatter/gather rides HBM between the three expert
matmuls.

Implementation: `jax.experimental.pallas.ops.tpu.megablox.ops.gmm` — the
custom-VJP grouped matmul (backward = gmm(grad, rhs^T) + tgmm for the
weight grad), which tiles group-irregular row spans onto the MXU with
per-tile store masks. This wrapper owns the policy bits:

- tiling selection (swept on v5e at the qwen2-moe proxy shape by an r5
  probe since deleted),
- padding rows up to an m-tile multiple (padding rows are appended to the
  LAST group; they multiply zeros and their outputs are dropped),
- interpret-mode fallback so CPU golden tests run the same code path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import gmm as _gmm

from deepspeed_tpu.ops.pallas import _interpret


def default_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tile sizes for the grouped GEMM. 512×1024×1024 won the r5 on-chip
    sweep at the proxy shape (m=16k, k=1k, n=2k); small dims shrink their
    tile to the dim (k/n remainders are masked in-kernel, m is padded).
    tm never drops below 16 — Mosaic's bf16 sublane minimum — so
    decode-sized row counts pad up instead of requesting a tiny tile."""
    return (max(16, min(m, 512)), min(k, 1024), min(n, 1024))


def grouped_gemm(lhs: jnp.ndarray,
                 rhs: jnp.ndarray,
                 group_sizes: jnp.ndarray,
                 tiling: Optional[Tuple[int, int, int]] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """`out[rows of group g] = lhs[rows of group g] @ rhs[g]`.

    lhs: (M, K) rows sorted by group id; rhs: (G, K, N); group_sizes: (G,)
    int32 summing to M. Differentiable in lhs and rhs. Output (M, N) in
    lhs.dtype (f32 MXU accumulation inside the kernel, like an XLA bf16
    einsum).
    """
    m, k = lhs.shape
    g, k2, n = rhs.shape
    if k != k2:
        raise ValueError(f"grouped_gemm: lhs K={k} vs rhs K={k2}")
    if group_sizes.shape != (g,):
        raise ValueError(
            f"grouped_gemm: group_sizes {group_sizes.shape} != ({g},)")
    if tiling is None:
        tiling = default_tiling(m, k, n)
    if interpret is None:
        interpret = _interpret()
    tm = tiling[0]
    m_pad = -(-m // tm) * tm - m
    if m_pad:
        # pad rows ride the LAST group: they multiply zero inputs and are
        # sliced off below, so only their (negligible) FLOPs exist
        lhs = jnp.concatenate(
            [lhs, jnp.zeros((m_pad, k), lhs.dtype)], axis=0)
        group_sizes = group_sizes.at[g - 1].add(m_pad)
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
               tiling, interpret=interpret)
    return out[:m] if m_pad else out


def sharded_grouped_gemm(lhs: jnp.ndarray,
                         rhs: jnp.ndarray,
                         group_sizes: jnp.ndarray,
                         mesh,
                         axis: str = "expert",
                         tiling: Optional[Tuple[int, int, int]] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """`grouped_gemm` under expert parallelism: rhs (G, K, N) sharded over
    the mesh `axis` (G/ep experts per shard), lhs rows and group_sizes
    replicated. Each shard runs megablox `gmm` over its OWN expert span
    via a per-shard `group_offset` (the SNIPPETS tpu_inference fused-MoE
    pattern), zeroes the rows outside its span, and a psum over `axis`
    reassembles the (M, N) output.

    The per-shard offset is a SHARDED INPUT (`jnp.arange(ep)·G/ep` with
    spec P(axis), each shard reading element [0]; see
    ops/pallas/sharded.py). Requires G % ep == 0; callers gate with `ep_grouped_gemm_shardable` and fall
    back to the ragged path otherwise."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.pallas.sharded import kernel_shard_map
    m, k = lhs.shape
    g, k2, n = rhs.shape
    if k != k2:
        raise ValueError(f"sharded_grouped_gemm: lhs K={k} vs rhs K={k2}")
    ep = int(mesh.shape[axis])
    if g % ep:
        raise ValueError(
            f"sharded_grouped_gemm: {g} experts not divisible by "
            f"{axis}={ep}")
    e_loc = g // ep
    if tiling is None:
        tiling = default_tiling(m, k, n)
    if interpret is None:
        interpret = _interpret()
    tm = tiling[0]
    m_pad = -(-m // tm) * tm - m
    if m_pad:
        lhs = jnp.concatenate(
            [lhs, jnp.zeros((m_pad, k), lhs.dtype)], axis=0)
        group_sizes = group_sizes.at[g - 1].add(m_pad)
    group_sizes = group_sizes.astype(jnp.int32)
    offsets = jnp.arange(ep, dtype=jnp.int32) * e_loc

    def body(lhs, rhs_loc, sizes, off):
        off = off[0]  # this shard's first expert (gmm wants a ()-shape)
        out = _gmm(lhs, rhs_loc, sizes, lhs.dtype, tiling,
                   group_offset=off, interpret=interpret)
        # gmm with group_offset only writes the row span of experts
        # [off, off+e_loc); rows outside it are uninitialized in `out` —
        # zero them so the psum is the disjoint-span union
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
        keep = (rows >= starts[off]) & (rows < starts[off + e_loc])
        return jax.lax.psum(jnp.where(keep, out, 0), axis)

    out = kernel_shard_map(body, mesh, (P(), P(axis), P(), P(axis)), P())(
        lhs, rhs, group_sizes, offsets)
    return out[:m] if m_pad else out
