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
  probe since deleted; a held expert layer's by its call's shape,
  `held_tiling`: the whole contraction as one K tile for decode-sized rows),
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


# `held_tiling`'s rule. The row tile of a call whose experts expect fewer
# than 8 rows each (`sharded_moe.held_row_tile`'s floor: every decode step),
# and the bytes two buffers of a weight tile may take of this chip's 16 MiB
# of default scoped VMEM (megablox passes no `vmem_limit_bytes`); the rest
# holds the rows' and the result's tiles and the float32 accumulator.
DECODE_ROW_TILE = 16
WEIGHT_TILES_BYTES = 12 << 20


def held_tiling(tm: int, k: int, n: int, itemsize: int = 2
                ) -> Tuple[int, int, int]:
    """Tile sizes for a held expert layer's call (`moe/layer.py`) whose row
    tile is `tm`, by shape alone.

    At `DECODE_ROW_TILE` the WHOLE contraction is one K tile, where two
    buffers of `k x min(n, 1024)` weights fit `WEIGHT_TILES_BYTES` (bf16: K
    up to 3,072). The sorted rows lie contiguous from row 0, aligned to
    nothing, so an expert of `s` rows straddles a row tile with probability
    `(s - 1) / 16` and is then two grid steps. The grid is (N tiles, row
    tiles x experts, K tiles) with K innermost and the weights' block index
    `(expert, k_i, n_i)`: with ONE K tile the second step's index is the
    first's, the pipeline skips the fetch and the expert's weights are read
    once; with several, the second step starts again at `(expert, 0, n_i)`
    and reads them all a second time (8 of the 63 experts a Nemotron decode
    call touches: 1.92 -> 1.79 ms a call with one K tile, 1.72 with each
    group padded to whole row tiles; PERF.md, PR 66).

    Any other `tm` (a prefill's experts span many row tiles by design), or a
    K too long for the widest N tile: 1,024 x 1,024 as swept (PERF.md, PR
    59), remainders masked in-kernel (the mask read as free: it hides under
    the tile's copy). A NARROWER N tile under the whole K loses at every
    shape read (more grid steps, the rows' tile fetched again each), and at
    K 7,168, where 512 columns are the most that fit, a decode step of 5
    held rows read +5.6% in its cell: such a K keeps its K tiles."""
    tn = min(n, 1024)
    if tm == DECODE_ROW_TILE and 2 * k * tn * itemsize <= WEIGHT_TILES_BYTES:
        return (tm, k, tn)
    return (tm, min(k, 1024), tn)


def weight_tile_revisits(group_sizes: jnp.ndarray, tm: int) -> jnp.ndarray:
    """Grid steps of a call under `held_tiling`'s decode rule that find their
    expert's weights resident: the sum over the groups that have rows of (row
    tiles of `tm` their rows reach - 1), the groups contiguous from row 0.
    Each was a second read of the expert's weights under K tiles (and still
    is for a projection whose K is too long for one tile). 0 at any other
    `tm`, where the rule keeps K tiles."""
    if tm != DECODE_ROW_TILE:
        return jnp.zeros((), jnp.int32)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    return jnp.sum(jnp.where(group_sizes > 0,
                             (ends - 1) // tm - starts // tm, 0),
                   dtype=jnp.int32)


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
