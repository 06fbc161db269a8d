"""Fused int8 dequant-GEMM (weight-only quantized matmul) on the MXU.

TPU counterpart of the reference's fused int8 inference GEMMs
(DeepSpeed-Inference kernel injection, `csrc/transformer/inference/csrc/
gelu.cu`-adjacent quantized GEMM path): computes `x @ dequant(q, scales)`
while the int8 blocks + scales stream HBM→VMEM and the dequantization
happens in-register inside the tile loop, so the bf16 weight form NEVER
exists in HBM. That is the whole point: ZeRO-Inference decode is
weight-READ-bound, and the naive `dequantize-then-matmul` materializes a
bf16/f32 copy of every weight every step (~2.6 GB/layer/step at 7B —
measured 4x SLOWER than bf16 serving despite reading 2x fewer weight
bytes). Fused, int8 decode reads 6.8 GB/step vs bf16's 13.5.

Quantization layout (`ops/quantization.py:quantize_int8_blockwise`): flat
row-major blocks of `group` consecutive elements share one f32 scale. For
the weight shapes in play the blocks never span rows, so the scale of
element (k, j) is `scales[k, j // g]` — a (K, N/g) grid. The kernel does
NOT expand that grid to (K, N) in-register (an awkward lane-repeat for
Mosaic); it folds the scale into the ACTIVATION side instead:

    out[:, jg:(j+1)g] = (x * s_j) @ q[:, jg:(j+1)g]        s_j = scales[:, j]

which is exact (scale is constant within a group and multiplies the
contraction linearly), needs only a lane-broadcast VPU multiply on the
small x tile, and keeps the MXU operand int8→bf16. The wrapper feeds the
kernel scales TRANSPOSED (G, K) so `s_j` is a lane-contiguous row.

House style (flash/megablox): interpret-mode path for CPU tests, block
sizes swept on v5e, f32 accumulation (hardware rounds MXU inputs to bf16 —
tests use loose tolerances on real chips). Forward-only by design — this
is a serving kernel; training keeps the XLA dequant path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret


def scale_group_width(k: int, n: int, nblocks: int) -> Optional[int]:
    """Per-row group width g (divides N) implied by flat blockwise scales
    over a (K, N) weight, or None when blocks straddle rows misaligned
    (callers then fall back to the naive dequant matmul)."""
    total = k * n
    if nblocks <= 0 or total % nblocks:
        return None
    e = total // nblocks  # elements per scale block
    if n % e == 0:
        return e          # blocks subdivide each row
    if e % n == 0:
        return n          # one block spans e//n whole rows
    return None


def _scales_t(k: int, n: int, scales: jnp.ndarray
              ) -> Tuple[jnp.ndarray, int]:
    """Flat (nblocks,) scales → transposed row-group layout (G, K), G=N/g.
    Tiny relayout (~1.5% of the int8 bytes) done inside the consumer's jit;
    the stored representation stays EXACTLY quantize_int8_blockwise's, so
    the fused kernel, the naive dequant and the whole-tree engine all
    consume one tree."""
    g = scale_group_width(k, n, scales.shape[0])
    if g is None:
        raise ValueError(
            f"quantized_matmul: {scales.shape[0]} scale blocks do not tile "
            f"a ({k}, {n}) weight row-aligned")
    e = k * n // scales.shape[0]
    if g == n and e != n:
        # one scale per e//n rows → expand to per-row, one group per row
        per_row = jnp.repeat(scales, e // n)
        return per_row.reshape(1, k), g
    return scales.reshape(k, n // g).T, g


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def default_tiling(m: int, k: int, n: int, g: int) -> Tuple[int, int, int]:
    """(bm, bk, bn) for the fused kernel: bm rounds tiny decode M up to a
    sublane-aligned tile (decode is weight-read-bound, bm barely matters),
    bk·bn sizes the double-buffered int8 weight tile at ≤4 MB of VMEM so
    the HBM weight stream pipelines, and bn is clamped to a multiple of
    the scale group width g. 512×1024 mirrors the flash/megablox sweet
    spot on v5e; sweep on chip per shape when tuning (the r5 rule: whole
    layers, one process — pass `tiling=` to override)."""
    bm = max(8, min(256, _round_up(m, 8)))
    bk = min(k, 512)
    if g <= 1024:
        bn = (1024 // g) * g
    else:
        bn = g
    bn = max(g, min(bn, _round_up(n, g)))
    # bound the double-buffered int8 weight tile (bk×bn) to ~4 MB of VMEM
    while bk > 128 and bk * bn > (4 << 20):
        bk //= 2
    return bm, bk, bn


def _qmm_kernel(x_ref, q_ref, st_ref, o_ref, acc_scr,
                *, g, sn, bk, k_total, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    k_rem = k_total % bk
    if k_rem:
        # last-tile K remainder: columns past K hold out-of-bounds reads —
        # zero them AFTER the scale multiply (an OOB f32 scale can be NaN,
        # and NaN·0 would survive a pre-mask)
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        keep = col < (k_total - kk * bk)
    for j in range(sn):
        xs = x * st_ref[j:j + 1, :]  # scale folded into the activation
        if k_rem:
            xs = jnp.where(keep, xs, 0.0)
        w = q_ref[:, j * g:(j + 1) * g].astype(jnp.float32)
        acc_scr[:, j * g:(j + 1) * g] += jax.lax.dot_general(
            xs, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _finalize():
        o_ref[...] = acc_scr[:].astype(o_ref.dtype)


def quantized_matmul(x: jnp.ndarray, q: jnp.ndarray, scales: jnp.ndarray,
                     tiling: Optional[Tuple[int, int, int]] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """`x @ dequantize_int8_blockwise(q, scales)` without materializing the
    dequantized weight.

    x: (..., K) float; q: (K, N) int8; scales: (nblocks,) f32 as produced
    by `quantize_int8_blockwise` (row-aligned blocks — see
    `scale_group_width`). Returns (..., N) in x.dtype, f32 accumulation.
    """
    *lead, k = x.shape
    kq, n = q.shape
    if k != kq:
        raise ValueError(f"quantized_matmul: x K={k} vs q K={kq}")
    st, g = _scales_t(kq, n, jnp.asarray(scales))
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    if interpret is None:
        interpret = _interpret()
    bm, bk, bn = tiling if tiling is not None else default_tiling(m, k, n, g)
    bn = max(g, bn - bn % g)  # group width must tile the n block
    sn = bn // g
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    # One n-block's sn scale rows ride a (sn, bk) tile. Mosaic wants a
    # block's second-to-last dim a multiple of 8 or the whole array dim,
    # and sn is neither in general (4 of 43 groups at 2048→11008) — so
    # re-lay (G, K) as (n-blocks, sn, K): the tile then spans its array's
    # sn dim. Pad groups cover only out-of-range output columns.
    pad = grid[1] * sn - st.shape[0]
    if pad:
        st = jnp.concatenate([st, jnp.ones((pad, k), st.dtype)], axis=0)
    st = st.reshape(grid[1], sn, k)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, g=g, sn=sn, bk=bk, k_total=k,
                          nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((None, sn, bk), lambda mi, ni, ki: (ni, 0, ki)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k * x.dtype.itemsize + k * n
                            + st.size * 4 + m * n * x.dtype.itemsize),
            transcendentals=0),
        interpret=interpret,
    )(x2, q, st)
    return out.reshape(*lead, n)


def tp_shard_flavor(k: int, n: int, nblocks: int, tp: int,
                    prefer: str = "n") -> Optional[str]:
    """Which tensor-parallel sharding of a (K, N) int8 weight with flat
    blockwise scales a tp-way 'model' axis supports: 'n' (column-parallel
    — shard output features, no collective), 'k' (row-parallel — shard
    the contraction, psum), or None (scale blocks can't split evenly →
    callers fall back to the naive dequant matmul). `prefer` breaks ties
    toward the weight's at-rest layout (q/k/v/gate/up are column-sharded
    by the placement specs, o/down row-sharded — matching it keeps the
    shard_map boundary reshard-free)."""
    g = scale_group_width(k, n, nblocks)
    if g is None or tp <= 1:
        return None
    e = k * n // nblocks  # elements per scale block
    rows_per_block = e // n if (e % n == 0 and e != n) else 1

    def ok(f: str) -> bool:
        if f == "n":
            # whole scale groups per shard: per-row blocks only, and the
            # (N/g) group grid must split evenly over tp
            return e <= n and (n // g) % tp == 0
        # 'k': row spans per shard must cover whole blocks
        return k % tp == 0 and (k // tp) % rows_per_block == 0

    order = ("n", "k") if prefer != "k" else ("k", "n")
    for f in order:
        if ok(f):
            return f
    return None


def sharded_quantized_matmul(x: jnp.ndarray, q: jnp.ndarray,
                             scales: jnp.ndarray, mesh,
                             axis: str = "model",
                             flavor: Optional[str] = None,
                             tiling: Optional[Tuple[int, int, int]] = None,
                             interpret: Optional[bool] = None) -> jnp.ndarray:
    """`quantized_matmul` under tensor parallelism: the int8 blocks and
    their scales sharded over the mesh `axis`, the fused kernel running
    per shard inside a full-manual shard_map region (GSPMD cannot
    partition the pallas_call itself — ops/pallas/sharded.py has the
    portability rules).

    flavor 'n' (column-parallel): q/scales shard the N dim, each shard
    computes its output columns, no collective. flavor 'k' (row-parallel):
    q/scales shard K, x arrives column-sliced, partial products psum over
    `axis`. Defaults to `tp_shard_flavor(...)`; raises when neither
    flavor divides (callers gate first and fall back to naive dequant)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.pallas.sharded import kernel_shard_map
    *lead, k = x.shape
    kq, n = q.shape
    if k != kq:
        raise ValueError(f"sharded_quantized_matmul: x K={k} vs q K={kq}")
    scales = jnp.asarray(scales)
    tp = int(mesh.shape[axis])
    if flavor is None:
        flavor = tp_shard_flavor(k, n, scales.shape[0], tp)
    if flavor not in ("n", "k"):
        raise ValueError(
            f"sharded_quantized_matmul: ({k}, {n}) weight with "
            f"{scales.shape[0]} scale blocks has no {axis}={tp} sharding "
            "(tp_shard_flavor returned None)")
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    if flavor == "n":
        g = scale_group_width(k, n, scales.shape[0])
        grid = scales.reshape(k, n // g)  # per-row groups ('n' guarantee)

        def body_n(xb, q_loc, s_loc):
            return quantized_matmul(xb, q_loc, s_loc.reshape(-1),
                                    tiling=tiling, interpret=interpret)

        out = kernel_shard_map(
            body_n, mesh, (P(), P(None, axis), P(None, axis)),
            P(None, axis))(x2, q, grid)
    else:

        def body_k(xb, q_loc, s_loc):
            y = quantized_matmul(xb, q_loc, s_loc,
                                 tiling=tiling, interpret=interpret)
            return jax.lax.psum(y, axis)

        out = kernel_shard_map(
            body_k, mesh, (P(None, axis), P(axis), P(axis)),
            P())(x2, q, scales)
    return out.reshape(*lead, n)
