"""The way back of a held expert layer: each token's weighted float32 sum of
its held rows, in ONE pass over the rows as the grouped GEMM left them.

`moe/sharded_moe.held_dispatch_gmm` sorts a call's assignments by held
expert with a STABLE sort, so inside an expert's group the rows lie in token
order and a token holds at most one row of an expert (a top-k's ids are
distinct). The rows a tile of `tt` tokens needs are therefore `count`
contiguous WINDOWS of the sorted rows, one in each expert's group, and where
a token's row lies is known without the sort: its expert's group start plus
the number of tokens before it that hold the same expert. The wrapper makes
that table (T, count) and each window's first row (tiles, count) in XLA from
the held mask by sums within a tile and running sums; no sort, gather or
scatter.

The kernel, over a grid of (token tiles, blocks of the hidden width), copies
the tile's windows from HBM into one VMEM buffer in CHUNKS of 16 rows (a
whole sublane tile of bf16: a window is drawn back to one and copied to the
end of its last), the chunks of all the windows packed one after another, so
that a router that loads its experts unevenly fills the buffer no further
than an even one; two slots, the next step's copies in flight under this
step's product. It builds the (tt, buffer rows) matrix that holds a token's
weight where the buffer holds its row and zero elsewhere (once a tile: it
serves every hidden block) and multiplies it against the rows on the MXU
with float32 accumulation: bf16 rows against the weights in three bf16
limbs, one under the other in ONE product (every term exact), float32 rows
at the highest precision. A tile whose chunks outnumber the buffer's (hot
experts past twice the rows the share expects) takes further passes; a tile
with no held row copies nothing and writes zeros; rows at and past the held
ones, which the grouped GEMM never wrote, are zeroed in VMEM before the
product (their weight is zero, but zero times what lies there need not be).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret

F32 = jnp.float32
_CHUNK = 16          # rows of a bf16 sublane tile: what one copy moves
# bytes an entry of the (tile, buffer rows) weight matrix costs in VMEM: the
# kept limbs and the float32 matrices they are made from
_WEIGHT_BYTES = 16


def combine_plan(t: int, bound: int, count: int, d: int,
                 itemsize: int) -> Tuple[int, int, int]:
    """(tokens a tile, rows the buffer holds, hidden block) from the shapes
    alone. A tile is sized so that a FULL bound spread evenly lays at most 16
    rows in a window (128 to 512 tokens: the product's cost goes with the
    buffer, not with the tile) and halved while the (tile, buffer) weight
    matrices pass 4 MiB (many held experts); the buffer holds the chunks of a
    full bound's rows of a tile and one more an expert for the draw-back; the
    hidden block is the widest multiple of 128 lanes that divides `d` and
    keeps two slots of the buffer, two output tiles and the product's
    float32 partials in what is left of 12 MiB of VMEM."""
    want = max(128, min(512, 16 * count * t // max(bound, 1)))
    want = 1 << (want.bit_length() - 1)

    def buffer(tt):
        return _CHUNK * (-(-bound * tt // (t * _CHUNK)) + count)
    while want > _CHUNK and _WEIGHT_BYTES * want * buffer(want) > 4 << 20:
        want //= 2
    tt = next((c for c in range(min(want, t) // _CHUNK * _CHUNK, 0, -_CHUNK)
               if t % c == 0), min(want, -(-t // _CHUNK) * _CHUNK))
    width = buffer(tt)
    left = (12 << 20) - _WEIGHT_BYTES * tt * width
    column = 2 * width * itemsize + 5 * tt * 4
    lanes = d // 128 if d % 128 == 0 else 0
    fit = max(1, left // column // 128)
    dblk = 128 * max((c for c in range(1, lanes + 1)
                      if lanes % c == 0 and c <= fit), default=0) or d
    return tt, width, dblk


def _limbs(v):
    """A float32 array as three bf16 arrays that add up to it exactly."""
    out = []
    for _ in range(3):
        limb = v.astype(jnp.bfloat16)
        out.append(limb)
        v = v - limb.astype(F32)
    return out


def _kernel(first_ref, slot_ref, held_ref, rid_ref, wt_ref, rows_hbm, o_ref,
            buf, wmat, sems, *, count, tt, width, dblk, nd):
    """`first_ref` (tiles x count): each window's first row; `slot_ref`
    (tiles x (count + 1)): the buffer chunk each window's first chunk takes,
    counted through the tile's windows, and after the last the tile's chunks
    in all; `held_ref`: the held rows' number."""
    i, j = pl.program_id(0), pl.program_id(1)
    step = i * nd + j
    slot = step % 2
    room = width // _CHUNK                   # chunks a pass
    n_held = held_ref[0]

    def chunks(tile):
        return slot_ref[tile * (count + 1) + count]

    def window(tile, e, p):
        """Expert e's window of a tile in pass p: (the row its first chunk
        starts at, the buffer chunk that one takes, which may lie before or
        after the pass's, the window's chunks)."""
        at = slot_ref[tile * (count + 1) + e]
        return (first_ref[tile * count + e] // _CHUNK * _CHUNK,
                at - p * room, slot_ref[tile * (count + 1) + e + 1] - at)

    def copy(row, into, chunk, block):
        src = rows_hbm.at[pl.ds(pl.multiple_of(row, _CHUNK), _CHUNK)]
        if nd > 1:
            src = src.at[:, pl.ds(pl.multiple_of(block * dblk, 128), dblk)]
        return pltpu.make_async_copy(
            src, buf.at[into, pl.ds(pl.multiple_of(chunk * _CHUNK, _CHUNK),
                                    _CHUNK)], sems.at[into])

    def each_chunk(tile, p, fn):
        """fn(row, buffer chunk) for every chunk of the tile in pass p."""
        def of(e, _):
            row, at, n = window(tile, e, p)
            jax.lax.fori_loop(
                jnp.maximum(-at, 0), jnp.minimum(n, room - at),
                lambda c, _: fn(row + c * _CHUNK, at + c), None)
        jax.lax.fori_loop(0, count, of, None)

    def start(tile, block, p, into):
        each_chunk(tile, p, lambda row, chunk: copy(row, into, chunk,
                                                    block).start())

    def land(p):
        """Wait for this step's chunks and zero what the GEMM never wrote."""
        def one(row, chunk):
            copy(row, slot, chunk, j).wait()

            @pl.when(row + _CHUNK > n_held)
            def _():
                rows = pl.ds(pl.multiple_of(chunk * _CHUNK, _CHUNK), _CHUNK)
                written = row + jax.lax.broadcasted_iota(
                    jnp.int32, (_CHUNK, 1), 0) < n_held
                buf[slot, rows, :] = jnp.where(written, buf[slot, rows, :], 0)
        each_chunk(i, p, one)

    def weigh(p):
        """Into `wmat`: pass p's weight matrix, a token's weight where the
        buffer holds its row; for bf16 rows its three limbs one under the
        other, so that one product serves all three."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        holds = jnp.full((1, width), -2, jnp.int32)  # the row a lane holds
        whose = jnp.full((1, width), count, jnp.int32)   # ... of which expert
        for e in range(count):
            row, at, n = window(i, e, p)
            mine = jnp.logical_and(lane >= at * _CHUNK,
                                   lane < (at + n) * _CHUNK)
            holds = jnp.where(mine, row + lane - at * _CHUNK, holds)
            whose = jnp.where(mine, e, whose)
        # a token's (row, weight) of expert e in every lane of e's window:
        # bf16 limbs against a 0/1 matrix, one term a sum, so exact
        spread = (whose == jax.lax.broadcasted_iota(
            jnp.int32, (count, width), 0)).astype(jnp.bfloat16)

        def across(limb):
            return jnp.dot(limb, spread, preferred_element_type=F32)
        rid = sum(across(limb) for limb in _limbs(rid_ref[...].astype(F32)))
        hit = rid == holds.astype(F32)
        if wmat.dtype == jnp.bfloat16:
            for n, limb in enumerate(_limbs(wt_ref[...])):
                wmat[n * tt:(n + 1) * tt, :] = jnp.where(
                    hit, across(limb), 0.0).astype(jnp.bfloat16)
        else:
            wmat[...] = jnp.where(
                hit, sum(across(limb) for limb in _limbs(wt_ref[...])), 0.0)

    def product():
        """The tile's tokens' weighted sums over the chunks in `slot`."""
        if wmat.dtype == jnp.bfloat16:
            limbs = jnp.dot(wmat[...], buf[slot], preferred_element_type=F32)
            return limbs[:tt] + limbs[tt:2 * tt] + limbs[2 * tt:]
        return jnp.dot(wmat[...], buf[slot].astype(F32),
                       preferred_element_type=F32,
                       precision=jax.lax.Precision.HIGHEST)

    @pl.when(step == 0)
    def _():
        # a chunk of the buffer no copy has filled holds no weight, and has
        # to hold numbers
        buf[...] = jnp.zeros_like(buf)
        start(i, j, 0, slot)

    # the next step's first pass, in flight under this step's product
    nxt_i = jnp.where(j + 1 < nd, i, i + 1)
    nxt_j = jnp.where(j + 1 < nd, j + 1, 0)

    @pl.when(nxt_i < pl.num_programs(0))
    def _():
        start(nxt_i, nxt_j, 0, 1 - slot)

    @pl.when(chunks(i) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(chunks(i) > 0)
    def _():
        # the first pass's weights serve every hidden block of the tile,
        # unless a further pass has written over them
        @pl.when(jnp.logical_or(j == 0, chunks(i) > room))
        def _():
            weigh(0)
        land(0)
        o_ref[...] = product()

        def further(p, _):
            start(i, j, p, slot)
            weigh(p)
            land(p)
            o_ref[...] += product()
        jax.lax.fori_loop(1, (chunks(i) + room - 1) // room, further, None)


def _held_combine(out_s: jnp.ndarray, local: jnp.ndarray, gate_k: jnp.ndarray,
                  count: int) -> jnp.ndarray:
    """(T, D) float32: for each token the sum over its held assignments of
    `float32(out_s[its row]) * gate_k`, where `out_s` (bound, D) holds the
    held assignments' rows sorted by held expert and, within an expert, by
    token (`held_dispatch_gmm`'s stable sort), `local` (T, k) is each
    assignment's held expert, `count` where it is absent, and a token's ids
    are distinct. The held rows number `bound` or fewer; what `out_s` holds
    at and past them is never read into a sum."""
    height, d = out_s.shape
    t, k = local.shape
    tt, width, dblk = combine_plan(t, height, count, d, out_s.dtype.itemsize)
    assert d % dblk == 0 and width % _CHUNK == 0, (d, dblk, width)
    tiles, nd = -(-t // tt), d // dblk
    exact = out_s.dtype == jnp.bfloat16   # three bf16 limbs of a weight
    if height % _CHUNK:              # no shape the layer's rule makes
        out_s = jnp.pad(out_s, ((0, -height % _CHUNK), (0, 0)))
    # (token, held expert): does it hold a row, and with what weight
    mine = local[:, :, None] == jnp.arange(count, dtype=local.dtype)
    weight = jnp.sum(jnp.where(mine, gate_k.astype(F32)[:, :, None], 0.0),
                     axis=1)
    holds = jnp.pad(jnp.any(mine, axis=1), ((0, tiles * tt - t), (0, 0))
                    ).astype(jnp.int32).reshape(tiles, tt, count)
    weight = jnp.pad(weight, ((0, tiles * tt - t), (0, 0)))
    # a row's place: its group's start, the tiles before its own, the
    # tokens before it in its tile
    in_tile = jnp.sum(holds, axis=1)                          # (tiles, count)
    sizes = jnp.sum(in_tile, axis=0)
    first = (jnp.cumsum(sizes) - sizes)[None] \
        + jnp.cumsum(in_tile, axis=0) - in_tile
    rid = jnp.where(holds > 0,
                    first[:, None] + jnp.cumsum(holds, axis=1) - holds, -1)
    # a window's chunks, from its first row drawn back to a whole one, and
    # where in the tile's buffer they start
    chunks = jnp.where(in_tile > 0,
                       -(-(first % _CHUNK + in_tile) // _CHUNK), 0)
    takes = jnp.pad(jnp.cumsum(chunks, axis=1), ((0, 0), (1, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, count=count, tt=tt, width=width, dblk=dblk,
                          nd=nd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, nd),
            in_specs=[pl.BlockSpec((tt, count), lambda i, j, *_: (i, 0)),
                      pl.BlockSpec((tt, count), lambda i, j, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, dblk), lambda i, j, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((2, width, dblk), out_s.dtype),
                            pltpu.VMEM((3 * tt, width), jnp.bfloat16)
                            if exact else pltpu.VMEM((tt, width), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tiles * tt, d), F32),
        # one after another: a step starts the copies of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="held_combine",
    )(first.reshape(-1).astype(jnp.int32),
      takes.reshape(-1).astype(jnp.int32),
      jnp.sum(sizes).reshape(1).astype(jnp.int32),
      rid.reshape(tiles * tt, count).astype(jnp.int32), weight, out_s)
    return out[:t] if tiles * tt != t else out


# jitted, so that a model's unrolled layers trace and lower the kernel ONCE
# and not once a layer (0.45 s a call site otherwise: 9 s of set-up in a
# program of fourteen expert layers traced twice)
held_combine = jax.jit(_held_combine, static_argnames=("count",))
