"""Pallas TPU kernel for a serving PREFILL of the gated delta rule in its
chunked form (Gated DeltaNet, arXiv:2412.06464, a decay a head; Kimi Linear,
arXiv:2510.26692, a decay a channel): what `models/hybrid.delta_chunked`
computes, which stays the reference, in ONE call a layer.

The plain form is a `lax.scan` of five small batched products a block whose
(H, d_k, d_v) float32 state goes through HBM every step, behind eight
float32 stacks a call (the blocks' `(C, C)` inverses among them) and a
`moveaxis` of every operand. Here a grid step holds `heads` value heads of
one sequence over ONE block of `chunk` positions, the block axis last and
sequential: the heads' states live in the result's VMEM block from the
call's first block to its last (`s0` read once, the state written once),
and a block's cumulative decay, `between`, `A`, the solve, `p`, `rhs` and
`u` are made and consumed in VMEM.

Operands lie as the projections leave them: q, k (B, S, Hk x d_k), v and o
(B, S, Hv x d_v), a head a lane-aligned column block. Value head `h` reads
key head `h // (Hv / Hk)` through the index map: nothing is repeated and
nothing is transposed but the two (B, S, Hv) scalars a position (`g`'s
cumulative sum a block and `beta`, 0.26 MB a call), which XLA lays heads
minor a group, and the decay once more with a block's positions on the
lanes (`between` needs `G_i - G_j` both ways round).

The two normalisations a HEAD that stand round the rule in both families'
mixers are done here too: q and k over their L2 norm (q times `d_k ** -0.5`)
on the way in, the RMS norm of `o` over d_v times its weight on the way out.
In this order of the operands a head's 128 channels are a vreg's lanes and
each is a lane reduction of values the kernel holds anyway; XLA, handed (B,
S, H x 128), re-lays the whole operand to (B, S, H, 128) for the reduction
and back for the kernel (compiled for a v5e: two copies, a broadcast and a
reshape of the operand's size each, three operands a call).

Every product is float32 at `highest`, as the reference's. The unit lower
triangular system `(I + A) U = rhs` of a block is solved by SUBSTITUTION
over sub-blocks of `solve` rows: the rows above a sub-block enter by one
product, the sub-block's own rows by rank-one updates on the vector unit,
exact in float32. `solve` 0 forms the inverse by the reference's Neumann
products (ten `(C, C)` products a block: a third of the reference's
FLOP, and here most of the kernel's time).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret

KERNEL_NAME = "delta_rule_prefill"
CHANNEL_DECAY_NAME = "delta_rule_prefill_channel"   # the same body
F32 = jnp.float32
HEADS = 8       # value heads a grid step (0.5 MB of state at 128 x 128)
SOLVE = 32      # rows a sub-block of the substitution (0: the Neumann inverse)

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _solve(a, rhs, rows: int):
    """`(I + a)^-1 rhs` for STRICTLY lower triangular `a` (C, C)."""
    c = a.shape[0]
    if not rows:        # the inverse as the reference makes it, then applied
        idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        eye = (idx == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
               ).astype(F32)
        inv, power = eye - a, _dot(a, a)
        for left in range(max(0, math.ceil(math.log2(c)) - 1), 0, -1):
            inv = inv + _dot(inv, power)
            if left > 1:
                power = _dot(power, power)
        return _dot(inv, rhs)
    done = []
    for at in range(0, c, rows):
        x = rhs[at:at + rows]
        if done:        # less what the rows above answer: one product
            x = x - _dot(a[at:at + rows, :at], jnp.concatenate(done, axis=0))
        # the sub-block's own rows, a vreg of 8 at a time: row j is final
        # once the rows above it are, and comes off the rows below it
        own = a[at:at + rows, at:at + rows]
        tiles = [x[t:t + 8] for t in range(0, rows, 8)]
        for j in range(rows - 1):
            row = tiles[j // 8][j % 8:j % 8 + 1]
            for t in range((j + 1) // 8, rows // 8):
                tiles[t] = tiles[t] - own[8 * t:8 * t + 8, j:j + 1] * row
        done += tiles
    return jnp.concatenate(done, axis=0)


def _kernel(*refs, hb, rep, c, dk, dv, solve, head, l2_eps, norm_eps):
    if head:
        (q_ref, k_ref, v_ref, cum_ref, row_ref, beta_ref, w_ref, s0_ref,
         o_ref, s_ref) = refs
    else:       # a decay a channel lies as k does: no second layout of it
        (q_ref, k_ref, v_ref, cum_ref, beta_ref, w_ref, s0_ref, o_ref,
         s_ref) = refs

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + l2_eps)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict = ri >= ci, ri > ci
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, hb), 1)

    def column(ref, h):
        """(C, 1): head h's scalar a position, of (C, hb) heads minor."""
        return jnp.sum(jnp.where(lane == h, ref[...], 0.0), axis=-1,
                       keepdims=True)

    def key_head(kh, _):
        """The block of one key head's `rep` value heads."""
        at = pl.ds(pl.multiple_of(kh * dk, 128), dk)
        k = unit(k_ref[:, at])                                  # (C, dk)
        q = unit(q_ref[:, at]) * dk ** -0.5
        if head:
            kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        for h in range(rep):
            h = kh * rep + h
            mine = pl.ds(pl.multiple_of(h * dv, 128), dv)
            v = v_ref[:, mine]                                  # (C, dv)
            beta = column(beta_ref, h)                          # (C, 1)
            if head:
                cum = column(cum_ref, h)                        # G_i, (C, 1)
                last = cum[c - 1:c]                             # (1, 1)
                # exp(G_i - G_j), j <= i: no exponent is positive
                between = jnp.where(lower, jnp.exp(jnp.where(
                    lower, cum - row_ref[pl.ds(h, 1), :], 0.0)), 0.0)
                a = jnp.where(strict, kk * between, 0.0)
                p = qk * between
            else:
                cum = cum_ref[:, at]                            # (C, dk)
                last = cum[c - 1:c]                             # (1, dk)
                mid = cum[c // 2 - 1:c // 2]
                k_down = k * jnp.exp(mid - cum)
                up = jnp.exp(cum - mid)
                a = jnp.where(strict, _dot(k * up, k_down, _NT), 0.0)
                p = jnp.where(lower, _dot(q * up, k_down, _NT), 0.0)
            decay = jnp.exp(cum)                            # from the start
            state = s_ref[h]                                    # (dk, dv)
            # what the state answers to the block's keys and queries, one
            # product (its operand is split into bf16 limbs once)
            seen = _dot(jnp.concatenate([k * decay, q * decay], axis=0),
                        state)
            # u_i = beta_i (v_i - S'^T k_i): the rows of (I + A) U = rhs
            u = _solve(a * beta, beta * (v - seen[:c]), solve)  # (C, dv)
            # what u adds to the block's outputs and to the state, one
            # product: p u, and the keys as the block's END sees them
            to_end = k * jnp.exp(last - cum)
            adds = _dot(jnp.concatenate([p, to_end.T], axis=0), u)
            o = seen[c:] + adds[:c]
            o_ref[:, mine] = o * jax.lax.rsqrt(jnp.mean(
                o * o, axis=-1, keepdims=True) + norm_eps) * w_ref[...]
            # the block's whole decay: a head's one number, laid over the
            # lanes before the exponent (Mosaic broadcasts one axis at a
            # time), or a key channel's, a column
            whole = jnp.exp(last + jnp.zeros((1, dv), F32)) if head \
                else jnp.exp(last).T
            s_ref[h] = state * whole + adds[c:]

    # ONE body for the step's key heads, which Mosaic unrolls to straight-line
    # code: the scheduler interleaves the heads' chains (as a loop the same
    # step reads 1.39 ms a call for 1.17; PERF.md, PR 65)
    jax.lax.fori_loop(0, hb // rep, key_head, None, unroll=True)


def _head_block(nv: int, rep: int, want: int) -> int:
    """Value heads a grid step: the largest divisor of `nv` up to `want` that
    holds whole key heads' groups, else all of them."""
    return next((d for d in range(min(nv, want), 0, -1)
                 if nv % d == 0 and d % rep == 0), nv)


def delta_rule_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       g: jnp.ndarray, beta: jnp.ndarray, s0: jnp.ndarray,
                       chunk: int, norm_weight: jnp.ndarray, *, l2_eps: float,
                       norm_eps: float, heads: int = HEADS, solve: int = SOLVE,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The gated delta rule over a sequence from the state `s0`, between
    a mixer's convolution and its gate.

    q, k (B, S, Hk, dk) BEFORE their normalisation (here: over the root of
    their squares' sum plus `l2_eps`, q times `dk ** -0.5`), key head j
    serving value heads (Hv / Hk) j ..; v (B, S, Hv, dv); g (B, S, Hv)
    log-decay <= 0 a HEAD, or (B, S, Hv, dk) a channel
    (`CHANNEL_DECAY_NAME`; Hk = Hv); beta (B, S, Hv); s0 (B, Hv, dk, dv)
    float32; `norm_weight` (dv,), the weight of the RMS norm a head at
    `norm_eps` that `o` leaves through. dk and dv whole lane tiles, `chunk`
    whole sublane tiles. Returns `(o (B, S, Hv, dv) float32, normalised, the
    state after position S - 1)`: `models/hybrid.delta_chunked`'s at `chunk`
    between those norms (`models/hybrid.delta_prefill_reference`). Any S: the
    last block's tail is padded with g = 0, beta = 0, which leaves the state
    as it is. `heads` sizes a grid step and `solve` chooses the solve
    (`tools/delta_prefill_forms.py` sweeps them)."""
    bsz, s, nk, dk = q.shape
    nv, dv = v.shape[2:]
    head = g.ndim == 3
    if k.shape != q.shape or v.shape[:2] != (bsz, s) or nv % nk \
            or g.shape != (bsz, s, nv) + (() if head else (dk,)) \
            or beta.shape != (bsz, s, nv) or s0.shape != (bsz, nv, dk, dv) \
            or norm_weight.shape != (dv,) or (not head and nk != nv):
        raise ValueError(f"delta_rule_prefill: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, g {g.shape}, beta {beta.shape}, s0 "
                         f"{s0.shape}, norm_weight {norm_weight.shape}")
    if dk % 128 or dv % 128 or chunk % 8 or s0.dtype != F32:
        raise ValueError(f"delta_rule_prefill: heads of {dk} x {dv} in blocks "
                         f"of {chunk}, a state of {s0.dtype}: whole (8, 128) "
                         "tiles and a float32 state")
    solve = min(solve, chunk)
    if solve and (chunk % solve or solve % 8):
        raise ValueError(f"delta_rule_prefill: sub-blocks of {solve} rows in "
                         f"a block of {chunk}: whole vregs of 8 that divide "
                         "it")
    return _prefill(q, k, v, g, beta, s0, norm_weight, chunk=chunk,
                    l2_eps=l2_eps, norm_eps=norm_eps, heads=heads, solve=solve,
                    interpret=_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "l2_eps", "norm_eps", "heads", "solve", "interpret"))
def _prefill(q, k, v, g, beta, s0, norm_weight, *, chunk, l2_eps, norm_eps,
             heads, solve, interpret):
    """`delta_rule_prefill` once its operands are checked, a `jax.jit` of its
    own INSIDE the caller's program: a shape is traced once a process and
    lowered once a program, where a program of nine layers that the engine
    traces several times a set-up traced and lowered the body for each
    (0.8 s a time, 37 s of a set-up; PERF.md, PR 65). XLA inlines the call:
    the compiled program and each call's scopes are what they were."""
    bsz, s, nk, dk = q.shape
    nv, dv = v.shape[2:]
    head = g.ndim == 3
    q, k, v, g, beta = (t.astype(F32) for t in (q, k, v, g, beta))
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    sp = s + pad
    nc = sp // chunk
    rep = nv // nk
    hb = _head_block(nv, rep, heads)
    ng, kb = nv // hb, hb // rep
    # G_i, inclusive, a block
    cum = jnp.cumsum(g.reshape((bsz, nc, chunk) + g.shape[2:]), axis=2)

    def cols(x):        # (B, S, Hv) -> (B, ng, S, hb): a head's a lane
        return jnp.moveaxis(x.reshape(bsz, sp, ng, hb), 2, 1)

    wide = lambda n: pl.BlockSpec(  # noqa: E731
        (None, chunk, n), lambda b, j, i: (b, i, j))
    col = pl.BlockSpec((None, None, chunk, hb),
                       lambda b, j, i: (b, j, i, 0))
    slab = pl.BlockSpec((None, hb, dk, dv), lambda b, j, i: (b, j, 0, 0))
    if head:
        # the decay once more, a block's positions on the lanes
        row = jnp.transpose(cum.reshape(bsz, nc, chunk, ng, hb),
                            (0, 3, 1, 4, 2))
        decays = [cols(cum.reshape(bsz, sp, nv)), row]
        decay_specs = [col, pl.BlockSpec((None, None, None, hb, chunk),
                                         lambda b, j, i: (b, j, i, 0, 0))]
    else:
        decays = [cum.reshape(bsz, sp, nv * dk)]
        decay_specs = [wide(hb * dk)]
    kernel = functools.partial(_kernel, hb=hb, rep=rep, c=chunk, dk=dk,
                               dv=dv, solve=solve, head=head, l2_eps=l2_eps,
                               norm_eps=norm_eps)
    o, last = pl.pallas_call(
        kernel,
        grid=(bsz, ng, nc),
        in_specs=[wide(kb * dk), wide(kb * dk), wide(hb * dv), *decay_specs,
                  col, pl.BlockSpec((1, dv), lambda b, j, i: (0, 0)), slab],
        out_specs=[wide(hb * dv), slab],
        out_shape=[jax.ShapeDtypeStruct((bsz, sp, nv * dv), F32),
                   jax.ShapeDtypeStruct(s0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME if head else CHANNEL_DECAY_NAME,
    )(q.reshape(bsz, sp, nk * dk), k.reshape(bsz, sp, nk * dk),
      v.reshape(bsz, sp, nv * dv), *decays, cols(beta),
      norm_weight.astype(F32).reshape(1, dv), s0)
    return o.reshape(bsz, sp, nv, dv)[:, :s], last
