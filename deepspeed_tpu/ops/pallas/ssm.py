"""Pallas TPU kernels for one decode step of a state-space layer's state:
Mamba-2 (SSD, `ssm_state_update`) and Mamba-1 (`ssm_state_update_m1`, below).

Mamba-2:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        (a head: P x N, float32)
    y_t = H_t C_t + D x_t

`ssm_state_update` reads a layer's state ONCE and writes it ONCE, in place:
the state of every recurrent layer is one stacked `(L, B, H, P, N)` float32
buffer (`inference/kv_cache.RecurrentState.ssm`), the kernel is handed the
whole stack with the layer's index as a prefetched scalar, its block specs
address `[layer, rows, group]`, and `input_output_aliases` makes the result
the same buffer. `y` comes out of the same pass. A decode step of this
family is bound by exactly these bytes (2 x B x H x P x N x 4 a layer), so
the contract is the roofline: no second read, no copy of a layer's slab
out of the stack, and (PR 29's rule) no XLA scatter into the buffer this
kernel aliases.

Grid `(B / bb, G)`: one step holds the `H / G` heads that share one group's
`B_t` and `C_t`, for `bb` rows. `x`, `dt` and `D x` are prepared outside (a
few KB); the decay `exp(dt A)` arrives lane-broadcast so that the block is
(8, 128)-aligned at any head count.

Mamba-1 decays every (channel, state) element on its own:

    H_t[n, c] = exp(dt_t[c] A[n, c]) H_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n H_t[n, c] C_t[n] + D[c] x_t[c]

`dt` is per CHANNEL and the state of a sequence is `N x C` (16 x 5120 at
Phi-4-mini-flash): 16 is no lane width, so the stack lies `(L, B, N, C)` with
the channels on the lanes, and `ssm_state_update_m1` holds the same contract
over it: one read and one write of `[layer, rows]` in place, the decay
computed in the kernel from `dt` and `A` (a layer's `A` is 0.3 MB and is
fetched once, its block index never moves). Its name begins as the other's
does, so a trace's `^ssm_state_update` finds either.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret

KERNEL_NAME = "ssm_state_update"
_LANES = 128


def _kernel(layer_ref, state_ref, dtx_ref, decay_ref, b_ref, c_ref,
            y_ref, out_ref):
    del layer_ref                                   # used by the index maps
    h = state_ref[...]                              # (bb, hb, P, N) float32
    decay = decay_ref[...][:, :, :1][..., None]     # (bb, hb, 1, 1)
    dtx = dtx_ref[...][..., None]                   # (bb, hb, P, 1)
    h = decay * h + dtx * b_ref[...][:, :, None, :]
    out_ref[...] = h
    y_ref[...] = jnp.sum(h * c_ref[...][:, :, None, :], axis=-1)


def _row_block(batch: int) -> int:
    """Rows a grid step holds: 4 (a 1 MB state block at 8 heads of 64 x 128,
    which with its result double-buffered is 4 MB of VMEM) where the batch
    divides, else the largest of 2 and 1 that does."""
    return next(n for n in (4, 2, 1) if batch % n == 0)


def ssm_state_update(state: jnp.ndarray, layer, x: jnp.ndarray,
                     dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                     c: jnp.ndarray, d: jnp.ndarray,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the recurrence on layer `layer` of the stacked `state`.

    state (L, B, H, P, N) float32; layer: int or () int32; x (B, H, P);
    dt (B, H), after the softplus; a (H,), negative; b, c (B, G, N), heads
    `g H/G .. (g+1) H/G - 1` use group g; d (H,). Returns `(y (B, H, P)
    float32, state)`: the same buffer where the caller donates or carries
    it. Everything is computed in float32.
    """
    nl, bsz, nh, p, n = state.shape
    g = b.shape[1]
    hb = nh // g
    if state.dtype != jnp.float32:
        raise ValueError(f"ssm_state_update: the state is {state.dtype}; the "
                         "recurrence is kept in float32")
    if nh % g or b.shape != (bsz, g, n) or c.shape != b.shape:
        raise ValueError(f"ssm_state_update: state {state.shape}, b {b.shape}, "
                         f"c {c.shape}")
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    dtx = (dt[..., None] * x).reshape(bsz, g, hb, p)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (bsz, nh, _LANES)).reshape(bsz, g, hb, _LANES)
    bb = _row_block(bsz)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def rows(*tail):
        return pl.BlockSpec((bb, None) + tail,
                            lambda i, j, l: (i, j) + (0,) * len(tail))

    slab = pl.BlockSpec((None, bb, None, hb, p, n),
                        lambda i, j, l: (l[0], i, j, 0, 0, 0))
    stacked = state.reshape(nl, bsz, g, hb, p, n)
    y, stacked = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bsz // bb, g),
            in_specs=[slab, rows(hb, p), rows(hb, _LANES), rows(1, n),
                      rows(1, n)],
            out_specs=[rows(hb, p), slab]),
        out_shape=[jax.ShapeDtypeStruct((bsz, g, hb, p), f32),
                   jax.ShapeDtypeStruct(stacked.shape, f32)],
        input_output_aliases={1: 1},       # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret() if interpret is None else interpret,
        name=KERNEL_NAME,
    )(layer, stacked, dtx, decay, b.astype(f32)[:, :, None, :],
      c.astype(f32)[:, :, None, :])
    y = y.reshape(bsz, nh, p) + d.astype(f32)[None, :, None] * x
    return y, stacked.reshape(state.shape)


def ssm_state_update_reference(state, layer, x, dt, a, b, c, d):
    """The same step in plain `jax.numpy` (tests, `chip_smoke`)."""
    f32 = jnp.float32
    hb = state.shape[2] // b.shape[1]
    bh = jnp.repeat(b.astype(f32), hb, axis=1)          # (B, H, N)
    ch = jnp.repeat(c.astype(f32), hb, axis=1)
    x, dt = x.astype(f32), dt.astype(f32)
    h = jnp.exp(dt * a.astype(f32))[..., None, None] * state[layer] \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.sum(h * ch[:, :, None, :], axis=-1) + d.astype(f32)[None, :, None] * x
    return y, state.at[layer].set(h)


M1_KERNEL_NAME = "ssm_state_update_m1"


def _m1_kernel(layer_ref, state_ref, dt_ref, dtx_ref, a_ref, b_ref, c_ref,
               y_ref, out_ref):
    del layer_ref                                   # used by the index maps
    h = state_ref[...]                              # (bb, N, cb) float32
    decay = jnp.exp(dt_ref[...] * a_ref[...][None])  # (bb, 1, cb) x (N, cb)
    h = decay * h + dtx_ref[...] * b_ref[...][:, :, :1]
    out_ref[...] = h
    y_ref[...] = jnp.sum(h * c_ref[...][:, :, :1], axis=1, keepdims=True)


def _channel_block(channels: int, limit: int = 8192) -> int:
    """Channels a grid step holds: all of them up to `limit` (a row's block
    is then N x 4 bytes x that: 0.33 MB at 16 x 5120), else the largest
    multiple of 128 under it that divides."""
    if channels <= limit or channels % _LANES:
        return channels
    return next(c for c in range(limit - limit % _LANES, 0, -_LANES)
                if channels % c == 0)


def ssm_state_update_m1(state: jnp.ndarray, layer, x: jnp.ndarray,
                        dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                        c: jnp.ndarray, d: jnp.ndarray,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the Mamba-1 recurrence on layer `layer` of the stacked
    `state`.

    state (L, B, N, C) float32; layer: int or () int32; x, dt (B, C), dt
    after the softplus; a (N, C), negative; b, c (B, N); d (C,). Returns
    `(y (B, C) float32, state)`: the same buffer where the caller donates or
    carries it. Everything is computed in float32.
    """
    nl, bsz, n, ch = state.shape
    if state.dtype != jnp.float32:
        raise ValueError(f"ssm_state_update_m1: the state is {state.dtype}; "
                         "the recurrence is kept in float32")
    if a.shape != (n, ch) or b.shape != (bsz, n) or c.shape != b.shape \
            or x.shape != (bsz, ch) or dt.shape != x.shape:
        raise ValueError(f"ssm_state_update_m1: state {state.shape}, a "
                         f"{a.shape}, b {b.shape}, c {c.shape}, x {x.shape}")
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    bb, cb = _row_block(bsz), _channel_block(ch)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def lanes(t):      # (B, N) -> (B, N, 128): a column the kernel broadcasts
        return jnp.broadcast_to(t.astype(f32)[..., None], (bsz, n, _LANES))

    row = pl.BlockSpec((bb, 1, cb), lambda i, j, l: (i, 0, j))
    col = pl.BlockSpec((bb, n, _LANES), lambda i, j, l: (i, 0, 0))
    slab = pl.BlockSpec((None, bb, n, cb), lambda i, j, l: (l[0], i, 0, j))
    y, state = pl.pallas_call(
        _m1_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bsz // bb, ch // cb),
            in_specs=[slab, row, row,
                      pl.BlockSpec((n, cb), lambda i, j, l: (0, j)), col, col],
            out_specs=[row, slab]),
        out_shape=[jax.ShapeDtypeStruct((bsz, 1, ch), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={1: 1},       # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret() if interpret is None else interpret,
        name=M1_KERNEL_NAME,
    )(layer, state, dt[:, None], (dt * x)[:, None], a.astype(f32), lanes(b),
      lanes(c))
    return y[:, 0] + d.astype(f32)[None] * x, state


def ssm_state_update_m1_reference(state, layer, x, dt, a, b, c, d):
    """The same step in plain `jax.numpy` (tests, `chip_smoke`)."""
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    h = jnp.exp(dt[:, None, :] * a.astype(f32)[None]) * state[layer] \
        + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(h * c[:, :, None], axis=1) + d.astype(f32)[None] * x
    return y, state.at[layer].set(h)
