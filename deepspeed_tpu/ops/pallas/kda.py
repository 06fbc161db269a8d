"""Pallas TPU kernel for one decode step of a Kimi-Delta-Attention (KDA)
layer's state: a gated delta rule with a decay per CHANNEL (Kimi Linear,
arXiv:2510.26692). A head's state is a MATRIX `S` (d_k x d_v), float32:

    S <- diag(exp(g_t)) S                        (g_t: d_k log-decays, <= 0)
    S <- S + beta_t k_t (v_t - S^T k_t)^T        (the delta rule)
    o_t = S^T q_t

Unlike the state-space kernels of `ssm.py` (an elementwise decay and an
added outer product), the update READS the state it writes: `S^T k_t` is a
matrix-vector product with the decayed state, and the output another with
the updated one. `kda_state_update` does all of it in one pass over the
state: one read and one write of `[layer, rows, heads]` of the stacked
float32 buffer (`inference/kv_cache.RecurrentState.ssm`), in place
(`input_output_aliases`), the layer's index a prefetched scalar. A decode
step of this family is bound by exactly those bytes (2 x B x H x d_k x d_v
x 4 a layer: 4.2 MB a sequence at 32 heads of 128 x 128).

The state lies KEY-MAJOR, `(L, B, H, d_k, d_v)`: `S` itself, the value
channels on the lanes. Both products are then a multiply and a sum over the
SUBLANES (adds of whole registers and one fold), which leaves a row `(1,
d_v)`, the shape `v` arrives in and `o` leaves in, `(B, H, d_v)`. The
decays, `k`, `beta k` and `q` (a value a key channel) have to arrive as
COLUMNS: they are handed over heads-minor, `(B, H / hb, d_k, hb)`, a few KB
a row that XLA transposes, and a head's column is broadcast over the lanes.
Measured on the chip against the transposed layout (rows that broadcast for
free, but two reductions over the LANES a head): 0.88 against 1.05 ms a
layer of 128 sequences, 74% against 62% of the roofline (PERF.md, PR 47).

Grid `(B, H / hb)`: one step holds `hb` heads of one sequence (2 MB of
state at 32 heads of 128 x 128; with the result double-buffered 8 MB; 16
heads a step read 0.93 ms, 8 read 1.05).

A decay a HEAD (`g` of shape (B, H): Gated DeltaNet, arXiv:2412.06464, and
most published linear-attention models) is the same body with ONE scalar a
head in the decay's place: it arrives as a ROW, `(B, H / hb, hb, d_v)` like
`v`, broadcast over the sublanes, and the decay COLUMN and its lane
broadcast are gone (three column operands, not four). It is traced under a
second name, `gdn_state_update` (one kernel, two names, as
`decode_attention.py` and `diff_attention.py` have). With `g` (B, H, d_k)
nothing here differs from the kernel without that form, instruction for
instruction.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret

KERNEL_NAME = "kda_state_update"
HEAD_DECAY_NAME = "gdn_state_update"     # the same body, a decay a HEAD


def _kernel(layer_ref, state_ref, decay_ref, k_ref, kb_ref, q_ref, v_ref,
            o_ref, out_ref, *, hb, head_decay=False):
    del layer_ref                                   # used by the index maps
    for h in range(hb):
        # (dk, dv) x a (dk, 1) column; a decay a head: x its (1, dv) row
        s = state_ref[h] * (decay_ref[h:h + 1] if head_decay
                            else decay_ref[:, h:h + 1])
        # (1, dv): v less what the decayed state already answers to this key
        u = v_ref[h:h + 1] - jnp.sum(s * k_ref[:, h:h + 1], axis=0,
                                     keepdims=True)
        s = s + kb_ref[:, h:h + 1] * u              # kb = beta k
        out_ref[h] = s
        o_ref[h:h + 1] = jnp.sum(s * q_ref[:, h:h + 1], axis=0, keepdims=True)


def _head_block(heads: int) -> int:
    """Heads a grid step holds: 32 (2 MB of state at 128 x 128) where the
    head count divides, else all of them."""
    return 32 if heads % 32 == 0 else heads


def kda_state_update(state: jnp.ndarray, layer, q: jnp.ndarray,
                     k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     beta: jnp.ndarray, interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the gated delta rule on layer `layer` of the stacked
    `state`.

    state (L, B, H, dk, dv) float32, a head's `S`; layer: int or () int32;
    q, k (B, H, dk), as they enter the recurrence (normalised, `q` scaled);
    v (B, H, dv); g (B, H, dk) log-decay, <= 0, or (B, H): a decay a HEAD
    (`HEAD_DECAY_NAME`); beta (B, H). Returns `(o (B, H, dv) float32,
    state)`: the same buffer where the caller donates or carries it.
    Everything is computed in float32."""
    nl, bsz, nh, dk, dv = state.shape
    if state.dtype != jnp.float32:
        raise ValueError(f"kda_state_update: the state is {state.dtype}; the "
                         "recurrence is kept in float32")
    head_decay = g.ndim == 2
    if q.shape != (bsz, nh, dk) or k.shape != q.shape \
            or g.shape != q.shape[:2 if head_decay else 3] \
            or v.shape != (bsz, nh, dv) or beta.shape != (bsz, nh):
        raise ValueError(f"kda_state_update: state {state.shape}, q {q.shape}, "
                         f"k {k.shape}, v {v.shape}, g {g.shape}, beta "
                         f"{beta.shape}")
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    hb = _head_block(nh)
    ng = nh // hb
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def cols(t):       # (B, H, dk) -> (B, ng, dk, hb): a head's a column
        return jnp.swapaxes(t.reshape(bsz, ng, hb, dk), 2, 3)

    col = pl.BlockSpec((None, None, dk, hb), lambda i, j, l: (i, j, 0, 0))
    row = pl.BlockSpec((None, None, hb, dv), lambda i, j, l: (i, j, 0, 0))
    slab = pl.BlockSpec((None, None, None, hb, dk, dv),
                        lambda i, j, l: (l[0], i, j, 0, 0, 0))
    stacked = state.reshape(nl, bsz, ng, hb, dk, dv)
    if head_decay:      # a head's one decay, a row of it over the lanes
        kernel = functools.partial(_kernel, hb=hb, head_decay=True)
        decay = jnp.broadcast_to(jnp.exp(g).reshape(bsz, ng, hb, 1),
                                 (bsz, ng, hb, dv))
    else:
        kernel, decay = functools.partial(_kernel, hb=hb), cols(jnp.exp(g))
    o, stacked = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bsz, ng),
            in_specs=[slab, row if head_decay else col, col, col, col, row],
            out_specs=[row, slab]),
        out_shape=[jax.ShapeDtypeStruct((bsz, ng, hb, dv), f32),
                   jax.ShapeDtypeStruct(stacked.shape, f32)],
        input_output_aliases={1: 1},       # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret() if interpret is None else interpret,
        name=HEAD_DECAY_NAME if head_decay else KERNEL_NAME,
    )(layer, stacked, decay, cols(k), cols(beta[..., None] * k),
      cols(q), v.reshape(bsz, ng, hb, dv))
    return o.reshape(bsz, nh, dv), stacked.reshape(state.shape)


def kda_step(s, q, k, v, g, beta):
    """The same step on a bare state `s` (..., dk, dv) in plain `jax.numpy`:
    operands (..., dk) and (..., dv), beta (...), g (..., dk) or, a decay a
    head, (...). Returns (o, s)."""
    if g.ndim < k.ndim:
        g = g[..., None]
    s = s * jnp.exp(g)[..., :, None]
    u = v - jnp.sum(s * k[..., :, None], axis=-2)
    s = s + (beta[..., None] * k)[..., :, None] * u[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def kda_state_update_reference(state, layer, q, k, v, g, beta):
    """`kda_state_update` in plain `jax.numpy` (tests, `chip_smoke`, and the
    model's own path off the chip)."""
    f32 = jnp.float32
    o, s = kda_step(state[layer], *(t.astype(f32) for t in (q, k, v, g, beta)))
    return o, jax.lax.dynamic_update_index_in_dim(state, s, layer, 0)
