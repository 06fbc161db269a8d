"""Domino — tensor-parallel compute/communication overlap (reference
`runtime/domino/transformer.py`: `DominoTransformerLayer`, async allreduce
handles `NoOper:55`, `_CopyToModelParallelRegionA:78`).

The reference splits each batch into two micro-chunks and hand-schedules
chunk-1 compute against chunk-0's TP allreduce on side streams. Here the
same two things are said declaratively, and neither works without the other:

- THE INTERLEAVE (`DominoTransformerLayer`): a layer walks its rows as two
  half-batches (attention of half 0, attention of half 1, FFN of half 0, FFN
  of half 1), which is the independent work a reduction can lie under. The
  halves are cut ONCE, before the layer scan (`split_rows`: every device's
  own rows in two, so nothing moves), carried through it as a pair and
  joined once after it (`merge_rows`).
- THE REDUCTION AS AN EXCHANGE (`row_parallel`, `copy_to_model`): Megatron's
  pair of conjugate operators. A row-parallel product (`o_proj`,
  `down_proj`) is made in a manual region over the installed mesh and its
  partial sums are added by `comm.all_reduce_by_exchange`: a
  `collective-permute`, which the scheduler starts early and finishes late,
  where the partitioner's `all-reduce` is a synchronous op on a v5e's op
  line. Its backward is the plain products. The column-parallel INPUT (what
  `q`/`k`/`v` and `gate`/`up` read) is an identity forward that hands each
  `model` rank its own copy under a leading axis, so that the backward's
  partial `dx` of every product reading it are summed locally and exchanged
  ONCE a site.

`exchange_layout` decides from what can be observed (the installed mesh, the
rows, the widths); where it answers None nothing here is called and the
partitioner places what it placed before.

MEASURED on a 2x2 of TPU v5 lite (my chip runs, PR 53; Qwen2.5-3B at 20
layers, dp2 x tp2, ZeRO-3, 2 rows of 2,048 tokens a device a micro-batch,
`checkpoint_dots`; parent and change in one call, seeds 5300012011-3;
`PERF.md` sections 5 and 6 have the layers' timelines): 34,014 -> 35,107
tokens/s (+3.2%), a step 962.6 -> 933.2 ms, the trace's `all-reduce` line
0.546 s of four steps -> under 0.05, exposed collectives 16.6 -> 6.7% of
the device's time. A forward layer is 2.85 ms where it was 3.15: of its four
exchanges (8.4 MB each, 30 GB/s a direction with two in flight) half 0's
`o_proj` lies under half 1's flash kernel and `o_proj`, half 0's `down_proj`
under half 1's FFN, and the second of each phase waits 137-157 us, because
this compiler's scheduler places a `collective-permute-done` as EARLY as it
can: only work that is an ancestor of a later exchange's start ends up
under an earlier one (three scheduler options and scheduling annotations
changed nothing). A backward layer's four exchanges hide whole, given the
hold below. The r5 finding on a virtual CPU mesh (0.97x, the halves'
all-reduces merged again) was about the synchronous all-reduce: without the
exchange NAMED there is nothing for the halves to lie under.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm import comm
from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
from deepspeed_tpu.utils.partitioning import (BATCH_AXES, DEFAULT_RULES,
                                              ambient_manual_mesh,
                                              current_mesh, shard_along)

# `checkpoint_name` of a row-parallel product's SUMMED output: a `shard_map`
# equation is no dot, so a remat policy that saves dots saves this name too
# (`models/llama.py:_remat_policy`), or the backward would run the product
# and its exchange a second time.
TP_EXCHANGE = "tp_exchange"


class ExchangeLayout(NamedTuple):
    mesh: jax.sharding.Mesh
    batch: Tuple[str, ...]      # the axes larger than 1 the rows are cut over
    model: str                  # the tensor-parallel axis

    @property
    def batch_ranks(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.batch)

    @property
    def model_ranks(self) -> int:
        return self.mesh.shape[self.model]


def exchange_layout(rows: int, *widths: int) -> Optional[ExchangeLayout]:
    """Where a layer's tensor-parallel reductions can be exchanges under the
    other half-batch's compute: a topology is installed whose `model` axis
    is larger than 1 and within `comm.EXCHANGE_MAX_RANKS`, every other axis
    larger than 1 is one the batch is cut over (`BATCH_AXES`), no manual
    region encloses the call, each device holds an even number of `rows`,
    and `model` divides every one of `widths`. None otherwise (one device, a
    `sequence` or `pipe` axis, a larger `model` axis, an odd row count)."""
    mesh = current_mesh()
    sizes = nontrivial_axes(mesh)
    model = DEFAULT_RULES["heads"]
    batch = tuple(a for a in BATCH_AXES if a in sizes)
    tp = sizes.get(model, 1)
    if (not 1 < tp <= comm.EXCHANGE_MAX_RANKS
            or set(sizes) - {*batch, model} or ambient_manual_mesh()[1]):
        return None
    layout = ExchangeLayout(mesh, batch, model)
    if rows % (2 * layout.batch_ranks) or any(w % tp for w in widths):
        return None
    return layout


def split_rows(x, layout: ExchangeLayout):
    """`x` (B, ...) as a pair of half-batches: half `i` is the `i`-th half
    of the rows EACH device holds, so no row changes device."""
    nb, rest = layout.batch_ranks, x.shape[1:]
    parts = x.reshape(nb, 2, x.shape[0] // (2 * nb), *rest)
    return tuple(shard_along(parts[:, i].reshape(-1, *rest), BATCH_AXES)
                 for i in range(2))


def merge_rows(halves, layout: ExchangeLayout):
    """The inverse of `split_rows`: the rows back in their own order."""
    nb, rest = layout.batch_ranks, halves[0].shape[1:]
    parts = jnp.stack([h.reshape(nb, -1, *rest) for h in halves], axis=1)
    return shard_along(parts.reshape(-1, *rest), BATCH_AXES)


def copy_to_model(x, layout: ExchangeLayout):
    """Megatron's `f`, the carrier of its backward: `x` (B, ..., D), whole
    on every `model` rank, as `(tp, B, ..., D)` with the leading axis over
    `model` (each rank's own copy: no transfer, and nothing reads it
    forward). Backward: the ranks' partial `dx`, which every
    `column_parallel` product of `x` leaves under that axis, summed by ONE
    exchange."""
    mesh, tp = layout.mesh, layout.model_ranks
    spec = P(layout.model, layout.batch)

    def forward(x):
        return jax.lax.with_sharding_constraint(
            jnp.broadcast_to(x[None], (tp, *x.shape)),
            NamedSharding(mesh, spec))

    def backward(_, partials):
        return (jax.shard_map(
            lambda p: comm.all_reduce_by_exchange(p[0], layout.model),
            mesh=mesh, in_specs=spec, out_specs=P(layout.batch),
            check_vma=False)(partials),)

    copies = jax.custom_vjp(forward)
    copies.defvjp(lambda x: (forward(x), None), backward)
    return copies(x)


def _dot(dimension_numbers, precision):
    """`nn.Dense`'s own product, as its `dot_general` hook is handed it."""
    return partial(jax.lax.dot_general, dimension_numbers=dimension_numbers,
                   precision=precision)


def column_parallel(layout: ExchangeLayout, copies) -> Callable:
    """`nn.Dense(dot_general=)` of a column-parallel kernel `(D, F)` over the
    `x` that `copies = copy_to_model(x)` was made of. Forward and `dW` are
    the plain products, the partitioner's own (no collective). `dx` is
    not handed to `x`: each `model` rank's partial `dy @ W^T` goes, unsummed,
    under the leading axis of `copies`' cotangent, where the products of one
    site add up locally."""
    mesh, batch, model = layout

    def product(x, kernel, dimension_numbers, precision=None):
        dot = _dot(dimension_numbers, precision)

        def backward(operands, g):
            x, kernel = operands
            partial_dx = jax.shard_map(
                lambda g, w: jnp.einsum("...f,df->...d", g, w,
                                        precision=precision)[None],
                mesh=mesh,
                in_specs=(P(batch, *[None] * (g.ndim - 2), model),
                          P(None, model)),
                out_specs=P(model, batch), check_vma=False)(g, kernel)
            dw = jax.vjp(lambda w: dot(x, w), kernel)[1](g)[0]
            return partial_dx, jnp.zeros_like(x), dw

        partials = jax.custom_vjp(lambda copies, x, kernel: dot(x, kernel))
        partials.defvjp(
            lambda copies, x, kernel: (dot(x, kernel), (x, kernel)), backward)
        return partials(copies, x, kernel)

    return product


def row_parallel(layout: ExchangeLayout) -> Callable:
    """`nn.Dense(dot_general=)` of a row-parallel kernel `(F, D)`, Megatron's
    `g`: each `model` rank multiplies its slice of the features in a manual
    region and the partial sums are EXCHANGED there; the output carries
    `TP_EXCHANGE` for the remat policies. The kernel enters as
    `P(model, None)`: a ZeRO-3 kernel at rest is gathered over `data` by the
    partitioner before the region, as without it. Backward: the two plain
    products, which need no collective over `model`."""
    mesh, batch, model = layout

    def product(x, kernel, dimension_numbers, precision=None):
        dot = _dot(dimension_numbers, precision)

        def forward(x, kernel):
            summed = jax.shard_map(
                lambda x, w: comm.all_reduce_by_exchange(dot(x, w), model),
                mesh=mesh,
                in_specs=(P(batch, *[None] * (x.ndim - 2), model),
                          P(model, None)),
                out_specs=P(batch), check_vma=False)(x, kernel)
            return checkpoint_name(summed, TP_EXCHANGE)

        exchanged = jax.custom_vjp(forward)
        exchanged.defvjp(
            lambda x, kernel: (forward(x, kernel), (x, kernel)),
            lambda operands, g: jax.vjp(dot, *operands)[1](g))
        return exchanged(x, kernel)

    return product


def parallel_products(x, layout: Optional[ExchangeLayout]):
    """`(column, row)`: the `nn.Dense(dot_general=)` hooks of the products
    that read `x` and of the row-parallel product that follows them; None
    twice where nothing is named, which is `nn.Dense`'s own product."""
    if layout is None:
        return None, None
    return (column_parallel(layout, copy_to_model(x, layout)),
            row_parallel(layout))


def hold_until(ready, held):
    """`(ready, held)` with `held` unusable before `ready` is computed
    (`optimization_barrier`; its transpose ties the two cotangents the same
    way): the hint that keeps two half-batches a phase apart."""
    return jax.lax.optimization_barrier((ready, held))


def count_exchanges(jaxpr) -> int:
    """The `ppermute`s over the tensor-parallel axis in a traced program,
    nested jaxprs included (a scanned layer's body counts once): 8 a layer
    body pair where the layers' reductions are exchanges (2 sites x 2
    half-batches, forward and backward), more if a remat policy lets the
    backward run one again, 0 where nothing is named."""
    model = DEFAULT_RULES["heads"]
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            axes = eqn.params["axis_name"]
            found += model in (axes if isinstance(axes, tuple) else (axes,))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += count_exchanges(sub)
    return found


class DominoTransformerLayer:
    """(attn_fn, mlp_fn) as one pre-norm layer, over an array of rows or
    over a PAIR of half-batches, interleaved.

    attn_fn: (B, S, D) -> (B, S, D) and mlp_fn: (B, S, D) -> (B, S, D),
    containing TP-sharded matmuls (their output reductions are the
    collectives being overlapped); over a pair, half 0's mlp_fn is also
    handed an array to HOLD, `mlp_fn(x, held) -> (out, held)`, which it
    gives back once its activation stands (`hold_until`); `mid` names the
    residual between the two (`checkpoint_name`).
    """

    def __init__(self, attn_fn: Callable, mlp_fn: Callable,
                 input_ln: Callable = None, post_ln: Callable = None,
                 mid: Callable = None):
        self.attn_fn = attn_fn
        self.mlp_fn = mlp_fn
        self.input_ln = input_ln or (lambda x: x)
        self.post_ln = post_ln or (lambda x: x)
        self.mid = mid or (lambda x: x)

    def __call__(self, x):
        if not isinstance(x, tuple):
            h = self.mid(x + self.attn_fn(self.input_ln(x)))
            return h + self.mlp_fn(self.post_ln(h))
        x0, x1 = x
        # Interleave: attn(x1) is independent of attn(x0)'s exchange, and
        # mlp(h0) is independent of attn(x1)'s: each lies under the other.
        # Half 1's attention output is HELD until half 0's FFN has its
        # activation. Forward that changes nothing the scheduler did not do
        # already; its transpose keeps the backward's halves a phase apart
        # (FFN(1); FFN(0)'s input products only after FFN(1)'s `dx` exchange
        # is done; attention(1); attention(0)), so each `dx` exchange lies
        # under the next phase's products, where the scheduler alone ran
        # both FFNs' products and then waited for both exchanges (my chip
        # runs, PR 53: 929.8 against 943.7 ms a step).
        a0 = self.attn_fn(self.input_ln(x0))
        a1 = self.attn_fn(self.input_ln(x1))
        h0 = self.mid(x0 + a0)
        m0, a1 = self.mlp_fn(self.post_ln(h0), a1)
        h1 = self.mid(x1 + a1)
        m1 = self.mlp_fn(self.post_ln(h1))
        return h0 + m0, h1 + m1
