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

- THE `dW` REDUCTION AS AN EXCHANGE (`land_dw`, PR 57): under a ZeRO plan
  the gradient accumulators are cut over `data`, and the partitioner makes
  each kernel's `dW` by all-reducing the WHOLE product synchronously and
  keeping a slice. Here a `data` rank's product of ITS rows (both
  half-batches', added locally) goes under the leading axis of a carrier
  the block makes of each kernel, and the carrier's backward sends each
  peer its slice and adds what it is sent
  (`comm.reduce_scatter_by_exchange`) straight onto the shard the plan
  gives that leaf's accumulator, which the layer READS from the plan of
  the step being traced (`zero/partition.accumulator_at_rest`). With
  nothing synchronous left in the backward layer, three backward-only ties
  order its phases (`DominoTransformerLayer`, `backward_after`).

`exchange_layout` decides from what can be observed (the installed mesh, the
rows, the widths) and `land_dw` from the plan and each kernel's place and
shape; where they answer None nothing here is called and the partitioner
places what it placed before.

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

MEASURED with the `dW` exchanges (my chip runs, PR 57; the same cell, parent
and change in one call, the tree as sent, seeds 2147485711-3; `PERF.md`
sections 5 and 6): 35,407 -> 38,510 and 35,413 -> 38,527 tokens/s (+8.8%), a
step 925.3 -> 850.6 ms, the backward 512.8 -> 438.9 ms a step (a backward
layer 6.41 -> 5.49 ms), the seven synchronous `all-reduce-scatter` fusions
(1.27 ms a backward layer) gone, every first loss the parent's bit for bit.
A backward layer now: FFN(1)'s products, START of its `dx` exchange;
`down_proj`'s `dW` of both halves, START of its exchange; FFN(0)'s `dW` of
`gate_proj` and `up_proj`, START of theirs (11.3 MB each, together on the
`data` link), FFN(0)'s `dx` products; attention(1) (its flash kernel 183 us)
under FFN(0)'s `dx` exchange and the three `dW` exchanges, all done with 0 us
of wait; attention(0) under attention(1)'s `dx` exchange, whose done waits
66 us; then the layer's end in the open: attention(0)'s `dx` exchange and the
four attention kernels' `dW` exchanges (4.5 MB) with nothing left to cover
them, about 200 us, which the trace shows as the wait of the small
synchronous all-reduce of the `q`/`k`/`v` BIAS gradients queued behind them
(`dw_reduce_ms.x4` reads that wait, 16.2 ms a step, and none of the
kernels'). With PR 53's hold in place of the three ties the same exchanges
ran 871.7 ms a step: its `dx` exchanges had lain under the synchronous `dW`
reductions (58 us of wait at FFN(0)'s done, 255 us at the layer's end with
both attention exchanges sharing the link, 125 us of a parameter gather
queued behind `gate_proj`'s and `up_proj`'s `dW` on the `data` link).
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
from deepspeed_tpu.runtime.zero.partition import accumulator_at_rest
from deepspeed_tpu.utils.partitioning import (BATCH_AXES, DEFAULT_RULES,
                                              ambient_manual_mesh,
                                              current_mesh, shard_along)

# `checkpoint_name` of a row-parallel product's SUMMED output: a `shard_map`
# equation is no dot, so a remat policy that saves dots saves this name too
# (`models/llama.py:_remat_policy`), or the backward would run the product
# and its exchange a second time.
TP_EXCHANGE = "tp_exchange"
# `jax.named_scope` of a kernel's `dW` exchange over the batch axes: the
# program map's by-scope tables name its waits by it (docs/telemetry.md)
DW_EXCHANGE = "dw_exchange"


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


def column_parallel(layout: ExchangeLayout, copies, landing=None) -> Callable:
    """`nn.Dense(dot_general=)` of a column-parallel kernel `(D, F)` over the
    `x` that `copies = copy_to_model(x)` was made of. The forward is the
    plain product, the partitioner's own (no collective). `dx` is not
    handed to `x`: each `model` rank's partial `dy @ W^T` goes, unsummed,
    under the leading axis of `copies`' cotangent, where the products of one
    site add up locally. `dW` is the plain product too, unless `landing =
    land_dw(kernel)` is handed in: then each batch rank's product of ITS
    rows goes, unsummed, under the leading axis of `landing`'s cotangent."""
    mesh, batch, model = layout

    def product(x, kernel, dimension_numbers, precision=None):
        dot = _dot(dimension_numbers, precision)
        rows = P(batch, *[None] * (x.ndim - 2))

        def partial_dx(g, w):
            return jnp.einsum("...f,df->...d", g, w, precision=precision)[None]

        def backward(operands, g):
            x, kernel = operands
            if landing is None:
                dx = jax.shard_map(
                    partial_dx, mesh=mesh,
                    in_specs=(P(*rows, model), P(None, model)),
                    out_specs=P(model, batch), check_vma=False)(g, kernel)
                dw = jax.vjp(lambda w: dot(x, w), kernel)[1](g)[0]
                return dx, jnp.zeros_like(x), dw
            dx, dw = jax.shard_map(
                lambda g, x, w: (partial_dx(g, w), jax.vjp(
                    lambda w: dot(x, w), w)[1](g)[0][None]),
                mesh=mesh,
                in_specs=(P(*rows, model), rows, P(None, model)),
                out_specs=(P(model, batch), P(batch, None, model)),
                check_vma=False)(g, x, kernel)
            return dx, dw, jnp.zeros_like(x), jnp.zeros_like(kernel)

        if landing is None:
            partials = jax.custom_vjp(lambda copies, x, kernel: dot(x, kernel))
            partials.defvjp(
                lambda copies, x, kernel: (dot(x, kernel), (x, kernel)),
                backward)
            return partials(copies, x, kernel)
        partials = jax.custom_vjp(
            lambda copies, landing, x, kernel: dot(x, kernel))
        partials.defvjp(
            lambda copies, landing, x, kernel: (dot(x, kernel), (x, kernel)),
            backward)
        return partials(copies, landing, x, kernel)

    return product


def row_parallel(layout: ExchangeLayout, landing=None) -> Callable:
    """`nn.Dense(dot_general=)` of a row-parallel kernel `(F, D)`, Megatron's
    `g`: each `model` rank multiplies its slice of the features in a manual
    region and the partial sums are EXCHANGED there; the output carries
    `TP_EXCHANGE` for the remat policies. The kernel enters as
    `P(model, None)`: a ZeRO-3 kernel at rest is gathered over `data` by the
    partitioner before the region, as without it. Backward: the two plain
    products, which need no collective over `model`; with `landing =
    land_dw(kernel)` they are made in a manual region and each batch rank's
    `dW` of ITS rows goes, unsummed, under the leading axis of `landing`'s
    cotangent."""
    mesh, batch, model = layout

    def product(x, kernel, dimension_numbers, precision=None):
        dot = _dot(dimension_numbers, precision)
        rows = P(batch, *[None] * (x.ndim - 2))

        def forward(x, kernel):
            summed = jax.shard_map(
                lambda x, w: comm.all_reduce_by_exchange(dot(x, w), model),
                mesh=mesh, in_specs=(P(*rows, model), P(model, None)),
                out_specs=P(batch), check_vma=False)(x, kernel)
            return checkpoint_name(summed, TP_EXCHANGE)

        if landing is None:
            exchanged = jax.custom_vjp(forward)
            exchanged.defvjp(
                lambda x, kernel: (forward(x, kernel), (x, kernel)),
                lambda operands, g: jax.vjp(dot, *operands)[1](g))
            return exchanged(x, kernel)

        def backward(operands, g):
            x, kernel = operands

            def products(x, w, g):
                dx, dw = jax.vjp(dot, x, w)[1](g)
                return dx, dw[None]

            dx, dw = jax.shard_map(
                products, mesh=mesh,
                in_specs=(P(*rows, model), P(model, None), rows),
                out_specs=(P(*rows, model), P(batch, model, None)),
                check_vma=False)(x, kernel, g)
            return dw, dx, jnp.zeros_like(kernel)

        exchanged = jax.custom_vjp(lambda landing, x, kernel:
                                   forward(x, kernel))
        exchanged.defvjp(
            lambda landing, x, kernel: (forward(x, kernel), (x, kernel)),
            backward)
        return exchanged(landing, x, kernel)

    return product


def parallel_products(x, layout: Optional[ExchangeLayout], landings=None,
                      after=None, before=None, together=None):
    """`(column, row, tied)`: `column` and `row` each take a kernel's name
    and give the `nn.Dense(dot_general=)` hook of that kernel, a product
    that reads `x` or the row-parallel product that follows them; a hook is
    None where nothing is named, which is `nn.Dense`'s own product.
    `landings`: the kernels' `land_dw`, by name, of those whose `dW` is
    exchanged. One array may be handed in to order the BACKWARD by, and
    comes back as `tied` to be used in its place: `after`, whose cotangent
    nobody may use before this site's partial `dx` stand; `before`, whose
    cotangent must stand before this site's partial `dx` are exchanged;
    `together`, both at once (`backward_after`, `backward_together`)."""
    if layout is None:
        return (lambda name: None,) * 2 + (None,)
    landings = landings or {}
    copies, tied = copy_to_model(x, layout), None
    if after is not None:
        tied, copies = backward_after(after, copies)
    elif before is not None:
        copies, tied = backward_after(copies, before)
    elif together is not None:
        copies, tied = backward_together(copies, together)
    return (lambda name: column_parallel(layout, copies, landings.get(name)),
            lambda name: row_parallel(layout, landings.get(name)), tied)


def _landing_shard(layout: ExchangeLayout, path, shape, use: P):
    """`(dim, spec)`: the dimension of the kernel at `path` (of `shape`,
    entering its product as `use`) that the gradient's accumulator is cut
    over the batch axes, and the accumulator's spec for ONE layer's kernel;
    None where the installed plan does not cut this kernel so: no plan, a
    leaf it keeps whole, a stack cut along its layers, a dimension shared
    with `model`, batch axes other than the rows'."""
    acc = accumulator_at_rest(path)
    if acc is None or not layout.batch:
        return None
    spec = (*acc.sharding.spec,
            *[None] * (len(acc.shape) - len(acc.sharding.spec)))
    if acc.shape[1:] == shape and spec[0] is None:
        spec = spec[1:]                       # a scanned stack, cut in a layer
    elif acc.shape != shape:
        return None
    use = (*use, *[None] * (len(shape) - len(use)))
    batch = layout.batch if len(layout.batch) > 1 else layout.batch[0]
    cut = [d for d, (at_rest, used) in enumerate(zip(spec, use))
           if at_rest != used]
    if len(cut) != 1 or spec[cut[0]] != batch or use[cut[0]] is not None:
        return None
    return cut[0], P(*spec)


def land_dw(layout: ExchangeLayout, kernels: dict, path=()) -> dict:
    """The carriers of the layer's `dW` reductions over the batch axes, by
    kernel name. `kernels`: `{name: (kernel, product)}`, the kernel in the
    dtype its product reads and which product that is, `"column"` or
    `"row"`; `path`: the kernels' module in the parameters' tree. A carrier is
    `(ranks, *kernel.shape)` with the leading axis over the batch axes: no
    transfer (nothing reads it forward), and handed to every product of the
    kernel (`column_parallel`, `row_parallel`) it collects each batch rank's
    partial `dW`, the half-batches' added locally. Backward: ONE
    `comm.reduce_scatter_by_exchange` a kernel straight onto the shard the
    ZeRO plan gives the gradient's accumulator, in the products' dtype
    (over two ranks `a + b` is `b + a`: the all-reduce's bits), where the
    partitioner all-reduces the WHOLE `dW` synchronously and keeps a slice.
    Only kernels the installed plan cuts so are named (`_landing_shard`);
    the others keep the partitioner's form."""
    mesh, batch, model = layout
    enters = {"column": P(None, model), "row": P(model, None)}

    def land(kernel, use, dim, spec):
        held = P(batch, *use)

        def forward(kernel):
            return jax.lax.with_sharding_constraint(
                jnp.broadcast_to(kernel[None],
                                 (layout.batch_ranks, *kernel.shape)),
                NamedSharding(mesh, held))

        def backward(_, partials):
            with jax.named_scope(DW_EXCHANGE):
                return (jax.shard_map(
                    lambda p: comm.reduce_scatter_by_exchange(
                        p[0], batch, scatter_dim=dim),
                    mesh=mesh, in_specs=held, out_specs=spec,
                    check_vma=False)(partials),)

        landing = jax.custom_vjp(forward)
        landing.defvjp(lambda kernel: (forward(kernel), None), backward)
        return landing(kernel)

    landings = {}
    for name, (kernel, product) in kernels.items():
        shard = _landing_shard(layout, (*path, name, "kernel"), kernel.shape,
                               enters[product])
        if shard is not None:
            landings[name] = land(kernel, enters[product], *shard)
    return landings


def hold_until(ready, held):
    """`(ready, held)` with `held` unusable before `ready` is computed
    (`optimization_barrier`; its transpose ties the two cotangents the same
    way): the hint that keeps two half-batches a phase apart where the
    kernels' `dW` reductions are the partitioner's."""
    return jax.lax.optimization_barrier((ready, held))


@jax.custom_vjp
def forward_hold(ready, held):
    """`hold_until` forward ONLY (its backward passes both cotangents
    untouched): where ties of their own order the backward, the forward
    stays the program it was, bit for bit."""
    return jax.lax.optimization_barrier((ready, held))


forward_hold.defvjp(lambda ready, held: (forward_hold(ready, held), None),
                    lambda _, g: g)


@jax.custom_vjp
def backward_after(held, ready):
    """`(held, ready)` as they are, forward; backward, `held`'s cotangent is
    not usable before `ready`'s is computed (`optimization_barrier`, whose
    other output is dropped: `ready`'s own cotangent passes untouched): a
    hint that orders a backward layer's phases at no cost to the forward."""
    return held, ready


def _held_after_ready(_, g):
    g_held, g_ready = g
    return jax.lax.optimization_barrier((g_held, g_ready))[0], g_ready


backward_after.defvjp(lambda held, ready: ((held, ready), None),
                      _held_after_ready)


@jax.custom_vjp
def backward_together(a, b):
    """`backward_after` both ways: neither cotangent is usable before both
    are computed."""
    return a, b


backward_together.defvjp(lambda a, b: ((a, b), None),
                         lambda _, g: jax.lax.optimization_barrier(g))


def count_exchanges(jaxpr, axes=None, scope: Optional[str] = None) -> int:
    """The `ppermute`s over any of `axes` (default: the tensor-parallel
    axis) in a traced program, nested jaxprs included (a scanned layer's
    body counts once); with `scope`, only those traced under that
    `jax.named_scope`. Over `model`: 8 a layer body pair where the layers'
    reductions are exchanges (2 sites x 2 half-batches, forward and
    backward), more if a remat policy lets the backward run one again.
    Over the batch axes under `DW_EXCHANGE`: a kernel's `dW` exchange is
    one a peer, 7 a layer on two batch ranks. 0 where nothing is named."""
    axes = (DEFAULT_RULES["heads"],) if axes is None else tuple(axes)
    found = 0
    for eqn in jaxpr.eqns:
        # an equation's name stack is relative to the one that holds it
        under = scope is None or scope in str(
            eqn.source_info.name_stack).split("/")
        if eqn.primitive.name == "ppermute" and under:
            over = eqn.params["axis_name"]
            over = over if isinstance(over, tuple) else (over,)
            found += any(a in over for a in axes)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += count_exchanges(sub, axes, None if under else scope)
    return found


class DominoTransformerLayer:
    """(attn_fn, mlp_fn) as one pre-norm layer, over an array of rows or
    over a PAIR of half-batches, interleaved.

    attn_fn: (B, S, D) -> (B, S, D) and mlp_fn: (B, S, D) -> (B, S, D),
    containing TP-sharded matmuls (their output reductions are the
    collectives being overlapped); over a pair, half 0's mlp_fn is also
    handed an array to HOLD, `mlp_fn(x, held) -> (out, held)`, which it
    gives back once its activation stands (`hold_until`); or, where
    `ordered`, both functions ONE array by keyword, `fn(x, after=) -> (out,
    tied)`, `after` / `before` / `together`, which they tie to their
    input's partial `dx` (`parallel_products`); `mid` names the residual
    between the two (`checkpoint_name`).
    `ordered`: the kernels' `dW` reductions are exchanges too (`land_dw`).
    """

    def __init__(self, attn_fn: Callable, mlp_fn: Callable,
                 input_ln: Callable = None, post_ln: Callable = None,
                 mid: Callable = None, ordered: bool = False):
        self.attn_fn = attn_fn
        self.mlp_fn = mlp_fn
        self.input_ln = input_ln or (lambda x: x)
        self.post_ln = post_ln or (lambda x: x)
        self.mid = mid or (lambda x: x)
        self.ordered = ordered

    def __call__(self, x):
        if not isinstance(x, tuple):
            h = self.mid(x + self.attn_fn(self.input_ln(x)))
            return h + self.mlp_fn(self.post_ln(h))
        x0, x1 = x
        # Interleave: attn(x1) is independent of attn(x0)'s exchange, and
        # mlp(h0) is independent of attn(x1)'s: each lies under the other.
        if not self.ordered:
            # Half 1's attention output is HELD until half 0's FFN has its
            # activation. Forward that changes nothing the scheduler did
            # not do already; its transpose keeps the backward's halves a
            # phase apart (FFN(1); FFN(0)'s input products only after
            # FFN(1)'s `dx` exchange is done; attention(1); attention(0)),
            # so each `dx` exchange lies under the next phase's products
            # and the kernels' synchronous `dW` reductions, where the
            # scheduler alone ran both FFNs' products and then waited for
            # both exchanges (my chip runs, PR 53: 929.8 against 943.7 ms).
            a0 = self.attn_fn(self.input_ln(x0))
            a1 = self.attn_fn(self.input_ln(x1))
            h0 = self.mid(x0 + a0)
            m0, a1 = self.mlp_fn(self.post_ln(h0), a1)
        else:
            # With the `dW` reductions exchanges as well, nothing
            # synchronous is left for a `dx` exchange to lie under, and
            # three backward-only ties order the phases FFN(1), FFN(0),
            # attention(1), attention(0): attention(1) once FFN(0)'s
            # partial `dx` stand and FFN(1)'s exchange is done;
            # attention(0) once attention(1)'s stand; and attention(0)'s
            # exchange starts when attention(1)'s is DONE (this scheduler
            # moves a start down to its done unless a later start needs
            # that done), so that ONE exchange, not two sharing the link,
            # is left at the layer's end. A kernel's `dW` exchange starts
            # when half 0's product is added and has the later phases over
            # it. Forward, half 0's FFN still holds half 1's attention
            # output until its activation stands (`forward_hold`, in
            # mlp_fn): the forward is then the parent's to the bit (my
            # chip runs, PR 57: 850.6 ms a step; 855.2 without that hold
            # and a first loss 1e-5 off; 871.7 with PR 53's hold alone).
            a0, x1n = self.attn_fn(self.input_ln(x0),
                                   before=self.input_ln(x1))
            a1, a0 = self.attn_fn(x1n, after=a0)
            h0 = self.mid(x0 + a0)
            m0, a1 = self.mlp_fn(self.post_ln(h0), together=a1)
        h1 = self.mid(x1 + a1)
        m1 = self.mlp_fn(self.post_ln(h1))
        return h0 + m0, h1 + m1
