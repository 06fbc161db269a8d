"""Sequence-parallel / memory-chunked cross entropy.

Counterpart of reference `deepspeed/sequence/cross_entropy.py`
(`vocab_sequence_parallel_cross_entropy`) and the FPDT chunked-loss path
(`sequence/fpdt_layer.py:1137`). The reference splits the vocab matmul per
TP rank and all-reduces partial logsumexps; here the chunking is over the
*sequence* axis — per chunk we compute (B, C, V) logits, reduce them to a
per-token loss, and drop them before the next chunk. Vocab-parallel TP
falls out declaratively: with `lm_head` sharded over 'model' on the vocab
dim, XLA reduces the chunk logsumexp across TP ranks.

The logits and the softmax are computed ONCE. The loss is a
`jax.custom_vjp` whose forward rule makes the gradient while a chunk's
logits are live (`dlogits = (softmax - onehot) * mask`, every entry in
[-1, 1] when it is rounded to the matmuls' dtype, then
`dh_blk = dlogits @ W^T` and `dW += h_blk^T @ dlogits`, the latter carried
across chunks in float32), and whose backward rule only scales `dh` and
`dW` by `cotangent / count` in float32: three vocabulary-wide matmuls and
one softmax pass a chunk, no recompute, and no second gather of a sharded
head. Neither the mean nor a loss scale reaches a half-precision value
before that last multiply, so float16 with any loss scale keeps what
float32 would. The residuals are `dh` (the size of `h`), `dW` (the size of
the head, in float32) and the token count. Without `grad` around it the
loss runs the plain forward alone. `jax.jvp` straight over the loss is not
defined; over its `jax.grad` it is (`runtime/eigenvalue.py`), because that
differentiates the two rules.

On a mesh the loop names where its own collectives stand (`_loop_layout`:
an installed topology whose axes larger than 1 are the batch's, `repl` /
`data` / `expert`, and the vocabulary's, `model`; anything else, one device
included, runs the loop with no sharding named in it, which is the
partitioner's program). With the head at rest as the ZeRO-3 plan lays it,
vocabulary over `model` and width over `data`:

- OUTSIDE the loop, once a call: ONE all-gather of the head over `data`
  (`_head_for_loop`); the scan closes over the whole-width copy, and both
  of a chunk's uses (`logits`, `dh_blk`) read it. The partitioner alone
  gathers it once a CHUNK: XLA hoists no collective out of a `while`.
- INSIDE the loop, once a chunk: no all-gather; the all-reduces over
  `model` that vocabulary parallelism needs (the row maxima, the row sums
  with the label's logit, `dh_blk`); and ONE transfer over `data` that has
  the SHARD's shape `(V / tp, D / dp)`: a rank's `dW` product is a partial
  sum over its rows of the batch, each peer is sent its slice of it, and
  the slices are added in float32 straight into the carry, which is laid
  as that shard (`_onto_carry_shard`, `comm.reduce_scatter_by_exchange`).
  The partitioner alone all-reduces the WHOLE `(V / tp, D)` product and
  then keeps a slice. A reduce-scatter would say the same, but the chip's
  compiler keeps none on a 2x2 (it makes an all-reduce and a slice of it
  again, and moves it behind the loop); the permute is half the bytes and
  runs under the `dh_blk` product.

The gradient comes back on that shard; a head at rest in another layout
(`(model, None)`, the vocabulary over `data`) is re-laid ONCE after the
loop by whoever asked for it.

Peak logits memory: O(B · chunk · V) instead of O(B · S · V) — the piece
that makes 128k-context training (BASELINE config 5) fit.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import comm
from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
from deepspeed_tpu.utils.partitioning import (BATCH_AXES, DEFAULT_RULES,
                                              ambient_manual_mesh,
                                              current_mesh, shard_along)


def _loop_layout(h, lm_head, tied_embedding):
    """`(mesh, batch axes, vocabulary axis)` where the chunk loop can place
    its own collectives: a topology is installed, every axis of it larger
    than 1 is one the batch (`BATCH_AXES`) or the vocabulary (the rules'
    `"vocab"`) is sharded over, and each divides the dimension it cuts.
    None otherwise (one device, a `sequence` or `pipe` axis, an enclosing
    manual region, a shape that does not divide): the loop then carries no
    constraint at all and the partitioner places what it needs, as before."""
    mesh = current_mesh()
    sizes = nontrivial_axes(mesh)
    batch = tuple(a for a in BATCH_AXES if a in sizes)
    vocab = DEFAULT_RULES["vocab"] if DEFAULT_RULES["vocab"] in sizes else None
    if (not sizes or set(sizes) - {*batch, vocab}
            or ambient_manual_mesh()[1]):
        return None
    v, d = lm_head.shape if tied_embedding else lm_head.shape[::-1]
    nb = math.prod(sizes[a] for a in batch)
    if h.shape[0] % nb or d % nb or v % sizes.get(vocab, 1):
        return None
    return mesh, batch, vocab


def _head_for_loop(lm_head, tied_embedding, layout):
    """The head as the chunk loop reads it: the vocabulary on its own axes,
    the width WHOLE. A ZeRO-3 head at rest is gathered over `data` here,
    once, outside the `lax.scan`, which then closes over the copy (XLA
    hoists no collective out of a `while`)."""
    if layout is None:
        return lm_head
    vocab = layout[2]
    return shard_along(lm_head, *((vocab, None) if tied_embedding
                                  else (None, vocab)))


def _onto_carry_shard(product, tied_embedding, layout):
    """`product(dlogits, h_blk)` with a sharded batch's partial sums reduced
    straight onto the carry's shard: the head's width cut over the batch's
    axes, the vocabulary on its own. A rank multiplies ITS rows of the
    batch, sends each peer the peer's slice of the result and adds what it
    is sent, in float32: `(n - 1) / n` of the matrix on the wire where the
    partitioner all-reduces all of it and then keeps a slice."""
    mesh, batch, vocab = layout

    def exchanged(dlogits, h_blk):
        return comm.reduce_scatter_by_exchange(
            product(dlogits, h_blk), batch, sum_dtype=jnp.float32,
            scatter_dim=1 if tied_embedding else 0)

    return jax.shard_map(
        exchanged, mesh=mesh, in_specs=(P(batch, None, vocab), P(batch)),
        out_specs=P(vocab, batch) if tied_embedding else P(batch, vocab))


def _chunk_terms(h_blk, y_blk, lm_head, ignore_index, tied_embedding):
    """One chunk's float32 logits reduced to its loss sum, with what the
    gradient needs of the same softmax pass: `exp(logits - max)`, its row
    sums, the clipped labels and the mask."""
    if tied_embedding:
        logits = jnp.einsum("bcd,vd->bcv", h_blk, lm_head)
    else:
        logits = h_blk @ lm_head
    logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - top)
    e_sum = jnp.sum(e, axis=-1, keepdims=True)
    lse = (jnp.log(e_sum) + top)[..., 0]
    y_safe = jnp.clip(y_blk, 0, logits.shape[-1] - 1)
    gold = jnp.take_along_axis(logits, y_safe[..., None], axis=-1)[..., 0]
    mask = (y_blk != ignore_index).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), (e, e_sum, y_safe, mask)


def _token_count(labels, ignore_index):
    """The mean's denominator: known from the labels, before any logits."""
    return jnp.maximum(
        jnp.sum((labels != ignore_index).astype(jnp.float32)), 1.0)


def _by_chunk(x, chunk):
    """(B, S, ...) -> (S // chunk, B, chunk, ...): a scan's leading axis."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // chunk, chunk, *x.shape[2:]), 1, 0)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunked_ce(h, lm_head, labels, chunk, ignore_index, tied_embedding):
    lm_head = _head_for_loop(lm_head, tied_embedding,
                             _loop_layout(h, lm_head, tied_embedding))

    def body(loss_sum, xs):
        blk_sum, _ = _chunk_terms(*xs, lm_head, ignore_index, tied_embedding)
        return loss_sum + blk_sum, None

    loss_sum, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (_by_chunk(h, chunk), _by_chunk(labels, chunk)))
    return loss_sum / _token_count(labels, ignore_index)


def _chunked_ce_fwd(h, lm_head, labels, chunk, ignore_index, tied_embedding):
    operand_dtype = jnp.result_type(h.dtype, lm_head.dtype)
    # float16 alone can overflow on a sum over a chunk's tokens
    dw_blk_dtype = (jnp.float32 if operand_dtype == jnp.float16
                    else operand_dtype)
    layout = _loop_layout(h, lm_head, tied_embedding)
    lm_head = _head_for_loop(lm_head, tied_embedding, layout)

    def dw_product(dlogits, h_blk):
        if tied_embedding:
            return jnp.einsum("bcv,bcd->vd", dlogits, h_blk,
                              preferred_element_type=dw_blk_dtype)
        return jnp.einsum("bcd,bcv->dv", h_blk, dlogits,
                          preferred_element_type=dw_blk_dtype)

    if layout is not None and layout[1]:
        dw_product = _onto_carry_shard(dw_product, tied_embedding, layout)

    def body(carry, xs):
        loss_sum, dw = carry
        h_blk, y_blk = xs
        blk_sum, (e, e_sum, y_safe, mask) = _chunk_terms(
            h_blk, y_blk, lm_head, ignore_index, tied_embedding)
        # UNSCALED, so every entry is in [-1, 1] when it is rounded to the
        # matmuls' dtype: the mean's 1 / count and the cotangent (a loss
        # scale, under float16) are applied in float32 by the backward, and
        # neither can push a float16 `dlogits` or `dh` under its range
        mask = mask[..., None]
        softmax_part = e * (mask / e_sum)
        hit = y_safe[..., None] == jnp.arange(e.shape[-1])
        dlogits = jnp.where(hit, softmax_part - mask, softmax_part)
        dlogits = dlogits.astype(operand_dtype)
        # a chunk's products leave the matmul in its operands' dtype, as the
        # transpose of the forward's would (and a sharded batch's partial
        # `dw_blk` crosses the wire in it); the SUM over chunks is float32
        dh_blk = jnp.einsum("bcv,vd->bcd" if tied_embedding else "bcv,dv->bcd",
                            dlogits, lm_head)
        dw_blk = dw_product(dlogits, h_blk)
        return ((loss_sum + blk_sum, dw + dw_blk.astype(jnp.float32)),
                dh_blk.astype(h.dtype))

    (loss_sum, dw), dh = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32),
               jnp.zeros(lm_head.shape, jnp.float32)),
        (_by_chunk(h, chunk), _by_chunk(labels, chunk)))
    dh = jnp.moveaxis(dh, 0, 1).reshape(h.shape)
    count = _token_count(labels, ignore_index)
    # the empty array carries the head's dtype to the backward, not the head
    return loss_sum / count, (dh, dw, count, jnp.zeros((0,), lm_head.dtype))


def _chunked_ce_bwd(chunk, ignore_index, tied_embedding, residuals, g):
    dh, dw, count, head_like = residuals
    scale = g.astype(jnp.float32) / count
    return ((scale * dh).astype(dh.dtype),
            (scale * dw).astype(head_like.dtype), None)


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def chunked_softmax_cross_entropy(h: jnp.ndarray, lm_head, labels: jnp.ndarray,
                                  chunk_size: int = 2048,
                                  ignore_index: int = -100,
                                  tied_embedding: bool = False) -> jnp.ndarray:
    """Mean token CE of `h @ lm_head` against `labels` without materializing
    the full (B, S, V) logits.

    h: (B, S, D); lm_head: (D, V) — or (V, D) with `tied_embedding=True`;
    labels: (B, S) int32, `ignore_index` masks tokens out.
    """
    s = h.shape[1]
    chunk = min(chunk_size, s)
    while s % chunk:
        chunk -= 1
    with jax.named_scope("chunked_ce"):
        return _chunked_ce(h, lm_head, labels, chunk, ignore_index,
                           tied_embedding)


def vocab_sequence_parallel_cross_entropy(h, lm_head, labels, chunk_size=2048,
                                          **kwargs) -> jnp.ndarray:
    """Reference-name alias (`sequence/cross_entropy.py`)."""
    return chunked_softmax_cross_entropy(h, lm_head, labels,
                                         chunk_size=chunk_size, **kwargs)
