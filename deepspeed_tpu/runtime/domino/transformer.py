"""Domino — tensor-parallel compute/communication overlap (reference
`runtime/domino/transformer.py`: `DominoTransformerLayer`, async allreduce
handles `NoOper:55`, `_CopyToModelParallelRegionA:78`).

The reference splits each batch into two micro-chunks and hand-schedules
chunk-1 compute against chunk-0's TP allreduce on side streams. On TPU the
XLA latency-hiding scheduler already overlaps collectives with independent
compute — what Domino contributes is the *dependency break*: processing the
batch as two interleaved halves creates the independent work the scheduler
can overlap. This layer applies exactly that transform declaratively; the
async handle machinery has no analog because nothing blocks.

MEASURED (r5, an A/B script since deleted, llama tp=2 on the virtual CPU
mesh; real multi-chip TP is not available on the dev box): the transform
wins NOTHING under XLA — identical loss, 0.97x wall-clock (the concat
costs more than the break buys), and the optimized HLO carries the SAME
3 all-reduce ops with or without domino: XLA re-merges the per-chunk
collectives during fusion, so the hand dependency-break does not even
survive to the scheduler. `LlamaConfig(domino=True)` wires it for
parity/experimentation (exercised at tp2 in the driver dryrun); it is
intentionally OFF by default.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp


class DominoTransformerLayer:
    """Wrap (attn_fn, mlp_fn) into a two-chunk interleaved layer.

    attn_fn/mlp_fn: (B, S, D) -> (B, S, D) containing TP-sharded matmuls
    (their output allreduces are the collectives being overlapped).
    """

    def __init__(self, attn_fn: Callable, mlp_fn: Callable,
                 input_ln: Callable = None, post_ln: Callable = None):
        self.attn_fn = attn_fn
        self.mlp_fn = mlp_fn
        self.input_ln = input_ln or (lambda x: x)
        self.post_ln = post_ln or (lambda x: x)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b = x.shape[0]
        if b < 2:
            h = x + self.attn_fn(self.input_ln(x))
            return h + self.mlp_fn(self.post_ln(h))
        x0, x1 = x[: b // 2], x[b // 2:]
        # Interleave: attn(x1) is independent of attn(x0)'s TP allreduce, and
        # mlp(h0) is independent of attn(x1)'s — XLA overlaps the pairs.
        a0 = self.attn_fn(self.input_ln(x0))
        a1 = self.attn_fn(self.input_ln(x1))
        h0 = x0 + a0
        m0 = self.mlp_fn(self.post_ln(h0))
        h1 = x1 + a1
        m1 = self.mlp_fn(self.post_ln(h1))
        return jnp.concatenate([h0 + m0, h1 + m1], axis=0)
