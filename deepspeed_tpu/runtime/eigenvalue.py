"""Block Hessian eigenvalue estimation (reference `runtime/eigenvalue.py`,
`compute_eigenvalue`) — power iteration on Hessian-vector products. The
torch version needs retain_graph double-backward; JAX's `jax.jvp` over
`jax.grad` gives exact HVPs in one jitted program.

Used by MoQ (`runtime/quantize.py`) to schedule per-layer quantization
periods by curvature.

`loss_fn` is differentiated in forward mode over REVERSE mode: `jax.jvp`
sees the forward and backward rules of any `jax.custom_vjp` inside it, not the
function itself, so a model built with the chunked loss of
`sequence/cross_entropy.py` gives the same eigenvalue as the unchunked
`causal_lm_loss` (tests/unit/sequence/test_sequence.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


class Eigenvalue:
    def __init__(self, verbose: bool = False, max_iter: int = 100,
                 tol: float = 1e-2, stability: float = 1e-6,
                 gas_boundary_resolution: int = 1, layer_name: str = "",
                 layer_num: int = 0):
        self.max_iter = max_iter
        self.tol = tol
        self.stability = stability
        self.verbose = verbose

    def compute_eigenvalue(self, loss_fn: Callable, params: Any, rng=None
                           ) -> float:
        """Dominant |eigenvalue| of the Hessian of loss_fn at params."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        grad_fn = jax.grad(loss_fn)

        def hvp(v):
            return jax.jvp(grad_fn, (params,), (v,))[1]

        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(rng, len(leaves))
        v = jax.tree_util.tree_unflatten(
            treedef, [jax.random.normal(k, l.shape, jnp.float32)
                      for k, l in zip(keys, leaves)])

        def norm(t):
            return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree_util.tree_leaves(t)))

        def normalize(t):
            n = norm(t) + self.stability
            return jax.tree_util.tree_map(lambda x: x / n, t)

        v = normalize(v)
        eig = jnp.zeros(())

        @jax.jit
        def power_iter(v, _eig):
            hv = hvp(v)
            new_eig = sum(jnp.sum(a * b) for a, b in zip(
                jax.tree_util.tree_leaves(v), jax.tree_util.tree_leaves(hv)))
            return normalize(hv), new_eig

        prev = 0.0
        for _ in range(self.max_iter):
            v, eig = power_iter(v, eig)
            e = float(eig)
            if abs(e - prev) / (abs(e) + self.stability) < self.tol:
                break
            prev = e
        return abs(float(eig))
