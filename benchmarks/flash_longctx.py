"""Flash-attention long-context micro-bench (VERDICT r3 weak #2).

Measures the Pallas flash kernel's fwd and fwd+bwd throughput at long
sequence lengths (attention is ~87% of step FLOPs at 128k on the 470m
flagship, so kernel efficiency ~= long-ctx MFU), and sweeps block sizes.

Chained fori_loop timing (CLAUDE.md method): chaining N calls inside one
jit (output feeds input) and timing the whole program, ended by a fetch,
resolves per-call cost without N dispatches in the number.

Usage: python benchmarks/flash_longctx.py [S ...] (default 32768 65536)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    seqs = [int(a) for a in sys.argv[1:] if a.isdigit()] or [32768, 65536]
    blocks = [(512, 512), (1024, 1024), (1024, 512), (512, 1024)]
    h, d = 8, 128
    peak = 197e12
    key = jax.random.PRNGKey(0)

    for s in seqs:
        n_iter = max(2, min(16, (32768 * 4) // s))
        q = jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
        k = jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
        v = jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
        fwd_flops = 4 * s * s / 2 * h * d  # causal

        for bq, bk in blocks:
            row = {"seq": s, "block": f"{bq}x{bk}", "iters": n_iter}
            try:
                @jax.jit
                def fwd_chain(q0):
                    def body(i, qc):
                        o = flash_attention(qc, k, v, causal=True,
                                            block_q=bq, block_k=bk)
                        return (o * 1e-3).astype(qc.dtype)
                    return jax.lax.fori_loop(0, n_iter, body, q0)

                float(fwd_chain(q).astype(jnp.float32).sum())  # compile+sync
                t0 = time.perf_counter()
                float(fwd_chain(q).astype(jnp.float32).sum())
                dt = (time.perf_counter() - t0) / n_iter
                row["fwd_ms"] = round(1e3 * dt, 1)
                row["fwd_mfu"] = round(fwd_flops / dt / peak, 3)

                @jax.jit
                def bwd_chain(q0):
                    def body(i, qc):
                        def loss(qq):
                            return flash_attention(
                                qq, k, v, causal=True, block_q=bq,
                                block_k=bk).astype(jnp.float32).sum()
                        g = jax.grad(loss)(qc)
                        return (g * 1e-3).astype(qc.dtype)
                    return jax.lax.fori_loop(0, n_iter, body, q0)

                float(bwd_chain(q).astype(jnp.float32).sum())
                t0 = time.perf_counter()
                float(bwd_chain(q).astype(jnp.float32).sum())
                dt = (time.perf_counter() - t0) / n_iter
                row["fwdbwd_ms"] = round(1e3 * dt, 1)
                # fwd recompute inside grad: fwd + dq + dkv = 3.5x fwd volume
                row["fwdbwd_mfu"] = round(3.5 * fwd_flops / dt / peak, 3)
            except Exception as e:  # OOM etc.
                row["error"] = str(e)[:120]
            print(json.dumps(row))


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
