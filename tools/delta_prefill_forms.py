"""The chunked delta rule's prefill kernel ALONE against its reference
(`ops/pallas/delta_rule.py`, `models/hybrid.delta_chunked`) at the two
cells' call shapes: Qwen3-Next's chunk (one row of 2,048 positions, 16 key
heads serving 32 value heads of 128, a decay a head, blocks of 64) and
Ling's group (8 rows of 1,024, 32 heads of 128, a decay a channel, blocks of
32). The heads a grid step and the solve's forms are swept one at a time
round the kernel's defaults. The kernel takes the keys' L2 norms and
the heads' RMS norm in on its way, so the reference is the plain form between
them, as `hybrid.delta_prefill` has it off the chip. Host clock over
`--calls` back-to-back calls of one jitted call, one fetch at the end;
`mxu_share` is the reference's count of the mathematics (15.8 MFLOP a (value
head, block of 64) in the head form: `k k^T`, `q k^T`, the Neumann
inverse's ten products, three products against the state, `solve @ rhs`, `p
@ u`) at six bf16 passes over the chip's bf16 peak, whatever the form
computes; `err` is the largest difference from the reference over the
reference's largest value, `o` and the state. Prints one JSON line a form.

    python tools/delta_prefill_forms.py                     # on the chip
    JAX_PLATFORMS=cpu python tools/delta_prefill_forms.py --rehearsal
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_BF16 = 197e12      # a v5e's, FLOP/s (Google Cloud, "TPU v5e")
PASSES = 6              # bf16 passes a float32 product at `highest`
NORM_EPS = 1e-6         # both families' `rms_norm_eps`


def delta_prefill_flops(heads: int, blocks: int, c: int, dk: int, dv: int,
                        channel: bool) -> int:
    """The reference's products a call, in FLOP before the passes: a (value
    head, block) has `k k^T` and `q k^T` (2 c^2 dk each), the Neumann
    inverse's 2 log2(c) - 2 products (2 c^3 each), `k_in S`, `q_in S` and the
    state's update (2 c dk dv each), `solve @ rhs` and `p @ u` (2 c^2 dv
    each). A decay a channel multiplies operands, not products: the same."""
    del channel
    neumann = 2 * max(0, (c - 1).bit_length() - 1)
    return heads * blocks * (4 * c * c * dk + neumann * 2 * c ** 3
                             + 6 * c * dk * dv + 4 * c * c * dv)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--forms", default="head,channel")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas import delta_rule as dr

    shapes = {"head": (1, 2048, 16, 32, 128, 64),
              "channel": (8, 1024, 32, 32, 128, 32)}
    sweeps = {"heads": (4, 16), "solve": (0, 16, 64)}
    if args.rehearsal:
        shapes = {"head": (1, 128, 2, 4, 128, 64),
                  "channel": (2, 64, 2, 2, 128, 32)}
        sweeps = {"heads": (2,), "solve": (0, 8)}
        args.calls = 1

    def operands(b, s, nk, nv, d, channel):
        keys = jax.random.split(jax.random.PRNGKey(65), 6)
        q, k = (jax.random.normal(key, (b, s, nk, d)) for key in keys[:2])
        v = jax.random.normal(keys[2], (b, s, nv, d))
        # the families' gates: -a softplus(.) a head, a bounded one a channel
        g = -4.0 * jax.nn.softplus(jax.random.normal(keys[3], (b, s, nv))) \
            if not channel else -5.0 * jax.nn.sigmoid(
                jax.random.normal(keys[3], (b, s, nv, d)))
        beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, nv)))
        s0 = 0.1 * jax.random.normal(keys[5], (b, nv, d, d))
        return q, k, v, g, beta, s0

    def timed(fn, ops):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*ops))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            last = fn(*ops)
        jax.block_until_ready(last)
        return 1e3 * (time.perf_counter() - t0) / args.calls, out

    for form in args.forms.split(","):
        b, s, nk, nv, d, c = shapes[form]
        ops = operands(b, s, nk, nv, d, form == "channel")
        flops = delta_prefill_flops(b * nv, -(-s // c), c, d, d,
                                    form == "channel")
        floor_ms = 1e3 * flops * PASSES / PEAK_BF16

        weight = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(66), (d,))

        ref_ms, ref = timed(lambda *a: hybrid.delta_prefill_reference(
            *a, c, weight, NORM_EPS), ops)
        out = {"shape": [b, s, nk, nv, d, c], "six_pass_floor_ms":
               round(floor_ms, 4), "reference_ms": round(ref_ms, 3),
               "kernel": {}}

        def kernel(**kw):
            ms, got = timed(lambda *a: dr.delta_rule_prefill(
                *a, c, weight, l2_eps=hybrid.L2_EPS, norm_eps=NORM_EPS, **kw),
                ops)
            err = max(float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
                      for x, y in zip(got, ref))
            return {"ms": round(ms, 3), "mxu_share": round(floor_ms / ms, 3),
                    "err": float(f"{err:.2e}")}

        out["kernel"]["default"] = dict(
            kernel(), heads=dr.HEADS, solve=dr.SOLVE)
        for name, values in sweeps.items():
            for value in values:
                if name != "solve" or c % max(value, 1) == 0:
                    out["kernel"][f"{name}={value}"] = kernel(**{name: value})
        print(json.dumps({"device": jax.devices()[0].platform,
                          "calls": args.calls, "form": form, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
