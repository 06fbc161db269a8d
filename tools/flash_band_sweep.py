"""The banded flash forward ALONE by block (`ops/pallas/flash_attention.py`,
`BAND_BLOCK`): Trinity-Mini's window layers as its prefill walks them, two
rows of 8,192 positions, 32 query heads on 4 KV heads of 128 under a window
of 2,048, against the plain causal kernel on the same arrays. A block of
`b` covers a query block's band with `window / b + 1` key blocks: `(1 + b /
window)` times the columns the mathematics asks for, and smaller blocks pay
more grid steps. Host clock over `--calls` back-to-back calls of one jitted
call whose arguments are q, k and v themselves (no cache stack to re-lay),
one fetch at the end. Prints one JSON line.

    python tools/flash_band_sweep.py                        # on the chip
    JAX_PLATFORMS=cpu python tools/flash_band_sweep.py --rehearsal
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rows, s, h, hkv, d, window = 2, 8192, 32, 4, 128, 2048
    blocks = [(256, 256), (512, 512), (512, 1024), (1024, 512), (1024, 1024)]
    if args.rehearsal:
        rows, s, h, hkv, d, window, args.calls = 1, 64, 4, 2, 16, 24, 2
        blocks = [(8, 8), (16, 16), (16, 32)]
    keys = jax.random.split(jax.random.PRNGKey(60), 3)
    q, k, v = (jax.random.normal(key, (rows, s, n, d), jnp.float32).astype(
        jnp.bfloat16) for key, n in zip(keys, (h, hkv, hkv)))
    pairs = s * (s + 1) // 2 - (s - window) * (s - window + 1) // 2
    flops = rows * h * 4 * d * pairs

    def timed(**kw):
        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))
        jax.block_until_ready(fn(q, k, v))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.calls

    line = {"device": jax.devices()[0].platform, "shape": [rows, s, h, hkv, d],
            "window": window, "calls": args.calls, "band_ms": {},
            "band_tflops": {}, "causal_ms": round(timed(), 3)}
    for bq, bk in blocks:
        ms = timed(window=window, block_q=bq, block_k=bk)
        line["band_ms"][f"{bq}x{bk}"] = round(ms, 3)
        line["band_tflops"][f"{bq}x{bk}"] = round(flops / ms / 1e9, 1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
