"""The two latent-attention kernels of the dense form, timed ALONE on the
chip at the shapes of `openpangu-ultra-l5-ep16.generate-longctx-dense`, by
plan (PERF.md, PR 58, has what this read; the plans shipped are the
constants it was run to choose):

- `decode`: `ops/pallas/mla.mla_latent_decode` at 128 heads, 8 rows of
  25,600 slots of which 24,704 are live (the traffic file's mean context), 5
  layers a step, by the slots a block (`mla._WIDE_BLOCK_SLOTS`), each
  reading beside BOTH of its bounds (`openpangu_counts.latent_read_bytes`
  over the HBM bandwidth, `latent_attn_flops` over the bf16 peak); and at
  Ling's shape (32 heads, 128 rows of 2,048 slots, 1 layer), whose plan
  must read what it read;
- `prefill`: `ops/pallas/mla_sparse.mla_dense_prefill`, expansion and flash
  pass together, a chunk of 2,048 queries at the END of a row of 24,576 in
  25,600 slots (the costliest chunk) and one in the MIDDLE, by tile
  (`PREFILL_HEADS`, `PREFILL_QUERIES`, `PREFILL_BLOCK`), beside the biased
  form (`mla_sparse_prefill` under an all-zero-below-the-diagonal bias) at
  the shipped tiles.

    python tools/mla_dense_sweep.py                 # on the chip, ~3 min
    JAX_PLATFORMS=cpu python tools/mla_dense_sweep.py --rehearsal

Prints one JSON line a reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, reps):
    """Seconds a call by the device's own clock, by op: `reps` calls under
    the profiler, the first device's self times by op name over `reps`
    (a jitted function's PARAMETERS may be re-laid around a Mosaic call,
    which a serving program's carried cache is not: the host's clock over
    the call counts that copy, the kernel's own line does not)."""
    import shutil
    import tempfile
    import jax
    from perfbench import trace as tm
    jax.block_until_ready(fn(*args))            # compile
    logdir = tempfile.mkdtemp(prefix="mla_dense_sweep")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    trace = tm.read_xplane(tm.newest_xplane(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    if not trace["devices"]:                    # a CPU rehearsal: no device line
        return {}
    times = tm.self_times(tm.first_device(trace)["ops"])
    return {name: s / reps for name, s in times.items()}


def ms_of(times, pattern):
    import re
    return round(1e3 * sum(s for n, s in times.items()
                           if re.search(pattern, n)), 4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow")
    ap.add_argument("--phases", default="decode,prefill")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import mla
    from deepspeed_tpu.ops.pallas import mla_sparse as ms
    from perfbench.manifest import Manifest

    manifest = Manifest()
    sizes = manifest.config("openpangu-ultra-l5-ep16")
    counts = manifest.module("configs", sizes["counts"])
    small = args.rehearsal
    bf = jnp.bfloat16
    rank, rope, dn, dv = (32, 8, 16, 16) if small else (512, 64, 128, 128)
    reps = 2 if small else 10
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    device = jax.devices()[0].platform

    def say(**line):
        print(json.dumps({"device": device, **line}), flush=True)

    if "decode" in args.phases:
        shapes = {"h128": (5, 8, 128, 25600, 24704), "h32": (1, 128, 32, 2048,
                                                              1536)}
        if small:
            shapes = {"h128": (2, 2, 128, 512, 300), "h32": (1, 3, 32, 256,
                                                            100)}
        for name, (layers, b, h, m, live) in shapes.items():
            stack = jax.random.normal(key[0], (layers, b, 1, m, rank + rope),
                                      bf)
            q_lat = jax.random.normal(key[1], (b, h, rank), bf)
            q_rope = jax.random.normal(key[2], (b, h, rope), bf)
            new = jax.random.normal(key[3], (b, rank + rope), bf)
            lengths = jnp.full((b,), live, jnp.int32)
            least = {
                "bytes_ms": 1e3 * layers * b * live * 2 * (rank + rope)
                / 819e9,
                "flops_ms": 1e3 * layers * b * live * h * 2
                * (2 * rank + rope) / 197e12}
            blocks = (512,) if name == "h32" else (128, 256) if small \
                else (512, 1024, 1280, 2560, 5120)
            for blk in blocks:
                mla._WIDE_BLOCK_SLOTS = blk

                def step(q_lat, q_rope, stack, lengths, new):
                    out = 0.0
                    for layer in range(layers):
                        out = out + mla.mla_latent_decode(
                            q_lat, q_rope, stack, layer, lengths, 0.07,
                            new=new, slots=lengths - 1)
                    return out
                try:
                    times = timed(jax.jit(step), q_lat, q_rope, stack,
                                  lengths, new, reps=reps)
                    ms_ = ms_of(times, "^mla_latent_decode")
                    say(phase="decode", shape=name, block=mla.decode_block(
                        h, m), ms_a_step=ms_, all_ops_ms=ms_of(times, ""),
                        **{k: round(v, 4) for k, v in least.items()},
                        share=ms_ and round(100 * max(least.values()) / ms_,
                                            1))
                except Exception as e:      # a plan the compiler refuses
                    say(phase="decode", shape=name, block=blk,
                        error=str(e)[:300])
            del stack

    if "prefill" in args.phases:
        h, m, c = (4, 512, 128) if small else (128, 25600, 2048)
        prompt = 384 if small else 24576
        stack = jax.random.normal(key[4], (1, 1, 1, m, rank + rope), bf)
        q_nope = jax.random.normal(key[5], (c, h, dn), bf)
        q_rope = jax.random.normal(key[6], (c, h, rope), bf)
        w_kvb = (jax.random.normal(key[7], (rank, h, dn + dv)) * 0.05).astype(
            bf)
        # (8, 1024, 1280), (4, 2048, 1280) and (4, 1024, 2560) outgrow VMEM
        # (the compile for a described v5e, no chip)
        tiles = [(4, 1024, 1280), (8, 512, 1280), (2, 2048, 1280),
                 (2, 1024, 2560)]
        if small:
            tiles = [(2, 64, 128), (4, 128, 128)]
        pairs = h * 2 * (dn + rope + dv)
        for at in (prompt - c, prompt // 2):
            flops = pairs * (c * at + c * (c + 1) / 2.0)
            for tile in tiles:
                ms.PREFILL_HEADS, ms.PREFILL_QUERIES, ms.PREFILL_BLOCK = tile
                if small:
                    ms.EXPAND_HEADS, ms.EXPAND_BLOCK = 2, 128

                def dense(qn, qr, w, stack, start):
                    return ms.mla_dense_prefill(qn, qr, w, stack, 0, 0, start,
                                                0.07)

                def biased(qn, qr, w, stack, start):
                    return ms.mla_sparse_prefill(
                        qn, qr, w, ms.causal_bias(start, c, m), stack, 0, 0,
                        start, 0.07)
                forms = [("dense", dense)] + (
                    [("biased", biased)] if tile == tiles[0] else [])
                for form, fn in forms:
                    try:
                        times = timed(jax.jit(fn), q_nope, q_rope, w_kvb,
                                      stack, jnp.int32(at), reps=reps)
                        kernel = ms_of(times, "^mla_(dense|sparse)_prefill")
                        say(phase="prefill", form=form, start=at, tile=tile,
                            kernel_ms=kernel, all_ops_ms=ms_of(times, ""),
                            causal_tflops=kernel and round(
                                flops / kernel / 1e9, 1),
                            top=sorted(((n, round(1e3 * s_, 3)) for n, s_ in
                                        times.items()),
                                       key=lambda t: -t[1])[:5])
                    except Exception as e:
                        say(phase="prefill", form=form, start=at, tile=tile,
                            error=str(e)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main())
