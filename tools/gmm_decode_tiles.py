#!/usr/bin/env python3
"""A decode step's grouped GEMMs by their K and N tiles: does an expert whose
rows straddle two 16-row tiles have its weights read twice, and what does the
mask of a K remainder tile cost? The grouped FFN of a held expert layer (two
or three `gmm` calls and the activation between them) inside a jitted
function whose weights are program ARGUMENTS, twenty calls back to back,
device ms a call of the `gmm` ops by the trace.

Shapes: the decode calls of four cells. Group sizes: as the cell's router
draws them over a seeded input (`routed`), and three rows an expert
(`threes`). Forms:

  a         today's tiles, (16, min(K, 1024), min(N, 1024))
  b<tn>     the whole contraction in one K tile: (16, K, min(N, tn)) at
            tn = 1024, 512, 384, 256; no second fetch for a straddling expert
            (the block index does not change), no remainder to mask
  c         today's N tile over EQUAL K tiles with no remainder where K has
            one and divides so (2,688 = 3 x 896, 2,560 = 2 x 1,280): the
            mask alone; a projection whose K does not keeps today's tiles
  d         today's tiles over groups PADDED to whole row tiles (sizes
            rounded up to 16, the call sized for them): the re-read alone
  rule      the tiles `grouped_gemm.held_tiling` gives the call
  buffer    no grouped GEMM: the buffer path's batched products over a
            (held, tokens, hidden) buffer, every held expert's weights read
            (`moe/layer.py` keeps the grouped GEMM at decode for the second
            layout the compiler holds beside a prefill's, not for this time)

    JAX_PLATFORMS=cpu python tools/gmm_decode_tiles.py --rehearsal
    chiprun --timeout 1200 -- python tools/gmm_decode_tiles.py

One JSON line a reading: the `gmm` ops' ms a call (and by projection), the
device's busy ms a call and its three longest ops that are not `gmm`,
the experts touched, the second visits today's grid makes (`revisits`), and
the touched experts' bytes over the `gmm` time as a share of the chip's
bandwidth.
Prints no time off the chip.
"""

import argparse
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TM = 16
# name: tokens, top k, hidden, expert width, held, scored, score, selection
# bias, groups, groups kept, the router's logits' spread, activation: a
# decode step's expert call in the cell named
SHAPES = {
    "nemotron": (64, 6, 2688, 1856, 64, 128, "sigmoid", True, 1, 1, 1.0,
                 "relu2"),
    "ling": (128, 8, 2560, 768, 128, 512, "sigmoid", True, 8, 4, 1.0, "silu"),
    "trinity": (32, 8, 2048, 1024, 16, 128, "sigmoid", True, 1, 1, 4.0,
                "silu"),
    "deepseek": (8, 8, 7168, 2048, 16, 256, "sigmoid", True, 8, 4, 2.0,
                 "silu"),
}
TOY = {"toy": (16, 4, 336, 232, 8, 16, "sigmoid", True, 1, 1, 1.0, "relu2"),
       "toy_gated": (8, 4, 256, 128, 4, 16, "softmax", False, 1, 1, 1.0,
                     "silu")}
HBM_GBPS = 819.0        # one v5e chip (perfbench/peaks.json)


def equal_k_tile(k: int, tk: int) -> int:
    """The largest K tile of whole lane rows that divides `k` into as many
    tiles as `tk` does or one fewer, `tk` itself where none does or `k` has
    no remainder."""
    if k % tk == 0:
        return tk
    tiles = -(-k // tk)
    for n in (tiles, tiles - 1):
        if n > 0 and k % n == 0 and (k // n) % 128 == 0:
            return k // n
    return tk


def tiles_of(form: str, k: int, n: int, cap: int):
    """(tm, tk, tn) of `form` for one projection; `cap`: today's tile cap
    (1,024; the rehearsal's is smaller so that its toy K has a remainder)."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import held_tiling
    if form == "rule":
        return held_tiling(TM, k, n)
    if form.startswith("b"):
        return (TM, k, min(n, int(form[1:])))
    tk = min(k, cap)
    return (TM, equal_k_tile(k, tk) if form == "c" else tk, min(n, cap))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--forms", default="a,b1024,b512,b384,b256,c,d,rule,buffer")
    ap.add_argument("--sizes", default="routed,threes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.moe.layer import _activate
    from deepspeed_tpu.ops.pallas.grouped_gemm import (grouped_gemm,
                                                       weight_tile_revisits)
    from deepspeed_tpu.telemetry.program_map import read_device_events

    F32, BF = jnp.float32, jnp.bfloat16
    shapes = TOY if args.rehearsal else SHAPES
    cap = 128 if args.rehearsal else 1024
    names = [n for n in args.shapes.split(",") if n] or list(shapes)
    reps = 2 if args.rehearsal else 20
    device = jax.devices()[0].platform

    def say(**line):
        print(json.dumps({"device": device, **line}), flush=True)

    def operands(shape, key):
        t, k, d, f, count, scored = shape[:6]
        ks = jax.random.split(key, 6)

        def normal(key, dims, scale=1.0):
            return (jax.random.normal(key, dims, F32) * scale).astype(BF)
        x = normal(ks[0], (t, d))
        logits = jnp.dot(x, normal(ks[1], (d, scored), shape[10] * d ** -0.5),
                         preferred_element_type=F32)
        bias = jax.random.normal(ks[2], (scored,), F32) * 0.01
        _, idx = sm.route_topk(logits, k, shape[6], bias if shape[7] else None,
                               n_group=shape[8], topk_group=shape[9])
        routed = sm.held_group_sizes(sm.held_assignments(idx, 0, count)[1],
                                     count)
        gated = shape[11] == "silu"
        return (routed, normal(ks[0], (t * k, d)),
                normal(ks[3], (count, d, f), 0.02),
                normal(ks[4], (count, d, f), 0.02) if gated else None,
                normal(ks[5], (count, f, d), 0.02))

    def ffn(shape, form):
        d, f, activation = shape[2], shape[3], shape[11]
        up_t, down_t = ((), ()) if form == "buffer" else (
            tiles_of(form, d, f, cap), tiles_of(form, f, d, cap))

        def buffered(rows, sizes, w_up, w_gate, w_down):
            buf = jnp.broadcast_to(rows[:shape[0]], (shape[4], shape[0], d))
            gate = None if w_gate is None else jnp.einsum(
                "ecd,edf->ecf", buf, w_gate)
            h = _activate(jnp.einsum("ecd,edf->ecf", buf, w_up), gate,
                          activation)
            return jnp.einsum("ecf,efd->ecd", h, w_down)
        if form == "buffer":
            return buffered, ()

        def fn(rows, sizes, w_up, w_gate, w_down):
            gate = None if w_gate is None else grouped_gemm(
                rows, w_gate, sizes, tiling=up_t)
            h = _activate(grouped_gemm(rows, w_up, sizes, tiling=up_t), gate,
                          activation)
            return grouped_gemm(h, w_down, sizes, tiling=down_t)
        fn.__name__ = f"gmm_tiles_{form}"
        return fn, (up_t, down_t)

    def gmm_ms(jitted, inputs):
        """ms a call of the `gmm` ops, summed and by instruction name."""
        jax.block_until_ready(jitted(*inputs))
        with tempfile.TemporaryDirectory(prefix="gmm_decode_tiles_") as logdir:
            with telemetry.trace_capture(logdir):
                for _ in range(reps):
                    out = jitted(*inputs)
                jax.block_until_ready(out)
            ops, modules = read_device_events(logdir)
        busy = round(sum(dur for _, _, dur in modules) / 1e6 / reps, 4)
        by_name, others = {}, {}
        for name, _, dur in ops:
            into = by_name if re.match(r"^gmm", name) else others
            into[name] = into.get(name, 0.0) + dur / 1e6 / reps
        others = sorted(others.items(), key=lambda kv: -kv[1])[:3]
        return (round(sum(by_name.values()), 4) if by_name else None, busy,
                {n: round(v, 4) for n, v in sorted(by_name.items())},
                {n: round(v, 4) for n, v in others})

    for name in names:
        shape = shapes[name]
        t, k, d, f, count = shape[:5]
        routed, rows, w_up, w_gate, w_down = jax.jit(
            lambda key: operands(shape, key))(jax.random.PRNGKey(66))
        routed = np.asarray(routed)
        threes = np.full((count,), 3, np.int32)
        assert threes.sum() <= t * k, (name, "all threes outgrow the call")
        weights = (1 if w_gate is None else 2, 1)   # up-like, down GEMMs
        for kind in args.sizes.split(","):
            sizes = {"routed": routed, "threes": threes}[kind]
            touched = int((sizes > 0).sum())
            revisits = int(weight_tile_revisits(jnp.asarray(sizes), TM))
            touched_bytes = touched * d * f * 2 * sum(weights)
            want = None
            for form in args.forms.split(","):
                fn, tiles = ffn(shape, "a" if form == "d" else form)
                call_sizes, call_rows = sizes, rows
                if form == "d":
                    call_sizes = -(-sizes // TM) * TM
                    call_rows = jnp.zeros(
                        (-(-(t * k + (TM - 1) * count) // TM) * TM, d),
                        BF).at[:t * k].set(rows)
                inputs = (call_rows, jnp.asarray(call_sizes, jnp.int32),
                          w_up, w_gate, w_down)
                line = dict(shape=name, sizes=kind, form=form,
                            tiles=[list(x) for x in tiles],
                            held_rows=int(sizes.sum()), touched=touched,
                            revisits=revisits)
                jitted = jax.jit(fn)
                try:
                    got = jitted(*inputs)
                    if form not in ("d", "buffer"):  # rows lie elsewhere
                        got = got[:int(sizes.sum())].astype(F32)
                        if want is None:
                            want = got
                        line["rel_err_to_first"] = float(
                            jnp.max(jnp.abs(got - want))
                            / jnp.maximum(jnp.max(jnp.abs(want)), 1e-9))
                    ms, busy, by_name, others = gmm_ms(jitted, inputs)
                except Exception as e:      # tiles the compiler refuses
                    say(**line, error=str(e)[-300:])
                    continue
                if not args.rehearsal:
                    line.update(busy_ms=busy, others=others)
                if ms is not None and not args.rehearsal:
                    line.update(
                        ms=ms, by_name=by_name,
                        touched_gb=round(touched_bytes / 1e9, 4),
                        touched_bw_share=round(
                            100 * touched_bytes / (ms * 1e-3)
                            / (HBM_GBPS * 1e9), 2))
                say(**line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
