"""LOGITS of the Trinity (afmoe) serving path against its float32 reference,
at the published widths and the cell's batch on the chip (the benchmark's
`correct` judges the first token only, which is the prefill): a prefill of
`--prompt` tokens and then `--steps` decode steps THROUGH THE CACHES (twelve
rings that the prefill has already wrapped four times over and every step
turns once more, four full-length rows; the step's token staged in each),
teacher-forced on seeded token ids, against the reference's blocked float32
pass over the same ids, at a few positions of the first `--rows` rows (the
program serves `--batch` rows; a row's result does not depend on its
neighbours).

    python tools/afmoe_decode_logits.py                     # on the chip
    JAX_PLATFORMS=cpu python tools/afmoe_decode_logits.py --rehearsal

Passes of the program, one of the reference; each pass also TIMES its decode
steps (host clock over the whole loop, one fetch at its end):

- `served`: the program as it is;
- `no_gate`: the sigmoid gate on the attention's output left out;
- `rotary_in_full`: the full layers' queries and keys rotated like the
  window layers' (they have NO positional embedding);
- `ring_one_short`: a ring read one live slot short (the count a reader makes
  of the cursors, `ops.attention.ring_live`, less one);
- `bf16_router`: the router's logits rounded to bf16 before the sigmoid and
  the choice, where float32 is stated.

Each fault is planted by replacing a function of the program from here; the
program has no such option. The tree is the cell's but for the routed experts,
which are put back to their seeded range (the cell damps them: `afmoe_adapter.
ROUTED_EXPERT_DAMP`). The number compared is, per row and position,
the RMS of (program - reference) over the vocabulary over the RMS of the
reference's centred logits. `served` must read under `--limit` at every
(row, position) whose routing the REFERENCE decides (a margin of
`MARGIN_SAFE` or more, as `correct` judges a first token: at a near-tie a
correct bf16 program takes the other expert and reads 0.1-0.2). A fault is
told apart PAIR BY PAIR against the served pass of the same run (the same
program but for the fault, the same ids): where its reading passes the
served one by more than `--limit`. `no_gate` and `rotary_in_full` must do so
at EVERY pair the reference decides (at a near-tie the served pass itself
reads 0.2, which a fault of 0.2 does not pass by the limit); `ring_one_short` and `bf16_router` at SOME decode pair: the
one slot a short ring drops holds one key of 2,048, which matters where the
softmax leans on it (and changes no prefill), and a rounded router moves a
token only where two experts stand within the rounding at the edge of its
choice. What holds each term to a tolerance is the CPU
test in float32 (`tests/unit/models/test_afmoe.py`). Prints one JSON line;
exit 1 if a judged reading is on the wrong side.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# WRITTEN BEFORE THE FIRST READING (PERF.md, PR 60): openPangu's served path
# read 0.009-0.014 at five layers; this walk is sixteen layers of bf16
# rounding, so `served` is expected at 0.01-0.03 and each fault at 0.1 or
# more; the limit lies between, nearer the served path.
LIMIT = 0.05
FAULTS = ("no_gate", "rotary_in_full", "ring_one_short", "bf16_router")
# where a fault must pass the served reading by the limit: at every judged
# (row, position) the reference decides, or at some decode one
SHOWS = {"no_gate": "all", "rotary_in_full": "all",
         "ring_one_short": "some_decode", "bf16_router": "some_decode"}


def rel_rms(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, positions): RMS over the vocabulary of the difference, over
    the RMS of the reference's logits about their mean."""
    centred = want - want.mean(-1, keepdims=True)
    return np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt(
        (centred ** 2).mean(-1))


def planted(fault):
    """(module, attribute, replacement given the real one) of a fault."""
    import jax
    from deepspeed_tpu.models import afmoe
    from deepspeed_tpu.moe import layer
    from deepspeed_tpu.ops import attention

    def no_gate(real):
        return lambda o, g, dtype: o.astype(dtype)

    def rotary_in_full(real):
        return lambda cfg, q, k, positions, sliding: real(cfg, q, k,
                                                          positions, True)

    def ring_one_short(real):
        def ring_live(index, m):
            count, slot = real(index, m)
            return count - 1, slot
        return ring_live

    def bf16_router(real):
        def route_topk(logits, *rest):
            # `reduce_precision`, not a cast there and back: XLA on the chip
            # drops a convert pair as excess precision it may keep (the
            # first chip run read this pass bit for bit the served one)
            return real(jax.lax.reduce_precision(logits, 8, 7), *rest)
        return route_topk
    return {"no_gate": (afmoe, "_gated", no_gate),
            "rotary_in_full": (afmoe, "_rotated", rotary_in_full),
            "ring_one_short": (attention, "ring_live", ring_one_short),
            "bf16_router": (layer, "route_topk", bf16_router)}[fault]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=60)
    ap.add_argument("--limit", type=float, default=LIMIT)
    ap.add_argument("--passes", default=",".join(("served",) + FAULTS))
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow; no verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from perfbench.manifest import Manifest
    from perfbench.runners_common import MARGIN_SAFE, tie_gap

    manifest = Manifest()
    sizes = manifest.config("trinity-mini-l16-ep8")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.batch, args.prompt, args.steps = 3, 40, 8
    adapter = manifest.module("configs", sizes["adapter"])
    reference = manifest.module("configs", sizes["reference"])
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    cfg = adapter.model_config(sizes, dtype=dtype)
    model, params = adapter.materialize(cfg, args.seed, dtype)
    # the routed experts AS SEEDED: the cell serves them at a quarter of their
    # output so that a router's near-tie cannot decide `correct`; here a flip
    # has to SHOW (`bf16_router` moves nothing else). A power of two: exact
    back = 1.0 / adapter.ROUTED_EXPERT_DAMP
    params = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(
        lambda path, leaf: (leaf * back).astype(leaf.dtype)
        if jax.tree_util.keystr(path[-2:]) in (
            "['experts']['up']", "['experts']['down']") else leaf, tree),
        donate_argnums=0)(params)

    batch, rows, prompt, steps = args.batch, args.rows, args.prompt, args.steps
    ids = np.random.default_rng([args.seed, 7]).integers(
        1, cfg.vocab_size, size=(batch, prompt + steps)).astype(np.int32)
    judged = sorted({prompt - 1, prompt, prompt + 1, prompt + steps // 2,
                     prompt + steps - 1})
    decode = [i for i, t in enumerate(judged) if t >= prompt]

    t0 = time.perf_counter()
    want, margin = (np.asarray(t) for t in jax.jit(
        lambda p, i: reference.logits_and_margin_at(p, i, judged, sizes))(
            params, ids[:rows]))
    seconds = {"reference": round(time.perf_counter() - t0, 1)}

    max_len = -(-(prompt + steps) // 128) * 128
    step_ms = {}

    def served(name):
        """(rows, judged, vocab) logits of the cache path, teacher-forced;
        its programs are traced anew (a pass may have replaced a function
        of the program), on a model object of its own."""
        mod = type(model)(cfg)
        prefill = jax.jit(lambda p, i: mod.apply(
            {"params": p}, i,
            cache=mod.make_cache(batch, max_len, dtype=dtype)))
        step = jax.jit(lambda p, tok, cache: mod.apply(
            {"params": p}, tok, cache=cache), donate_argnums=2)
        t0 = time.perf_counter()
        logits, cache = prefill(params, jnp.asarray(ids[:, :prompt]))
        got = {prompt - 1: np.asarray(logits[:rows, 0], np.float32)}
        seconds[name + "_prefill_with_compile"] = round(
            time.perf_counter() - t0, 1)
        if name == "served":            # the same program again, compiled
            del cache
            t0 = time.perf_counter()
            logits, cache = prefill(params, jnp.asarray(ids[:, :prompt]))
            jax.block_until_ready(cache)
            seconds["served_prefill"] = round(time.perf_counter() - t0, 2)
        kept = {}
        for t in range(prompt, prompt + steps):
            if t == prompt + 1:         # the first step compiled
                jax.block_until_ready(cache)
                t0 = time.perf_counter()
            logits, cache = step(params, jnp.asarray(ids[:, t:t + 1]), cache)
            if t in judged:
                kept[t] = logits[:rows, 0]
        jax.block_until_ready(cache)
        step_ms[name] = round(1e3 * (time.perf_counter() - t0)
                              / max(steps - 1, 1), 3)
        del cache
        got.update({t: np.asarray(v, np.float32) for t, v in kept.items()})
        return np.stack([got[t] for t in judged], axis=1)

    readings, last_served = {}, None
    for name in args.passes.split(","):
        if name == "served":
            last_served = served(name)
            readings[name] = rel_rms(last_served, want)
            continue
        module, attr, replace = planted(name)
        real = getattr(module, attr)
        setattr(module, attr, replace(real))
        try:
            readings[name] = rel_rms(served(name), want)
        finally:
            setattr(module, attr, real)
    line = {"device": jax.devices()[0].platform, "batch": batch, "rows": rows,
            "prompt": prompt, "steps": steps, "positions": judged,
            "limit": args.limit, "step_ms": step_ms, "seconds": seconds,
            **{name: {"min": float(r.min()), "max": float(r.max()),
                      "decode_min": float(r[:, decode].min()),
                      "by_position": [round(float(x), 5) for x in r.max(0)]}
               for name, r in readings.items()}}
    safe = margin >= MARGIN_SAFE
    line["margins"] = [[round(float(x), 4) for x in row] for row in margin]
    if last_served is not None:
        # each judged position as `correct` would judge a first token: how
        # far below the reference's argmax the program's own lies
        line["token_gaps"] = [[round(tie_gap(w, int(np.argmax(g))), 4)
                               for w, g in zip(w_row, g_row)]
                              for w_row, g_row in zip(want, last_served)]
        line["served_safe"] = {
            "pairs": int(safe.sum()), "of": int(safe.size),
            "max": float(readings["served"][safe].max()) if safe.any()
            else None}
    faults = [n for n in readings if n != "served"]
    # the served path is judged where the reference's routing is decided
    served_max = float(readings["served"][safe].max()) \
        if "served" in readings and safe.any() else 0.0
    base = readings.get("served", 0.0)

    def shows(name):
        over = readings[name] - base        # pair by pair, the same ids
        return {"all": over[safe].min() if safe.any() else over.min(),
                "some_decode": over[:, decode].max()}[SHOWS[name]] > args.limit
    line["told_apart"] = sorted(n for n in faults if shows(n))
    line["ok"] = bool(served_max < args.limit
                      and len(line["told_apart"]) == len(faults)) \
        or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
