"""LOGITS of the DeepSeek-sparse serving path against its float32 reference,
at the published widths and the cell's batch on the chip (the benchmark's
`correct` judges the first token only, which is the prefill): a prefill of
`--prompt` tokens and then `--steps` decode steps THROUGH THE CACHES (every
step scores the row's index keys, chooses 2,048 slots and attends their
latent rows in the absorbed form, its own token staged), teacher-forced on
seeded token ids, against the reference's blocked float32 pass over the same
ids, at a few positions of the first `--rows` rows (the program serves
`--batch` rows; a row's result does not depend on its neighbours).

    python tools/deepseek_decode_logits.py                      # on the chip
    JAX_PLATFORMS=cpu python tools/deepseek_decode_logits.py --rehearsal

Passes of the program, one of the reference; each pass also TIMES its decode
steps (host clock over the whole loop, one fetch at its end):

- `served`: the program as it is (the decode read takes the chosen rows
  GATHERED, `ops/pallas/mla_sparse.DECODE_GATHERS`);
- `slab`: the same with the other form of the decode read, the row's whole
  live slab under the choice's bias: the same mathematics, so it must read
  as `served` does, and its step time beside `served`'s is the
  gather-against-dense-read reading of PERF.md (PR 54);
- `dense`: `index_topk` at the cache's length: every cached position is
  kept (a program that skipped the selection);
- `half`: `index_topk` halved;
- `no_mscale`: YaRN's temperature dropped from the softmax scale (the
  script replaces `ops.attention.yarn_mscale`; the program has no such
  option).

The number compared is, per row and position, the RMS of (program -
reference) over the vocabulary over the RMS of the reference's centred
logits. `served` and `slab` must read under `--limit` and EACH of the other
three over it. What holds the choice to `jax.lax.top_k`'s set, and each term
to a tolerance, is the CPU test in float32
(`tests/unit/models/test_deepseek_sparse.py`). Prints one JSON line; exit 1
if a judged reading is on the wrong side.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Read on the chip (PERF.md, PR 54; 8 rows served, 2 judged, 24,576 + 16
# positions, the embedding's rows as the adapter serves them): `served`
# 0.012-0.018 at every position judged; the least of the three passes without a
# term, `no_mscale`, 0.081. The limit lies between, nearer the served path.
LIMIT = 0.06


def rel_rms(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, positions): RMS over the vocabulary of the difference, over
    the RMS of the reference's logits about their mean."""
    centred = want - want.mean(-1, keepdims=True)
    return np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt(
        (centred ** 2).mean(-1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=24576)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=54)
    ap.add_argument("--limit", type=float, default=LIMIT)
    ap.add_argument("--passes", default="served,slab,dense,half,no_mscale")
    ap.add_argument("--both", default="", choices=("", "no_routed", "dense"),
                    help="a diagnosis, applied to program AND reference: "
                    "`no_routed` zeroes the routed experts' down projections "
                    "(no router's flip moves anything), `dense` keeps every "
                    "position (no choice's flip does)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow; no verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import mla_sparse
    from perfbench.manifest import Manifest
    from perfbench.runners_common import MARGIN_SAFE

    manifest = Manifest()
    sizes = manifest.config("deepseek-v3.2-l5-ep16")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.batch, args.prompt, args.steps = 3, 40, 8
    adapter = manifest.module("configs", sizes["adapter"])
    reference = manifest.module("configs", sizes["reference"])
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    cfg = adapter.model_config(sizes, dtype=dtype)
    model, params = adapter.materialize(cfg, args.seed, dtype)
    if args.both == "no_routed":
        params = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
                path[-2:]) == "['experts']['down']" else x, tree),
            donate_argnums=0)(params)
    if args.both == "dense":
        sizes = {**sizes, "index_topk": 1 << 20}
        cfg = dataclasses.replace(cfg, index_topk=1 << 20)
        model = type(model)(cfg)

    batch, rows, prompt, steps = args.batch, args.rows, args.prompt, args.steps
    ids = np.random.default_rng([args.seed, 7]).integers(
        1, cfg.vocab_size, size=(batch, prompt + steps)).astype(np.int32)
    judged = sorted({prompt - 1, prompt, prompt + 1, prompt + steps // 2,
                     prompt + steps - 1})

    t0 = time.perf_counter()
    want, margin = (np.asarray(t) for t in jax.jit(
        lambda p, i: reference.logits_and_margin_at(p, i, judged, sizes))(
            params, ids[:rows]))
    seconds = {"reference": round(time.perf_counter() - t0, 1)}

    max_len = -(-(prompt + steps) // 128) * 128
    step_ms = {}

    def served(name, model=model):
        """(rows, judged, vocab) logits of the cache path, teacher-forced;
        its programs are traced anew (a pass may have replaced a
        constant)."""
        prefill = jax.jit(lambda p, i: model.apply(
            {"params": p}, i,
            cache=model.make_cache(batch, max_len, dtype=dtype)))
        step = jax.jit(lambda p, tok, cache: model.apply(
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

    def with_topk(k):
        return type(model)(dataclasses.replace(cfg, index_topk=k))

    passes = args.passes.split(",")
    readings = {}
    last_served = None
    if "served" in passes:
        last_served = served("served")
        readings["served"] = rel_rms(last_served, want)
    if "slab" in passes:
        mla_sparse.DECODE_GATHERS = False
        try:
            readings["slab"] = rel_rms(served("slab"), want)
        finally:
            mla_sparse.DECODE_GATHERS = True
    if "dense" in passes:
        readings["dense"] = rel_rms(served("dense", with_topk(max_len)), want)
    if "half" in passes:
        readings["half"] = rel_rms(
            served("half", with_topk(cfg.index_topk // 2)), want)
    if "no_mscale" in passes:
        mscale, attention.yarn_mscale = attention.yarn_mscale, \
            lambda factor, mscale=1.0: 1.0
        try:
            readings["no_mscale"] = rel_rms(served("no_mscale"), want)
        finally:
            attention.yarn_mscale = mscale
    same = ("served", "slab")
    line = {"device": jax.devices()[0].platform, "batch": batch, "rows": rows,
            "prompt": prompt, "steps": steps, "positions": judged,
            "limit": args.limit, "step_ms": step_ms, "seconds": seconds,
            **{name: {"min": float(r.min()), "max": float(r.max()),
                      "by_position": [round(float(x), 5) for x in r.max(0)]}
               for name, r in readings.items()}}
    safe = margin >= MARGIN_SAFE
    line["margins"] = [[round(float(x), 4) for x in row] for row in margin]
    if "served" in readings:
        # each judged position as `correct` would judge a first token: how
        # far below the reference's argmax the program's own lies
        from perfbench.runners_common import tie_gap
        line["token_gaps"] = [[round(tie_gap(w, int(np.argmax(g))), 4)
                               for w, g in zip(w_row, g_row)]
                              for w_row, g_row in zip(want, last_served)]
    if "served" in readings:
        line["served_safe"] = {
            "pairs": int(safe.sum()), "of": int(safe.size),
            "max": float(readings["served"][safe].max()) if safe.any()
            else None}
    line["told_apart"] = sorted(name for name, r in readings.items()
                                if name not in same and r.min() > args.limit)
    line["ok"] = bool(
        all(readings[n].max() < args.limit for n in same if n in readings)
        and len(line["told_apart"]) == len(
            [n for n in readings if n not in same])) or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
