"""LOGITS of the Keye-sparse serving path against its float32 reference, at
the published widths and the cell's batch on the chip (the benchmark's
`correct` judges the first token only): a prefill of `--prompt` tokens and
then `--steps` decode steps THROUGH THE CACHES (every step scores the row's
index keys, chooses 2,048 slots and attends them, its own token staged),
teacher-forced on seeded token ids, against the reference's blocked float32
pass over the same ids, at a few positions of the first `--rows` rows (the
program serves `--batch` rows; a row's result does not depend on its
neighbours).

    python tools/keye_decode_logits.py                      # on the chip
    JAX_PLATFORMS=cpu python tools/keye_decode_logits.py --rehearsal

Passes of the program, one of the reference:

- `served`: the program as it is;
- `dense`: the same with `index_topk` at the cache's length: every cached
  position is kept, attention is dense (a program that skipped the
  selection "because the result stays inside the tolerance");
- `half`: `index_topk` halved;
- `no_relu`: the index scores without their `relu` (the script replaces
  `ops/pallas/sparse_select._relu`, the one place kernels and plain forms
  take it from; the program has no such option);
- `no_index_weights`: the heads' weights `w` zeroed in the PROGRAM's tree:
  every index score is 0, they all tie, and the lowest 2,048 positions are
  kept whatever the query.

The number compared is, per row and position, the RMS of (program -
reference) over the vocabulary over the RMS of the reference's centred
logits. `served` must read under `--limit` and EACH of the other four over
it: the limit then tells the served path from one that skipped the
selection, halved it, or dropped a term of the index scores. On the chip it
does (PERF.md, PR 51: served 0.010-0.034; `half` 0.081-0.134, `no_relu`
0.102-0.140, `dense` 0.187-0.289, `no_index_weights` 0.741-0.783), seeded
weights and all: a query's 2,048 kept keys carry softmax weights of spread
1 (q and k are normalised a head), and which keys are kept moves the mixed
value by more than bf16 rounding of the served path does. What it CANNOT
tell is a boundary swap (one of 2,048 kept keys of near-equal index score
for another: under the served reading by construction), nor a fault that
only shows past 128 steps. `served` is also given over the (row, position)
pairs at which the reference's own routing is decided (`served_safe`).
What holds the choice to `jax.lax.top_k`'s set, and each term to a
tolerance, is the CPU test in float32 (`tests/unit/models/
test_keye_sparse.py`). Prints one JSON line; exit 1 if a judged reading is
on the wrong side.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Read on the chip (PERF.md, PR 51; 8 rows served, 2 judged, 32,768 + 128
# positions): `served` 0.010-0.034 at every position judged; the least of the
# four passes without a term, `half`, 0.081. The limit is twice the one's
# worst, and under the other's least.
LIMIT = 0.067


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
    ap.add_argument("--prompt", type=int, default=32768)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--limit", type=float, default=LIMIT)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow; no verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import sparse_select
    from perfbench.manifest import Manifest
    from perfbench.runners_common import MARGIN_SAFE

    manifest = Manifest()
    sizes = manifest.config("keye-vl2-30b-l12-ep8")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.batch, args.prompt, args.steps = 3, 40, 8
    adapter = manifest.module("configs", sizes["adapter"])
    reference = manifest.module("configs", sizes["reference"])
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    cfg = adapter.model_config(sizes, dtype=dtype)
    model, params = adapter.materialize(cfg, args.seed, dtype)

    batch, rows, prompt, steps = args.batch, args.rows, args.prompt, args.steps
    ids = np.random.default_rng([args.seed, 7]).integers(
        1, cfg.vocab_size, size=(batch, prompt + steps)).astype(np.int32)
    judged = sorted({prompt - 1, prompt, prompt + 1, prompt + steps // 2,
                     prompt + steps - 1})

    want, margin = (np.asarray(t) for t in jax.jit(
        lambda p, i: reference.logits_and_margin_at(p, i, judged, sizes))(
            params, ids[:rows]))

    max_len = -(-(prompt + steps) // 128) * 128

    def served(p, model=model):
        """(rows, judged, vocab) logits of the cache path, teacher-forced;
        its programs are traced anew (a pass may have replaced `_relu`)."""
        prefill = jax.jit(lambda p, i: model.apply(
            {"params": p}, i,
            cache=model.make_cache(batch, max_len, dtype=dtype)))
        step = jax.jit(lambda p, tok, cache: model.apply(
            {"params": p}, tok, cache=cache), donate_argnums=2)
        logits, cache = prefill(p, jnp.asarray(ids[:, :prompt]))
        got = {prompt - 1: np.asarray(logits[:rows, 0], np.float32)}
        for t in range(prompt, prompt + steps):
            logits, cache = step(p, jnp.asarray(ids[:, t:t + 1]), cache)
            if t in judged:
                got[t] = np.asarray(logits[:rows, 0], np.float32)
        del cache
        return np.stack([got[t] for t in judged], axis=1)

    def with_topk(k):
        return type(model)(dataclasses.replace(cfg, index_topk=k))

    readings = {"served": rel_rms(served(params), want),
                "dense": rel_rms(served(params, with_topk(max_len)), want),
                "half": rel_rms(served(params, with_topk(cfg.index_topk // 2)),
                                want)}
    relu, sparse_select._relu = sparse_select._relu, lambda s: s
    try:
        readings["no_relu"] = rel_rms(served(params), want)
    finally:
        sparse_select._relu = relu
    readings["no_index_weights"] = rel_rms(served(
        jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
                path).endswith("['index_w_proj']['kernel']") else x, params)),
        want)
    line = {"device": jax.devices()[0].platform, "batch": batch, "rows": rows,
            "prompt": prompt, "steps": steps, "positions": judged,
            "limit": args.limit,
            **{name: {"min": float(r.min()), "max": float(r.max()),
                      "by_position": [round(float(x), 5) for x in r.max(0)]}
               for name, r in readings.items()}}
    safe = margin >= MARGIN_SAFE
    line["served_safe"] = {
        "pairs": int(safe.sum()), "of": int(safe.size),
        "max": float(readings["served"][safe].max()) if safe.any() else None}
    line["told_apart"] = sorted(name for name, r in readings.items()
                                if name != "served" and r.min() > args.limit)
    line["ok"] = bool(readings["served"].max() < args.limit
                      and len(line["told_apart"]) == len(readings) - 1) \
        or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
