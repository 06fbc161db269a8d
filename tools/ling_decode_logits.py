"""LOGITS of the Ling-linear serving path against its float32 reference, at
the published widths and the cell's batch on the chip (the benchmark's
`correct` judges the first token only): a prefill of `--prompt` tokens and
then `--steps` decode steps THROUGH THE CACHES (the matrix states have run
hundreds of delta-rule updates in the kernel, the latent slab has grown a
row a step), teacher-forced on seeded token ids, against the reference's
full pass over the same ids, at a few positions of the first `--rows` rows
(the program serves `--batch` rows; a row's result does not depend on its
neighbours).

    python tools/ling_decode_logits.py                      # on the chip
    JAX_PLATFORMS=cpu python tools/ling_decode_logits.py --rehearsal

Passes of the program, one of the reference:

- `served`: the program as it is;
- `bf16_state`: the same with the KDA state rounded to bfloat16 between
  steps (the script rounds the cache it carries; the program has no such
  option);
- `no_dt_bias`: the same with the KDA decay gate's bias zeroed in the
  PROGRAM's weights: a dropped term of the five recurrent layers (the
  seeded decays go from about 1 to e^-2.5 a step);
- `no_bias`: the routers' selection bias zeroed: a dropped term of the choice;
- `no_head_gate`: the MLA layers' head-wise gate projection zeroed (every
  head times a half): a dropped term of one layer in six.

The number compared is, per row and position, the RMS of (program - reference)
over the vocabulary over the RMS of the reference's centred logits. `served`
must read under `--limit` and `no_dt_bias` over it: the limit then tells the
served path from one with a missing term of the new mechanism. The other
three are REPORTED and not judged (PERF.md, PR 47, has the readings): a
CORRECT bf16 program takes another expert than the float32 reference at a
near-tie of a router, which moves a row's logits (with the routed experts
as seeded, by as much as a dropped selection bias did: 0.05-0.15), so
`served` is also given over the (row, position) pairs at which the
reference's own routing is decided (`served_safe`, margin `MARGIN_SAFE` or
more; earlier tokens' flips still reach them through the state). What holds
the state's precision, the bias and the gate to a tolerance is the CPU test
in float32 (`tests/unit/models/test_ling_linear.py`). Prints
one JSON line; exit 1 if either judged reading is on the wrong side.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Read on the chip (PERF.md, PR 47; 128 rows served, 4 judged, 1,024 + 640
# positions, the served weights): `served` 0.026-0.036 at every position
# judged, `no_dt_bias` 1.34-1.43; the limit is twice the one's worst. (Of the
# passes not judged: `no_bias` 0.038-0.055 and `no_head_gate` 0.034-0.044
# stand over `served` at every position and under the limit; `bf16_state`
# grows with the steps, 0.029 at the first to 0.082 at the 640th.)
LIMIT = 0.07


def rel_rms(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, positions): RMS over the vocabulary of the difference, over
    the RMS of the reference's logits about their mean."""
    centred = want - want.mean(-1, keepdims=True)
    return np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt(
        (centred ** 2).mean(-1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=640)
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--limit", type=float, default=LIMIT)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow; no verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from perfbench.manifest import Manifest
    from perfbench.runners_common import MARGIN_SAFE

    manifest = Manifest()
    sizes = manifest.config("ling3-flash-l6-ep4")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.batch, args.prompt, args.steps = 4, 40, 14
    adapter = manifest.module("configs", sizes["adapter"])
    reference = manifest.module("configs", sizes["reference"])
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    cfg = adapter.model_config(sizes, dtype=dtype)
    model, params = adapter.materialize(cfg, args.seed, dtype)

    batch, rows, prompt, steps = args.batch, args.rows, args.prompt, args.steps
    ids = np.random.default_rng([args.seed, 7]).integers(
        1, cfg.vocab_size, size=(batch, prompt + steps)).astype(np.int32)
    # positions judged: the prefill's, the first steps, the middle, the last
    judged = sorted({prompt - 1, prompt, prompt + 1, prompt + steps // 4,
                     prompt + steps // 2, prompt + steps - 1})

    want, margin = (np.asarray(t) for t in jax.jit(
        lambda p, i: reference.logits_and_margin_at(p, i, judged, sizes))(
            params, ids[:rows]))

    max_len = -(-(prompt + steps) // 128) * 128
    prefill = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, cache=model.make_cache(batch, max_len, dtype=dtype)))
    step = jax.jit(lambda p, tok, cache: model.apply({"params": p}, tok,
                                                     cache=cache),
                   donate_argnums=2)
    # `reduce_precision`: XLA may drop a float32 -> bfloat16 -> float32 pair
    to_bf16 = jax.jit(lambda cache: cache.replace(state=cache.state.replace(
        ssm=jax.lax.reduce_precision(cache.state.ssm, 8, 7))),
        donate_argnums=0)

    def served(p, round_state=False):
        """(rows, judged, vocab) logits of the cache path, teacher-forced."""
        logits, cache = prefill(p, jnp.asarray(ids[:, :prompt]))
        got = {prompt - 1: np.asarray(logits[:rows, 0], np.float32)}
        for t in range(prompt, prompt + steps):
            if round_state:
                cache = to_bf16(cache)
            logits, cache = step(p, jnp.asarray(ids[:, t:t + 1]), cache)
            if t in judged:
                got[t] = np.asarray(logits[:rows, 0], np.float32)
        del cache
        return np.stack([got[t] for t in judged], axis=1)

    def zeroed(p, which):
        """The tree with the leaves whose path ends in `which` zeroed."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
                path).endswith(which) else x, p)

    readings = {
        "served": rel_rms(served(params), want),
        "bf16_state": rel_rms(served(params, round_state=True), want),
        "no_dt_bias": rel_rms(served(zeroed(params, "['dt_bias']")), want),
        "no_bias": rel_rms(served(zeroed(params, "['gate']['bias']")), want),
        "no_head_gate": rel_rms(
            served(zeroed(params, "['g_proj']['kernel']")), want)}
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
    ok = readings["served"].max() < args.limit < readings["no_dt_bias"].min()
    line["ok"] = bool(ok) or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
