"""LOGITS of Phi-4-mini-flash's serving path against its float32 reference,
at the published widths on the chip (the benchmark's `correct` judges the
first token only): a prefill of `--prompt` tokens and then `--steps` decode
steps THROUGH THE CACHES (so a ring has wrapped and the state has run
hundreds of updates), teacher-forced on seeded token ids, against the
reference's full pass over the same ids, at a few positions of a few rows.

    python tools/phi4flash_decode_logits.py                 # on the chip
    JAX_PLATFORMS=cpu python tools/phi4flash_decode_logits.py --rehearsal

Three passes of the program, one of the reference:

- `served`: the program as it is;
- `bf16_state`: the same with the Mamba state rounded to bfloat16 between
  steps (the script rounds the cache it carries; the program has no such
  option);
- `no_lambda`: the same with the four learned lambda vectors zeroed in the
  PROGRAM's weights, so `lam = lambda_init` alone: a dropped term.

The number compared is, per row and position, the RMS of (program - reference)
over the vocabulary over the RMS of the reference's centred logits. `served`
must read under `--limit` and `no_lambda` over it: the limit then tells the
served path from one with a missing term. `bf16_state` is REPORTED and not
judged: on the family's seeded weights the state is a tenth of a Mamba layer's
output beside the `D` skip, its bf16 rounding a few thousandths of that, and
the logits' own bf16 noise hides it (PERF.md, PR 45, has the readings); what
holds the state's precision is the CPU test in float32
(`tests/unit/models/test_phi4flash.py`). Prints one JSON line; exit 1 if
either judged reading is on the wrong side.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Read on the chip (PERF.md, PR 45; 2 rows, 2,048 + 640 positions, two
# runs): `served` 0.046-0.058 at every position judged, the prefill's among
# them (bf16 weights and activations through 32 layers against float32: the
# caches add nothing), `no_lambda` 0.43-0.51. The limit is about their
# geometric mean: 2.6 times of room above the one, 2.9 below the other.
LIMIT = 0.15


def rel_rms(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, positions): RMS over the vocabulary of the difference, over
    the RMS of the reference's logits about their mean."""
    centred = want - want.mean(-1, keepdims=True)
    return np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt(
        (centred ** 2).mean(-1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=640)
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--limit", type=float, default=LIMIT)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow; no verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from perfbench.manifest import Manifest

    manifest = Manifest()
    sizes = manifest.config("phi4-mini-flash")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.prompt, args.steps = 12, 14
    adapter = manifest.module("configs", sizes["adapter"])
    reference = manifest.module("configs", sizes["reference"])
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    cfg = adapter.model_config(sizes, dtype=dtype)
    model, params = adapter.materialize(cfg, args.seed, dtype)

    rows, prompt, steps = args.rows, args.prompt, args.steps
    window = cfg.sliding_window
    ids = np.random.default_rng([args.seed, 7]).integers(
        1, cfg.vocab_size, size=(rows, prompt + steps)).astype(np.int32)
    # positions judged: the prefill's, the first steps, either side of the
    # step at which a ring's first slot is overwritten, and the last
    wrap = prompt + (-prompt) % window if prompt >= window else window
    judged = sorted({prompt - 1, prompt, prompt + 1, wrap - 1, wrap, wrap + 1,
                     prompt + steps // 2, prompt + steps - 1}
                    & set(range(prompt - 1, prompt + steps)))

    want = np.asarray(jax.jit(
        lambda p, i: reference.logits_at(p, i, judged, sizes))(params, ids))

    max_len = -(-(prompt + steps) // 128) * 128
    prefill = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, cache=model.make_cache(rows, max_len, dtype=dtype)))
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
        got = {prompt - 1: np.asarray(logits[:, 0], np.float32)}
        for t in range(prompt, prompt + steps):
            if round_state:
                cache = to_bf16(cache)
            logits, cache = step(p, jnp.asarray(ids[:, t:t + 1]), cache)
            if t in judged:
                got[t] = np.asarray(logits[:, 0], np.float32)
        return np.stack([got[t] for t in judged], axis=1)

    def without_lambda(p):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "lambda_" in
            jax.tree_util.keystr(path[-1:]) else x, p)

    readings = {
        "served": rel_rms(served(params), want),
        "bf16_state": rel_rms(served(params, round_state=True), want),
        "no_lambda": rel_rms(served(without_lambda(params)), want)}
    line = {"device": jax.devices()[0].platform, "rows": rows,
            "prompt": prompt, "steps": steps, "positions": judged,
            "limit": args.limit,
            **{name: {"min": float(r.min()), "max": float(r.max()),
                      "by_position": [round(float(x), 5) for x in r.max(0)]}
               for name, r in readings.items()}}
    ok = readings["served"].max() < args.limit < readings["no_lambda"].min()
    line["ok"] = bool(ok) or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
