"""LOGITS of the openPangu serving path against its float32 reference, at
the published widths and the cell's batch on the chip (the benchmark's
`correct` judges the first token only, which is the prefill): a prefill of
`--prompt` tokens and then `--steps` decode steps THROUGH THE LATENT CACHE
(every step reads the row's whole live slab in the absorbed form at 128
heads, its own token staged), teacher-forced on seeded token ids, against
the reference's blocked float32 pass over the same ids, at a few positions
of the first `--rows` rows (the program serves `--batch` rows; a row's
result does not depend on its neighbours).

    python tools/openpangu_decode_logits.py                     # on the chip
    JAX_PLATFORMS=cpu python tools/openpangu_decode_logits.py --rehearsal

Passes of the program, one of the reference; each pass also TIMES its decode
steps (host clock over the whole loop, one fetch at its end):

- `served`: the program as it is;
- `bf16_angles`: the rotary's angles (position x frequency) made in bf16
  where float32 is stated: at position 24,576 bf16 holds multiples of 128;
- `no_post_norm`: the two POST norms of every layer left out (the sub-layer's
  output joins the stream as it is);
- `no_rope_key`: the `q_rope . k_r` term left out of every score;
- `half_cache`: a decode step reads the first HALF of its row's live slots.

Each fault is planted by replacing a function of the program from here; the
program has no such option. The number compared is, per row and position,
the RMS of (program - reference) over the vocabulary over the RMS of the
reference's centred logits. `served` must read under `--limit` at every
position and EACH fault over it at every DECODE position (`half_cache`
changes no prefill). What holds each term to a tolerance is the CPU test in
float32 (`tests/unit/models/test_openpangu.py`). Prints one JSON line; exit
1 if a judged reading is on the wrong side.
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

# WRITTEN BEFORE THE FIRST READING (PERF.md, PR 58): DeepSeek's served path
# read 0.012-0.018 with every position kept in program and reference alike,
# which is this model's mathematics, so `served` is expected at 0.01-0.02
# and each fault at 0.1 or more; the limit lies between, nearer the served
# path.
LIMIT = 0.05
FAULTS = ("bf16_angles", "no_post_norm", "no_rope_key", "half_cache")


def rel_rms(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, positions): RMS over the vocabulary of the difference, over
    the RMS of the reference's logits about their mean."""
    centred = want - want.mean(-1, keepdims=True)
    return np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt(
        (centred ** 2).mean(-1))


def planted(fault):
    """(module, attribute, replacement given the real one) of a fault."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import latent, openpangu
    from deepspeed_tpu.ops import attention

    def bf16_angles(real):
        def rope_cos_sin(positions, head_dim, theta=10000.0,
                         dtype=jnp.float32, scaling=None):
            inv_freq = 1.0 / (theta ** (jnp.arange(
                0, head_dim, 2, dtype=jnp.float32) / head_dim))
            angles = (positions[..., None].astype(jnp.bfloat16)
                      * inv_freq.astype(jnp.bfloat16)).astype(jnp.float32)
            return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)
        return rope_cos_sin

    def no_post_norm(real):
        class Nothing:
            def __call__(self, x):
                return x
        return lambda eps, dtype, name=None: Nothing() if re.fullmatch(
            r"layer_\d+_post_(attn|mlp)_norm", name or "") else real(
                eps, dtype, name=name)

    def no_rope_key(real):
        def project(mod, x, start, rope_scaling=None):
            p = real(mod, x, start, rope_scaling)
            return p._replace(q_rope=jnp.zeros_like(p.q_rope))
        return project

    def half_cache(real):
        def latent_decode(q_lat, q_rope, lat, lengths, scale, new=None,
                          slots=None):
            return real(q_lat, q_rope, lat, lengths // 2, scale, new=new,
                        slots=slots)
        return latent_decode
    return {"bf16_angles": (attention, "rope_cos_sin", bf16_angles),
            "no_post_norm": (openpangu, "RMSNorm", no_post_norm),
            "no_rope_key": (latent, "project", no_rope_key),
            "half_cache": (attention, "latent_decode", half_cache)}[fault]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=24576)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=58)
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
    sizes = manifest.config("openpangu-ultra-l5-ep16")
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
    line["told_apart"] = sorted(
        n for n in faults if readings[n][:, decode].min() > max(
            args.limit, readings["served"].max() if "served" in readings
            else 0.0))
    line["ok"] = bool(
        ("served" not in readings or readings["served"].max() < args.limit)
        and len(line["told_apart"]) == len(faults)) or args.rehearsal
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
