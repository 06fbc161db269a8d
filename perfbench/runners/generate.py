"""Offline generation cells: v1 `init_inference(...).generate`, batches back
to back, every prompt of a batch the same length, a fixed number of new
tokens each. Bypasses the v2 scheduler and the paged kernels."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from perfbench import traffic as tg
from perfbench.runners_common import TIE_TOL, tie_gap


def run(ctx, devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    tf = ctx.traffic
    rows, new = tf["batch"], tf["new_tokens"]
    cfg = ctx.adapter.model_config(ctx.sizes, remat=False, dtype=jnp.bfloat16)
    groups.reset_topology()
    model, params = ctx.adapter.materialize(cfg, ctx.seed, jnp.bfloat16)
    rng = np.random.default_rng([ctx.seed, 11])
    lengths = sorted(set(int(n) for n in tf["prompt"]["values"]))

    def prompts(n):
        return rng.integers(1, cfg.vocab_size, size=(rows, n)).astype(np.int32)

    # the reference's logits for a few rows of the first warm-up batch, from
    # the RAW tree, before the engine places or re-lays it
    warm_ids = {n: prompts(n) for n in lengths}
    k = tf["check_rows"]
    sample = warm_ids[lengths[0]][:k]
    ref = jax.jit(lambda p, i, l: ctx.reference.last_logits(p, i, l, ctx.sizes))
    anchor = np.asarray(ref(params, sample,
                            np.full((k,), sample.shape[1] - 1, np.int32)))

    engine = deepspeed_tpu.init_inference(model, params=params, dtype="bf16",
                                          tensor_parallel={"tp_size": 1})
    del params

    def generate(ids):
        """(sequences that came back whole, the output): whole means the
        right shape and every token in the vocabulary."""
        with ctx.annotate("generate"):
            out = np.asarray(engine.generate(ids, max_new_tokens=new))
        ok = out.shape == (ids.shape[0], ids.shape[1] + new) and \
            out.min() >= 0 and out.max() < cfg.vocab_size
        return (ids.shape[0] if ok else 0), out

    gaps = []
    for n in lengths:                    # compiles one program per length
        _, out = generate(warm_ids[n])
        if n == lengths[0]:
            gaps = [tie_gap(anchor[i], int(out[i, n])) for i in range(k)]

    order = tg.stratified(tf["prompt"], 10_000, rng)   # a cycle: its values in turn
    t0 = ctx.clock()
    ctx.counters["setup_s"] = t0 - ctx.t_start
    done = attempted = 0
    times, t_last, i = [], 0.0, 0
    with ctx.counting_compiles():
        before = ctx.compiles
        while ctx.clock() - t0 < ctx.seconds:
            ids = prompts(int(order[i]))
            i += 1
            t = ctx.clock()
            attempted += rows
            done += generate(ids)[0]
            t_last = ctx.clock() - t0
            times.append(ctx.clock() - t)
        compiles = ctx.compiles - before
    ctx.samples["batch_ms"] = [t * 1e3 for t in times]
    ctx.samples["ms_per_new_token"] = [t * 1e3 / new for t in times]
    ctx.counters.update(batches=len(times), out_tok_s=done * new / t_last,
                        compiles_in_window=compiles)

    if ctx.traced:
        n = int(tf["trace_batches"])
        with ctx.profile():
            with ctx.annotate("traced"):
                for j in range(n):
                    generate(prompts(int(order[i + j])))
        ctx.counters["traced_decode_steps"] = n * new
        ctx.counters["traced_batches"] = n

    return {"correct": all(g <= TIE_TOL for g in gaps) and done == attempted,
            "attempted": attempted, "failed": attempted - done,
            "notes": {"first_token_gaps": [round(g, 5) for g in gaps],
                      "tie_tolerance": TIE_TOL, "batches": len(times),
                      "compiles_in_window": compiles,
                      "serve_mode": getattr(engine, "serve_mode", None)}}
