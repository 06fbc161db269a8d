"""Domino A/B (VERDICT r4 weak #5): is the two-chunk batch interleave
(reference `runtime/domino/transformer.py`, blog claim 1.2-1.3x) worth
anything under XLA, which already runs a latency-hiding scheduler?

Method (one process; real multi-chip TP is unavailable on this box, so
the evidence is (a) wall-clock on the virtual-CPU TP mesh and (b) the
collective STRUCTURE of the compiled programs):

  1. llama train step at tp=2 (dp fills the rest), domino off vs on —
     chained steps, best-of-3.
  2. optimized-HLO accounting of both programs: all-reduce count and how
     many are ASYNC pairs (`all-reduce-start`/`-done`) with compute
     scheduled between — XLA's own overlap, no hand scheduling.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python benchmarks/domino_ab.py [tpu]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    if "tpu" not in sys.argv[1:]:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=8"
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        llama_config, llama_loss_fn, materialize_params)
    from deepspeed_tpu.utils import groups

    out = {}
    for domino in (False, True):
        groups.reset_topology()
        cfg = llama_config("llama-tiny", dtype=jnp.float32, domino=domino,
                           hidden_size=256, intermediate_size=512,
                           num_hidden_layers=4, num_attention_heads=8,
                           num_key_value_heads=8)
        model, params = materialize_params(cfg)
        topo = groups.MeshTopology(tp=2)
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            loss_fn=llama_loss_fn(model), topology=topo,
            config={"train_micro_batch_size_per_gpu": 4,
                    "gradient_accumulation_steps": 1, "steps_per_print": 0,
                    "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 0}})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, cfg.vocab_size,
            (4 * topo.dense_dp_size, 64)).astype(np.int32)}
        losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(8):
                engine.train_batch(batch=batch)
            jax.block_until_ready(engine.state)
            best = min(best, (time.perf_counter() - t0) / 8)
        key = "domino" if domino else "plain"
        out[key] = {"step_ms": round(1e3 * best, 2),
                    "loss": round(losses[-1], 4)}

        # collective structure of the compiled fwd+bwd under the same
        # mesh/shardings (counts per ONE micro step)
        loss_fn = llama_loss_fn(model)
        rng_key = jax.random.PRNGKey(0)
        micro = {"input_ids": batch["input_ids"][:4]}

        def fwd_bwd(p, b, r):
            return jax.grad(lambda p: loss_fn(p, b, r)[0]
                            if isinstance(loss_fn(p, b, r), tuple)
                            else loss_fn(p, b, r))(p)
        with engine.mesh:
            txt = jax.jit(fwd_bwd).lower(
                engine.state.params, micro, rng_key).compile().as_text()
        out[key]["all_reduce_ops"] = txt.count(" all-reduce(")
        out[key]["async_all_reduce_starts"] = txt.count("all-reduce-start")
    if "plain" in out and "domino" in out:
        out["domino_speedup"] = round(
            out["plain"]["step_ms"] / out["domino"]["step_ms"], 3)
    print(json.dumps(out))


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
