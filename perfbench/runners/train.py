"""Training cells: `deepspeed_tpu.initialize` + `train_batch`, a fresh seeded
batch every step, each step ending in the fetched loss.

The recipe is the traffic file's (`seq`, `sequences_per_step`, `micro_batch`,
`mesh`, `zero_stage`, `remat_policy`, `loss_chunk`): the one `chip_smoke.py`
proved on the chip (ZeRO-3 plan, bf16, FusedAdam, flash attention,
`checkpoint_dots` remat, chunked cross-entropy). `mesh` states `dp` and `tp`
and may state `ep` (expert parallel, a division of the data-parallel ranks).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

from perfbench.flops import train_flops_per_token

# The engine's first-step loss (bf16 forward, mean over the whole batch)
# against the float32 reference on `check_rows` of that batch's rows. At
# seeded initialisation every row's loss is ln(vocab) plus a small term, and
# rows of 2047 random tokens differ from each other by ~0.1%; bf16 rounding
# through the stack adds about as much. 0.5% is several times both, and far
# inside what a wrong loss scale, label shift or lost layer norm would move.
LOSS_TOL = 5e-3
MESH_AXES = ("dp", "tp", "ep")     # of `MeshTopology`'s, those a cell may state


def mesh_of(traffic: Dict[str, Any], chips: int) -> Dict[str, int]:
    """The traffic file's `mesh` with every axis written out (1 where it
    states none). An axis this runner does not build, or a product that
    is not the cell's chips, ends the run."""
    stated = traffic["mesh"]
    unknown = sorted(set(stated) - set(MESH_AXES))
    if unknown:
        raise SystemExit(f"perfbench: mesh axis {unknown[0]!r} is none of "
                         f"{', '.join(MESH_AXES)}")
    mesh = {**dict.fromkeys(MESH_AXES, 1),
            **{a: int(n) for a, n in stated.items()}}
    if math.prod(mesh.values()) != chips:
        raise SystemExit("perfbench: mesh " + " x ".join(
            f"{a}{mesh[a]}" for a in MESH_AXES) + f" on {chips} device(s)")
    return mesh


def accumulation_steps(traffic: Dict[str, Any], mesh: Dict[str, int]) -> int:
    """Micro-batches a step accumulates on each data-like rank, of which
    there are dp x ep (`MeshTopology.dense_dp_size`)."""
    return traffic["sequences_per_step"] // (
        traffic["micro_batch"] * mesh["dp"] * mesh["ep"])


def batch_for(rng: np.random.Generator, vocab: int, rows: int, seq: int
              ) -> Dict[str, np.ndarray]:
    return {"input_ids": rng.integers(0, vocab, size=(rows, seq)
                                      ).astype(np.int32)}


def run(ctx, devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.groups import MeshTopology

    tf = ctx.traffic
    seq, rows, mbs = tf["seq"], tf["sequences_per_step"], tf["micro_batch"]
    mesh = mesh_of(tf, len(devices))
    dp, tp, ep = mesh["dp"], mesh["tp"], mesh["ep"]
    cfg = ctx.adapter.model_config(
        ctx.sizes, remat=True, remat_policy=tf["remat_policy"],
        loss_chunk_size=tf["loss_chunk"], dtype=jnp.bfloat16)
    groups.reset_topology()
    topology = MeshTopology(dp=dp, ep=ep, tp=tp, devices=list(devices))
    # bf16 from the start: the engine casts to bf16 before it builds its fp32
    # master anyway, and an fp32 tree would only crowd the chip
    model, params = ctx.adapter.materialize(cfg, ctx.seed, jnp.bfloat16)

    rng = np.random.default_rng([ctx.seed, 7])
    first = batch_for(rng, cfg.vocab_size, rows, seq)
    ref = jax.jit(lambda p, ids: ctx.reference.mean_loss(p, ids, ctx.sizes))
    want = float(ref(params, first["input_ids"][:tf["check_rows"]]))

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, topology=topology,
        config={"train_micro_batch_size_per_gpu": mbs,
                "gradient_accumulation_steps": accumulation_steps(tf, mesh),
                "steps_per_print": 0,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 2e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": tf["zero_stage"]},
                "tensor_parallel": {"tp_size": tp}},
        loss_fn=ctx.adapter.loss_fn(model),
        base_param_specs=ctx.adapter.partition_specs(cfg))
    del params   # the engine's state is the only copy from here on

    def step(batch) -> float:
        with ctx.annotate("step"):
            return float(engine.train_batch(batch=batch))

    got = step(first)                                   # compiles
    losses = [got, step(batch_for(rng, cfg.vocab_size, rows, seq))]
    rel = abs(got - want) / abs(want)

    t0 = ctx.clock()
    ctx.counters["setup_s"] = t0 - ctx.t_start
    times, t_last = [], 0.0
    with ctx.counting_compiles():
        before = ctx.compiles
        while ctx.clock() - t0 < ctx.seconds:
            batch = batch_for(rng, cfg.vocab_size, rows, seq)
            t = ctx.clock()
            losses.append(step(batch))
            t_last = ctx.clock() - t0
            times.append(ctx.clock() - t)
        compiles = ctx.compiles - before
    steps = len(times)
    ctx.samples["step_ms"] = [t * 1e3 for t in times]
    ctx.counters.update(
        steps=steps, train_tok_s=steps * rows * seq / t_last,
        flops_per_token=train_flops_per_token(ctx.sizes, seq,
                                              manifest=ctx.manifest),
        compiles_in_window=compiles)

    if ctx.traced:
        n = int(tf["trace_steps"])
        with ctx.profile():
            with ctx.annotate("traced"):
                for _ in range(n):
                    losses.append(step(batch_for(rng, cfg.vocab_size, rows,
                                                 seq)))
        ctx.counters["traced_steps"] = n

    bad = sum(1 for l in losses[2:2 + steps] if not math.isfinite(l))
    finite = all(math.isfinite(l) for l in losses)
    return {"correct": finite and rel <= LOSS_TOL, "attempted": steps,
            "failed": bad,
            "notes": {"first_loss": got, "reference_loss": want,
                      "loss_rel_diff": rel, "loss_tolerance": LOSS_TOL,
                      "check_rows": tf["check_rows"], "last_loss": losses[-1],
                      "steps": steps, "compiles_in_window": compiles,
                      "params": int(engine.total_params)}}
