"""A training cell's step compiled for a DESCRIBED TPU v5e 2x2 (no chip, no
chip time), and the order the compiler gave a loop body's instructions.

    JAX_PLATFORMS=cpu python tools/described_step.py \
        --workload qwen2.5-3b-l20.train-zero3-x4 --out /root/scratch/x4.hlo.txt
    JAX_PLATFORMS=cpu python tools/described_step.py --schedule \
        /root/scratch/x4.hlo.txt --loop bwd

The first form builds the cell's engine on the described devices with shapes
in place of arrays (`perfbench`'s own configuration, traffic and adapter),
compiles `train:train_batch` and writes the optimised, SCHEDULED module's
text (about 15 s at Qwen2.5-3B, 20 layers); `--root` compiles another
checkout (`git archive <commit>`), for `tools/scope_proof.stripped` to
compare. The second prints, for the layer loop's forward or backward body,
every product, kernel, collective and asynchronous start / done in program
order with the compiler's own cycle estimate summed up to it: where an
exchange starts, what lies under it, which done has nothing before it.
What the chip then reads differs (the estimates run about 1.6 x the
measured products, and waits show only on the chip): the ORDER is what this
is for. PR 57 found its three backward ties with it at no chip time.
Never a measurement, never reported as one.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ASYNC = ("collective-permute-start", "collective-permute-done", "all-reduce",
         "all-gather", "all-to-all", "all-gather-start", "all-gather-done",
         "all-reduce-start", "all-reduce-done", "opt-barrier")


def compile_step(root: str, workload: str, out: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    # code that asks which backend it runs on takes its chip branch here
    import deepspeed_tpu.ops.attention as attention_ops
    import deepspeed_tpu.ops.pallas as pallas_ops
    from deepspeed_tpu.accelerator import tpu_accelerator
    for module in (tpu_accelerator, pallas_ops, attention_ops):
        module.on_tpu = lambda: True
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import TrainState
    from deepspeed_tpu.runtime.precision import cast_tree
    from deepspeed_tpu.utils import groups
    from perfbench.manifest import Manifest
    from perfbench.runners import train

    manifest = Manifest(None)
    cell = manifest.workload(workload)
    sizes, tf = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    adapter = manifest.module("configs", sizes["adapter"])
    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)[:int(cell["chips"])]
    mesh = train.mesh_of(tf, len(devices))
    cfg = adapter.model_config(
        sizes, remat=True, remat_policy=tf["remat_policy"],
        loss_chunk_size=tf["loss_chunk"], dtype=jnp.bfloat16)
    groups.reset_topology()
    topology = groups.MeshTopology(dp=mesh["dp"], ep=mesh["ep"], tp=mesh["tp"],
                                   devices=devices)
    from deepspeed_tpu.models import qwen2
    model, specs = qwen2.init_params_and_specs(cfg)
    shapes = jax.eval_shape(lambda: qwen2.materialize_params(
        cfg, param_dtype=jnp.bfloat16)[1])
    gas = train.accumulation_steps(tf, mesh)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=None, topology=topology,
        config={"train_micro_batch_size_per_gpu": tf["micro_batch"],
                "gradient_accumulation_steps": gas, "steps_per_print": 0,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 2e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": tf["zero_stage"]},
                "tensor_parallel": {"tp_size": mesh["tp"]}},
        loss_fn=adapter.loss_fn(model), base_param_specs=specs)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, engine.model_dtype), shapes)
    shardings = engine.build_shardings(params, specs)

    def rest(params):   # `initialize_state`'s, over shapes
        master = cast_tree(params, jnp.float32)
        return TrainState(
            jnp.zeros([], jnp.int32), params, master, engine.opt.init(master),
            None if engine._elide_grad_acc else jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
            engine.loss_scaler.init_state())

    def placed(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    state = placed(jax.eval_shape(rest, params), shardings)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (gas, tf["sequences_per_step"] // gas, tf["seq"]), jnp.int32)}
    batch = placed(batch, engine._batch_shardings(batch, extra_leading=True))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(engine.mesh, P()))
    with engine.mesh:
        compiled = engine._get_jit("train_batch").trace(
            state, batch, rng).lower().compile()
    with open(out, "w") as f:
        f.write(compiled.as_text())
    print(out, compiled.memory_analysis())


def schedule(path: str, loop: str, floor_cycles: int) -> None:
    text = open(path).read()
    bodies = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                      text)
    name = lambda c: (re.match(r"(?:ENTRY )?%([\w.\-]+) ", c) or [None, ""])[1]
    by_name = {name(c): c for c in bodies}
    backward = "transpose(jvp(LlamaForCausalLM))/while/body"
    forward = "micro/jvp(LlamaForCausalLM)/while/body"

    def score(c):
        if loop == "bwd":
            return c.count(backward)
        return c.count(forward) - 10 * c.count(backward) - 10 * c.count(
            "chunked_ce")

    body = max((c for c in bodies if "collective-permute-start" in c),
               key=score)
    print("# computation", name(body))
    cycles = 0
    for line in body.split("\n")[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not m:
            continue
        # nothing in a TYPE is a space followed by `word(`: the first such
        # word after the `=` is the opcode (as `tools/tpucomms/hlo.py` reads)
        op = re.match(r".*?\s([a-z][\w\-]*)\(", line[m.end():])
        if not op:
            continue
        op = op.group(1)
        scope = re.search(r'op_name="([^"]*)"', line)
        scope = "/".join(scope.group(1).split("/")[-4:]) if scope else ""
        est = re.search(r'"estimated_cycles":"(\d+)"', line)
        est = int(est.group(1)) if est else 0
        cycles += est
        called = re.search(r"calls=%([\w.\-]+)", line)
        kind = "PALLAS" if "tpu_custom_call" in line else ""
        if called and re.search(r"\bconvolution\(", by_name.get(
                called.group(1), "")):
            kind = "PRODUCT"
        if op in ASYNC or kind or est > floor_cycles:
            print(f"{cycles / 1e6:8.3f}Mcyc {m.group(1):44s} {op:26s} "
                  f"{kind:8s} {est / 1e3:8.1f}k  {scope}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--out", default="described_step.hlo.txt")
    ap.add_argument("--schedule", metavar="HLO_TEXT")
    ap.add_argument("--loop", choices=("fwd", "bwd"), default="bwd")
    ap.add_argument("--floor-cycles", type=int, default=20000)
    args = ap.parse_args()
    if args.schedule:
        schedule(args.schedule, args.loop, args.floor_cycles)
    elif args.workload:
        compile_step(os.path.abspath(args.root), args.workload,
                     os.path.abspath(args.out))
    else:
        ap.error("one of --workload and --schedule")


if __name__ == "__main__":
    main()
