"""CommsProgram: the unit tpucomms' contracts check, plus the builders.

A CommsProgram is one compiled program plus its comms expectations: the
mesh axes it is allowed to communicate over, the analytic wire-byte
budget its ZeRO partition plan implies (train only), and the weight
shapes no serving program may all-gather. ``fingerprint()`` compiles the
program on the virtual CPU mesh and decodes ``compiled.as_text()``;
programs this jaxlib cannot compile (shard_map-manual — the 0.4.x
``PartitionId UNIMPLEMENTED`` class) fall back to jaxpr-level collective
extraction. The known-SIGABRT pipeline-rotation family is never built
here at all: the default matrix has no pp>1 engine, and any
``allow_shard_map`` program harvested from the tpuverify builders is
routed to the jaxpr path without touching backend_compile.

``build_comms_matrix`` reuses tpuverify's engine builders (same smoke
dispatches) so the two tools stay in lockstep about
what "the engine matrix" means; only the train component is rebuilt
bigger here — comm-volume analysis needs token-heavy shapes (a tiny
model's params fall under ``param_persistence_threshold`` and GSPMD
gathers activations instead of weights, hiding exactly the traffic the
budget contract is about).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

from deepspeed_tpu.tools.tpucomms.fingerprint import (CommsFingerprint,
                                                      fingerprint_hlo,
                                                      fingerprint_jaxpr)

# numpy dtype name → HLO dtype token (weight-shape matching)
_NP_TO_HLO = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int8": "s8", "uint8": "u8", "int16": "s16",
    "int32": "s32", "int64": "s64", "uint32": "u32", "uint64": "u64",
    "bool": "pred",
}


@dataclass
class CommsProgram:
    name: str
    fn: Any                       # raw lowerable jit (or traceable callable)
    args: tuple                   # abstract example args
    sizes_map: Dict[str, int]     # canonical axis sizes at build time
    declared_axes: Optional[FrozenSet[str]] = None
    kind: str = "train"           # "train" | "serving"
    loop_multiplier: int = 1      # GAS trip count for in-loop collectives
    budget_bytes: Optional[int] = None
    budget_note: str = ""
    weight_shapes: FrozenSet[Tuple[Tuple[int, ...], str]] = frozenset()
    prefer_jaxpr: bool = False
    _fp: Optional[CommsFingerprint] = field(default=None, repr=False)

    def fingerprint(self) -> CommsFingerprint:
        if self._fp is not None:
            return self._fp
        if not self.prefer_jaxpr and hasattr(self.fn, "lower"):
            try:
                txt = self.fn.lower(*self.args).compile().as_text()
                self._fp = fingerprint_hlo(
                    self.name, txt, self.sizes_map,
                    loop_multiplier=self.loop_multiplier)
                return self._fp
            except Exception:
                pass  # old-jax partitioner gaps → jaxpr-level extraction
        import jax
        jaxpr = jax.make_jaxpr(self.fn)(*self.args)
        self._fp = fingerprint_jaxpr(self.name, jaxpr, self.sizes_map)
        return self._fp


# ----------------------------------------------------------------- analytic


def analytic_step_bytes(stage: int, param_bytes: int, gas: int = 1) -> int:
    """Ideal per-train-step wire bytes implied by the ZeRO plan, in the
    fingerprint's conventions (all-gather = gathered bytes, all-reduce =
    2×, reduce-scatter = input bytes): stage 3 moves ≤ 3×P per
    micro-step (fwd gather + bwd gather + grad reduce-scatter); stage
    1/2 reduce grads (2×P as AR) per micro-step plus one param gather
    per step; stage 0 just reduces grads. XLA's LICM typically hoists
    loop-invariant gathers out of the GAS scan, so observed volume lands
    UNDER these budgets — they are ceilings, not targets."""
    if stage >= 3:
        return 3 * param_bytes * gas
    if stage in (1, 2):
        return 2 * param_bytes * gas + param_bytes
    return 2 * param_bytes * gas


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(x.size) * int(x.dtype.itemsize)
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))


def _weight_shapes(tree) -> FrozenSet[Tuple[Tuple[int, ...], str]]:
    """(shape, hlo-dtype) of every ≥2-D param leaf; stacked nn.scan
    leaves also contribute their per-layer slice ``shape[1:]`` — the
    partitioner gathers inside the scan body at the sliced shape."""
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "shape") or len(leaf.shape) < 2:
            continue
        tok = _NP_TO_HLO.get(str(leaf.dtype), "f32")
        out.add((tuple(int(d) for d in leaf.shape), tok))
        if len(leaf.shape) >= 3:
            out.add((tuple(int(d) for d in leaf.shape[1:]), tok))
    return frozenset(out)


def _current_sizes() -> Dict[str, int]:
    from deepspeed_tpu.utils import groups
    return dict(groups.get_topology().sizes)


# ----------------------------------------------------------------- builders

# Train programs may ride every axis except the pipeline ring (no pp>1
# engine in the matrix; rotation is shard_map-manual and audited at the
# jaxpr level where it appears).
TRAIN_DECLARED = frozenset(("repl", "data", "expert", "sequence", "model"))
# Single-host serving communicates over the tensor-parallel axis only.
SERVING_DECLARED = frozenset(("model",))


def _token_mlp(dim: int = 128):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, y=None):
            h = nn.relu(nn.Dense(dim, name="linear_0")(x))
            out = nn.Dense(x.shape[-1], name="head")(h)
            if y is None:
                return out
            return jnp.mean((out - y) ** 2), {}

    model = MLP()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, dim), jnp.float32))["params"]
    return model, params


def build_train_comms(gas: int = 2, mbs: int = 16,
                      dim: int = 128) -> List[CommsProgram]:
    """ZeRO-3 train engine sized for comm-volume analysis: hidden 128
    (persistence threshold forced to 0 so every leaf shards — the
    default 1e5 keeps tiny models replicated and comm-free) and
    token-heavy micro-batches (at activation-heavy ratios GSPMD gathers
    the activations instead of the weights and the fingerprint stops
    measuring the plan)."""
    import numpy as np

    import deepspeed_tpu

    from deepspeed_tpu.utils import groups
    groups.reset_topology()
    model, params = _token_mlp(dim)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        loss_fn=lambda p, b, r: model.apply({"params": p}, b["x"], b["y"]),
        config={"train_micro_batch_size_per_gpu": mbs,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {
                    "stage": 3,
                    "stage3_param_persistence_threshold": 0}})
    engine.recompiles.record_signatures = True
    rng = np.random.default_rng(0)
    rows = engine.topology.dense_dp_size * mbs * gas
    batch = {"x": rng.standard_normal((rows, dim)).astype(np.float32),
             "y": rng.standard_normal((rows, dim)).astype(np.float32)}
    engine.train_batch(batch=batch)

    sizes = dict(engine.topology.sizes)
    p_bytes = _tree_bytes(engine.state.params)
    budget = analytic_step_bytes(3, p_bytes, gas)
    puts: List[CommsProgram] = []
    for name, fn in engine._jit_cache.items():
        if name == "eval":
            continue
        args = engine.recompiles.abstract.get(name)
        if args is None:
            continue
        puts.append(CommsProgram(
            name=f"train:{name}", fn=fn, args=args, sizes_map=sizes,
            declared_axes=TRAIN_DECLARED, kind="train",
            loop_multiplier=gas,
            budget_bytes=budget if name == "train_batch" else None,
            budget_note=f"zero3 3xP x gas{gas}, P={p_bytes}B"))
    return puts


def _convert_verify_puts(vputs, declared: FrozenSet[str]
                         ) -> List[CommsProgram]:
    """tpuverify PUT group → CommsPrograms: programs keep their raw jits
    and abstract args; weight shapes come from the group's pinned
    ``*.params`` trees; shard_map-manual programs go to the jaxpr path."""
    sizes = _current_sizes()
    weights: FrozenSet[Tuple[Tuple[int, ...], str]] = frozenset()
    for p in vputs:
        if p.kind != "engine":
            continue
        for label, tree in p.pinned_trees:
            if label.endswith(".params"):
                weights = weights | _weight_shapes(tree)
    out: List[CommsProgram] = []
    for p in vputs:
        if p.kind != "program":
            continue
        out.append(CommsProgram(
            name=p.name, fn=p.fn, args=p.args, sizes_map=sizes,
            declared_axes=declared, kind="serving",
            weight_shapes=weights,
            prefer_jaxpr=bool(getattr(p, "allow_shard_map", False))))
    return out


def build_comms_matrix(include: Sequence[str] = ("train", "v1", "v2",
                                                 "v2_layer_scan")
                       ) -> List[CommsProgram]:
    """The default matrix: the volume-sized train engine plus the same
    v1/v2 serving engines tpuverify smokes (dequant generate, v2 paged
    serving, v2 int8 layer_scan), all on the virtual CPU mesh."""
    from deepspeed_tpu.tools.tpuverify.put import (build_v1_puts,
                                                   build_v2_puts)
    serving = {
        "v1": build_v1_puts,
        "v2": build_v2_puts,
        "v2_layer_scan": lambda: build_v2_puts(
            serve_mode="layer_scan", quant={"enabled": True}),
    }
    unknown = [k for k in include if k != "train" and k not in serving]
    if unknown:
        raise KeyError(f"unknown matrix component(s): {unknown} "
                       f"(known: {['train'] + sorted(serving)})")
    puts: List[CommsProgram] = []
    for k in include:
        if k == "train":
            puts.extend(build_train_comms())
        else:
            puts.extend(_convert_verify_puts(serving[k](),
                                             SERVING_DECLARED))
    return puts


# ------------------------------------------------------------- dryrun audit


def audit_train_engine(engine, declared_axes: FrozenSet[str] = TRAIN_DECLARED
                       ) -> List[str]:
    """Axis-confinement audit of a LIVE engine's compiled programs — the
    dryrun_multichip comms phase. Returns human-readable problem strings
    (empty = clean). 0.4.x-safe: programs that fail to compile here fall
    back to jaxpr extraction inside fingerprint()."""
    sizes = dict(engine.topology.sizes)
    problems: List[str] = []
    for name, fn in engine._jit_cache.items():
        if name == "eval":
            continue
        args = engine.recompiles.abstract.get(name)
        if args is None:
            continue
        put = CommsProgram(name=f"train:{name}", fn=fn, args=args,
                           sizes_map=sizes, declared_axes=declared_axes,
                           kind="train")
        fp = put.fingerprint()
        for op in fp.ops:
            if not op.regular:
                problems.append(f"{put.name}: {op.kind} {op.shape}: "
                                f"irregular replica groups")
            stray = sorted(set(op.axes) - declared_axes)
            if stray:
                problems.append(f"{put.name}: {op.kind} {op.shape}: "
                                f"undeclared axis(es) {stray}")
    return problems
