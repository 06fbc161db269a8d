"""ProgramUnderTest: the unit tpuverify's contracts check.

Two kinds:

- ``ProgramUnderTest`` (kind="program"): ONE compiled program — a raw,
  lowerable jit plus the abstract argument signature it was dispatched
  with (recorded by the RecompileDetector during the smoke run). Contracts
  read its jaxpr (``make_jaxpr``) and its AOT lowering (``.lower()``) —
  both chip-free static analyses.
- ``EngineUnderTest`` (kind="engine"): one live engine's bookkeeping — the
  pinned param/cache trees, the RecompileDetector, and the
  (compiled program → detector name) records the registration-coverage
  contract cross-checks.

``build_default_matrix`` constructs the tiny-model matrix (train engine,
v1 generate, v2 serving) on the virtual CPU mesh, smoke-dispatches each
engine once with signature recording enabled, then harvests every
compiled program out of the engine caches. Serve-mode variants
(layer_scan / capacity / speculative) ride the same builders from the
slow tests — the default matrix stays within the tier-1 budget.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# -------------------------------------------------------------------- PUTs


@dataclass
class ProgramUnderTest:
    name: str
    fn: Any                      # raw lowerable jit (never a telemetry wrap)
    args: tuple                  # abstract example args (ShapeDtypeStructs)
    donate: Optional[Tuple[int, ...]] = None  # argnums contracted to donate
    cache_shapes: frozenset = frozenset()     # (shape, dtype) of KV buffers
    scatter_budget: int = 2      # per body per aval: one K + one V scatter
    # (shape, dtype) of the stacked paged pools (and int8 scales) a v2
    # program must keep whole and in place (kv-pool-in-place)
    pool_shapes: frozenset = frozenset()
    # (shape, dtype) of the stacked dense cache a v1 generate program creates
    # and must keep whole and in place from its first decode step to its
    # last, and the length of its token loop (kv-pool-in-place)
    stack_shapes: frozenset = frozenset()
    token_loop: Optional[int] = None
    allow_shard_map: bool = False
    check_callbacks: bool = True
    kind: str = "program"
    _jaxpr: Any = field(default=None, repr=False)
    _lowered: Any = field(default=None, repr=False)

    def jaxpr(self):
        if self._jaxpr is None:
            import jax
            self._jaxpr = jax.make_jaxpr(self.fn)(*self.args)
        return self._jaxpr

    def lowered(self):
        """AOT lowering, or None when the callable has no ``.lower`` (the
        v1 auto-layout path stores a plain lambda on TPU — contracts that
        need the lowering skip those)."""
        if self._lowered is None:
            if not hasattr(self.fn, "lower"):
                return None
            self._lowered = self.fn.lower(*self.args)
        return self._lowered


@dataclass(frozen=True)
class CompiledRecord:
    """One compiled program's registration pair: how the engine labels
    it and what the RecompileDetector knows it as (None = untracked —
    itself a violation)."""
    label: str
    detector_name: Optional[str]


@dataclass
class EngineUnderTest:
    name: str
    detector: Any                                  # RecompileDetector
    records: List[CompiledRecord]
    pinned_trees: List[Tuple[str, Any]]            # (label, pytree)
    check_signatures: bool = True
    bulk_bytes: int = 4096   # leaves at/above this entering a pinned
    #                          program must be committed (params/caches;
    #                          per-call ids/rng stay under it)
    # MemoryPlane component totals for this engine's owner after the smoke
    # dispatch ({component: bytes}) — the residency-coverage contract
    residency: Dict[str, int] = field(default_factory=dict)
    kind: str = "engine"


# ----------------------------------------------------------------- builders


def _reset_topology():
    from deepspeed_tpu.utils import groups
    groups.reset_topology()


def _engine_residency(eng) -> Dict[str, int]:
    """This engine's MemoryPlane component totals (owner-scoped, so other
    engines built in the same process never bleed in)."""
    from deepspeed_tpu.telemetry.memory import (COMPONENTS, get_plane,
                                                owner_for)
    owner = owner_for(eng, type(eng).__name__)
    plane = get_plane()
    return {c: plane.total(component=c, owner=owner) for c in COMPONENTS}


def _tiny_mlp():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, y=None):
            h = nn.relu(nn.Dense(16, name="linear_0")(x))
            out = nn.Dense(x.shape[-1], name="head")(h)
            if y is None:
                return out
            return jnp.mean((out - y) ** 2), {}

    model = MLP()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.float32))["params"]
    return model, params


def build_train_puts() -> List[Any]:
    """ZeRO-3 train engine on the CPU mesh: one fused train_batch program.
    Contract surface: the TrainState (argnum 0) must be donated, no host
    callbacks, no rogue shard_map, and the program must be pinned in the
    detector."""
    import numpy as np

    import deepspeed_tpu

    _reset_topology()
    model, params = _tiny_mlp()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        loss_fn=lambda p, b, r: model.apply({"params": p}, b["x"], b["y"]),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 3}})
    engine.recompiles.record_signatures = True
    rng = np.random.default_rng(0)
    rows = engine.topology.dense_dp_size * 2
    batch = {"x": rng.standard_normal((rows, 8)).astype(np.float32),
             "y": rng.standard_normal((rows, 8)).astype(np.float32)}
    engine.train_batch(batch=batch)

    puts: List[Any] = []
    records = []
    donate = None if engine._offload_manual else (0,)
    for name, fn in engine._jit_cache.items():
        if name == "eval":
            continue
        records.append(CompiledRecord(label=f"train:{name}",
                                      detector_name=name))
        args = engine.recompiles.abstract.get(name)
        if args is None:
            continue  # built but never dispatched — registration flags it
        puts.append(ProgramUnderTest(name=f"train:{name}", fn=fn, args=args,
                                     donate=donate))
    puts.append(EngineUnderTest(
        name="train", detector=engine.recompiles, records=records,
        pinned_trees=[],
        check_signatures=False,  # train batches are per-step host arrays
        residency=_engine_residency(engine)))
    return puts


def _v1_cache_shapes(eng, key) -> frozenset:
    """The KV-cache avals of one v1 generate program: v1 creates its cache
    IN-program with the engine's cache params, so reconstruct the same
    shapes via eval_shape (chip-free)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.kv_cache import scatter_target_shapes
    return scatter_target_shapes(_v1_cache(eng, key))


def _v1_cache(eng, key):
    """The shape tree of the cache `_build_generate` creates for `key`: the
    model's own (`make_cache`) where it has a say, the per-layer view else."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.kv_cache import KVCache
    b, s, new = key[0], key[1], key[2]
    max_len = -(-(s + new) // 128) * 128
    cfg = eng.model_cfg
    dtype = getattr(cfg, "dtype", jnp.float32)
    quantized = getattr(eng._config, "kv_cache_dtype", None) == "int8" and \
        getattr(eng, "serve_mode", "dequant") == "dequant"
    make_cache = getattr(eng.module, "make_cache", None)
    if make_cache is not None and \
            getattr(eng, "serve_mode", "dequant") == "dequant":
        return jax.eval_shape(lambda: make_cache(b, max_len, dtype=dtype,
                                                 quantized=quantized))
    return jax.eval_shape(
        lambda: KVCache.create(cfg.num_hidden_layers, b, max_len,
                               cfg.num_key_value_heads, cfg.head_dim,
                               dtype=dtype, quantized=quantized))


def build_v1_chip_dispatch_put(model_cls=None) -> ProgramUnderTest:
    """A v1 generate program (`jit_ds_v1_generate_b<rows>_s<prompt>_n<new>` on
    a device trace since PR 56) of a tiny llama (GQA group of 4, so that 'auto'
    picks the decode kernel) TRACED as the chip dispatches it: the dense
    decode kernel on the stacked cache by layer, the staged token, the
    Pallas writer. Off the chip the same program attends through the masked
    XLA path, which cuts a layer out of the stack to read it; what
    kv-pool-in-place holds is the chip's program, so its jaxpr is made here
    under the chip's dispatch (nothing runs). `model_cls`: another module
    class over the same tree (a test's per-layer-view reference)."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    import deepspeed_tpu.ops.attention as attention
    from deepspeed_tpu.models.llama import (LlamaForCausalLM, llama_config,
                                            materialize_params)
    from deepspeed_tpu.telemetry.recompile import abstract_args

    _reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32, num_attention_heads=8)
    _, params = materialize_params(cfg)
    eng = deepspeed_tpu.init_inference((model_cls or LlamaForCausalLM)(cfg),
                                       params=params, dtype="fp32")
    key = (2, 8, 4, 0.0, 0, 1.0, None, 0)
    args = abstract_args((eng.params, jax.ShapeDtypeStruct((2, 8), jnp.int32),
                          jax.random.PRNGKey(0)))
    with mock.patch.object(attention, "_use_pallas", lambda: True):
        fn = eng._build_generate(*key)
        jaxpr = jax.make_jaxpr(fn)(*args)
    cache = _v1_cache(eng, key)
    cache = getattr(cache, "kv", cache)
    return ProgramUnderTest(
        name="v1:generate[chip dispatch]", fn=fn, args=args,
        stack_shapes=frozenset(
            (tuple(x.shape), str(x.dtype))
            for x in jax.tree_util.tree_leaves((cache.k, cache.v))),
        token_loop=key[2] - 1, _jaxpr=jaxpr)


def build_v1_puts(serve_mode: Optional[str] = None,
                  quant: Optional[dict] = None,
                  speculative: Optional[dict] = None) -> List[Any]:
    """v1 inference engine (llama-tiny) smoke-dispatched through generate.
    The default matrix runs the dequant mode; the slow tests pass the
    other serve modes through the same builder."""
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import llama_config, materialize_params

    _reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    kwargs: Dict[str, Any] = {}
    if serve_mode is not None:
        kwargs["serve_mode"] = serve_mode
    if quant is not None:
        kwargs["quant"] = quant
    if speculative is not None:
        kwargs["speculative"] = speculative
    eng = deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                       **kwargs)
    eng.recompiles.record_signatures = True
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
    eng.generate(ids, max_new_tokens=4)

    label = f"v1[{serve_mode or eng.serve_mode}]"
    puts: List[Any] = []
    records = []
    spec = getattr(eng, "_spec", None)
    jits = dict(spec._jit) if spec is not None else dict(eng._generate_jit)
    names = spec._program_names if spec is not None else eng._program_names
    for key, fn in jits.items():
        det_name = names.get(key)
        name = (spec or eng)._program_name(key)
        records.append(CompiledRecord(label=f"{label}:{key}",
                                      detector_name=det_name))
        if det_name is None or not hasattr(fn, "lower"):
            continue  # untracked (registration flags it) / auto-layout
        if spec is not None:
            # the spec program signature is (params, draft_params, ids,
            # rng) — wider than what the detector observed; rebuild the
            # abstract args from the live trees. Spec cache sizing is the
            # decoder's own (k-widened) — the scatter contract is checked
            # on the underlying vanilla programs, not re-derived here.
            import jax
            from deepspeed_tpu.telemetry.recompile import abstract_args
            ids_sds = jax.ShapeDtypeStruct((key[0], key[1]), jnp.int32)
            args = abstract_args((eng.params, spec._draft_params, ids_sds,
                                  jax.random.PRNGKey(0)))
            puts.append(ProgramUnderTest(name=name, fn=fn, args=args,
                                         donate=None))
            continue
        args = eng.recompiles.abstract.get(det_name)
        if args is None:
            continue
        puts.append(ProgramUnderTest(
            name=name, fn=fn, args=args, donate=None,
            cache_shapes=_v1_cache_shapes(eng, key)))
    puts.append(EngineUnderTest(
        name=label, detector=eng.recompiles, records=records,
        pinned_trees=[(f"{label}.params", eng.params)],
        residency=_engine_residency(eng)))
    if serve_mode is None and quant is None and speculative is None:
        puts.append(build_v1_chip_dispatch_put())
    return puts


def build_v2_puts(serve_mode: Optional[str] = None,
                  quant: Optional[dict] = None) -> List[Any]:
    """v2 serving engine (llama-tiny): prefill + decode smoke, then every
    compiled program out of ``_jits``. Contract surface: cache (argnum 1)
    donation, pinned params AND cache leaves, staged-append scatter
    discipline, registration. ``serve_mode`` routes the big-model modes
    through the same builder (layer_scan rides the default matrix;
    capacity's eager host-loop fns carry ``_ds_raw=None`` and are skipped
    program-wise — the EngineUnderTest registration check still covers
    them)."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.kv_cache import scatter_target_shapes
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import llama_config, materialize_params

    _reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    kwargs: Dict[str, Any] = {}
    if serve_mode is not None:
        kwargs["serve_mode"] = serve_mode
    if quant is not None:
        kwargs["quant"] = quant
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                           **kwargs)
    v2.recompiles.record_signatures = True
    rng = np.random.default_rng(0)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    out = v2.put([7], [np.asarray(prompt)])          # prefill program
    v2.put([7], [[int(np.argmax(out[7]))]])          # decode program

    label = "v2" if serve_mode in (None, "dequant") else f"v2[{serve_mode}]"
    cache_shapes = scatter_target_shapes(v2.cache)
    # the zoo model's cached scan addresses the pools by layer; the
    # layer_scan and capacity modes have loops of their own over per-layer
    # views, which kv-pool-in-place does not hold yet
    pool_shapes = frozenset()
    if v2.kv_layout == "paged" and v2.serve_mode == "dequant":
        pool_shapes = frozenset(
            (tuple(x.shape), str(x.dtype))
            for side in (v2.cache.k, v2.cache.v)
            for x in (side.pool, side.scales) if x is not None)
    puts: List[Any] = []
    records = []
    for key, fn in v2._jits.items():
        first = key if isinstance(key, str) else key[0]
        if first == "sample":
            # on-device logits reduce, not a serving program (deliberately
            # untracked: its signature is (logits, rng) per bucket)
            continue
        raw = getattr(fn, "_ds_raw", None)
        det_name = getattr(fn, "_ds_program", None)
        records.append(CompiledRecord(label=f"{label}:{key}",
                                      detector_name=det_name))
        if raw is None or det_name is None:
            continue
        args = v2.recompiles.abstract.get(det_name)
        if args is None:
            continue
        donate = (0,) if first == "cow_copy" else (1,)
        puts.append(ProgramUnderTest(
            name=f"v2:{det_name}", fn=raw, args=args, donate=donate,
            cache_shapes=cache_shapes, pool_shapes=pool_shapes))
    puts.append(EngineUnderTest(
        name=label, detector=v2.recompiles, records=records,
        pinned_trees=[(f"{label}.params", v2.params),
                      (f"{label}.cache", v2.cache)],
        residency=_engine_residency(v2)))
    return puts


def build_default_matrix(include: Sequence[str] = ("train", "v1", "v2",
                                                   "v2_layer_scan")
                         ) -> List[Any]:
    """The tier-1 matrix: train + v1 dequant generate + v2 serving (dequant
    AND int8 layer_scan — the big-model mode's scan-body programs get the
    same static checks), all on the virtual CPU mesh. ~4 tiny-model
    compiles."""
    builders = {"train": build_train_puts,
                "v1": build_v1_puts,
                "v2": build_v2_puts,
                "v2_layer_scan": lambda: build_v2_puts(
                    serve_mode="layer_scan", quant={"enabled": True})}
    unknown = [k for k in include if k not in builders]
    if unknown:
        raise KeyError(f"unknown matrix component(s): {unknown} "
                       f"(known: {sorted(builders)})")
    puts: List[Any] = []
    for k in include:
        puts.extend(builders[k]())
    return puts
