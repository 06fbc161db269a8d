"""Shared serve-mode machinery: resolution, placement, OOM degradation.

Both inference engines serve weights in one of three modes — `dequant`
(whole tree device-resident, int8 trees dequantized in-program),
`layer_scan` (per-layer-stacked int8 + engine-level lax.scan,
quantized_layer_scan.py) and `capacity` (host-parked layer tiers streamed
per step, capacity_scan.py) — and both walk the same OOM degradation
ladder dequant → layer_scan → capacity (docs/resilience.md). Until r11
this logic lived as v1 methods and the v2 engine borrowed `_shard_params`
UNBOUND with the resolver getattr-guarded out (v2 was pinned to dequant
placement semantics). This module is the extraction: free functions over
an `engine` argument, so v1 keeps its method surface as thin delegates
and v2 owns identical placement without a foreign unbound method.

The `engine` argument is duck-typed; the functions read `module`,
`model_cfg`, `_config`, `mesh` and `_forced_mode`, and write the
placement products `serve_mode`, `_quantized` and `_capacity` back onto
it. Degradation state (`_forced_mode`) pins the mode across a
re-placement so the resolver can't re-pick the mode that OOMed.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.resilience.faults import fault_point
from deepspeed_tpu.telemetry import get_hub
from deepspeed_tpu.telemetry.memory import get_plane, owner_for
from deepspeed_tpu.utils.logging import logger, warn_once


def degrade_enabled(config) -> bool:
    """Opt-out switch for OOM-driven serve-mode degradation."""
    res = getattr(config, "resilience", None) or {}
    return bool(res.get("degrade_on_oom", True))


def note_degraded(engine_label: str, frm: str, to: str, stage: str,
                  reason: BaseException) -> None:
    """Warn once per (from, to) pair and emit the `serve_mode_degraded`
    telemetry event (docs/telemetry.md — append-only schema)."""
    warn_once(("degrade", frm, to),
              f"inference: serve_mode degraded {frm} → {to} after "
              f"{stage} OOM ({type(reason).__name__}) — see "
              "docs/resilience.md; repeats go to telemetry only")
    hub = get_hub()
    if hub.enabled:
        try:
            # the residency snapshot makes the failure's at-rest state
            # visible in the post-mortem (the r5 2×-residency class shows
            # up as doubled hbm params bytes instead of being inferred)
            hub.emit("serve_mode_degraded", engine=engine_label,
                     from_mode=frm, to_mode=to, stage=stage,
                     reason=str(reason)[:200],
                     residency=get_plane().snapshot())
        except Exception:
            pass


def degraded_mode(engine, mode: str, params) -> Optional[str]:
    """Next rung of the degradation ladder that is structurally viable
    for this tree/mesh, or None (nothing left — the OOM re-raises).
    Mirrors `resolve_serve_mode`'s support checks: layer_scan needs a
    quantized llama-layout tree on a single-device or pure-TP mesh;
    capacity additionally streams to ONE device's HBM."""
    from deepspeed_tpu.inference import quantized_layer_scan as qls
    from deepspeed_tpu.ops.pallas.sharded import (
        nontrivial_axes, sharded_kernels_supported)
    nt = nontrivial_axes(engine.mesh)
    multi = bool(nt)
    layout_ok = isinstance(params, dict) and qls.layer_scan_supported(params)
    tp_ok = multi and set(nt) == {"model"} and sharded_kernels_supported()
    ladder = {"dequant": ("layer_scan", "capacity"),
              "layer_scan": ("capacity",)}
    for nxt in ladder.get(mode, ()):
        if (nxt == "layer_scan" and getattr(engine, "_quantized", False)
                and layout_ok and (not multi or tp_ok)):
            return nxt
        if nxt == "capacity" and layout_ok and not multi:
            return nxt
    return None


def resolve_serve_mode(engine, params) -> str:
    """Pick how weights are served (docs/quantized_serving.md,
    docs/capacity_serving.md). `auto` delegates to
    `config.choose_serve_mode`, which accounts the FULL serving
    residency — weights in each mode's at-rest form PLUS the KV cache
    and decode workspace at the config's max batch/out-tokens — so a
    tree that wouldn't even fit as int8 layer-scan picks capacity."""
    from deepspeed_tpu.inference import quantized_layer_scan as qls
    from deepspeed_tpu.inference.config import choose_serve_mode
    config = engine._config
    mode = getattr(config, "serve_mode", "auto") or "auto"
    mode = {"quantized_layer_scan": "layer_scan",
            "whole_tree": "dequant"}.get(mode, mode)
    if mode not in ("auto", "dequant", "layer_scan", "capacity"):
        raise ValueError(
            f"init_inference: unknown serve_mode {mode!r} (expected "
            "'auto', 'dequant', 'layer_scan' or 'capacity')")
    # A pallas_call cannot be GSPMD-partitioned, but layer_scan's
    # kernels now ride shard_map wrappers on a PURE tensor-parallel
    # mesh (only 'model' nontrivial — ops/pallas/sharded.py has the
    # supported matrix); the capacity loop still streams to ONE
    # device's memory and stays single-device.
    from deepspeed_tpu.ops.pallas.sharded import (
        kernel_fallback, nontrivial_axes, sharded_kernels_supported)
    nt = nontrivial_axes(engine.mesh)
    multi_dev = bool(nt)
    layout_ok = isinstance(params, dict) and qls.layer_scan_supported(params)
    tp_shardable = (multi_dev and set(nt) == {"model"}
                    and sharded_kernels_supported())
    scan_ok = layout_ok and (not multi_dev or tp_shardable)
    cap_ok = layout_ok and not multi_dev
    if mode in ("layer_scan", "capacity") and not layout_ok and \
            getattr(engine.module, "make_cache", None) is not None:
        # both stream a llama-layout stack a layer at a time and carry raw
        # (K, V) through it: a model of another layout that keeps its own
        # kind of cache is refused by name, never served by something else
        # in silence
        raise ValueError(
            f"init_inference: serve_mode={mode!r} streams llama-layout trees; "
            f"{type(engine.module).__name__} keeps its own cache and is "
            "served device-resident (serve_mode 'dequant' or 'auto')")
    if mode == "layer_scan" and not scan_ok:
        if layout_ok and multi_dev:
            kernel_fallback(
                "quantized_matmul",
                f"mesh axes {sorted(nt)} unsupported for layer_scan "
                "(a pure 'model' TP mesh shards; others dequant)")
        logger.warning(
            "serve_mode='layer_scan' needs a llama-layout param tree "
            "(stacked layers with self_attn/mlp projections) on a "
            "single-device or pure-TP mesh; falling back to "
            "whole-tree dequant")
        return "dequant"
    if mode == "capacity" and not cap_ok:
        if layout_ok and multi_dev:
            kernel_fallback(
                "capacity_scan",
                f"mesh axes {sorted(nt)} unsupported: the capacity "
                "loop streams to one device's HBM")
        logger.warning(
            "serve_mode='capacity' needs a llama-layout param tree "
            "(stacked layers with self_attn/mlp projections) on a "
            "single-device mesh; falling back to whole-tree dequant")
        return "dequant"
    if mode == "layer_scan" and not engine._quantized:
        logger.warning(
            "serve_mode='layer_scan' without quant={'enabled': True} "
            "has nothing to stream; serving device-resident (dequant). "
            "For bf16 streaming use serve_mode='capacity'.")
        return "dequant"
    if mode != "auto":
        return mode
    # ---- byte accounting for the auto decision table ----
    from deepspeed_tpu.inference.capacity_scan import (
        decode_workspace_bytes, kv_cache_bytes, recurrent_state_bytes,
        round_up_len)
    from deepspeed_tpu.inference.quantization import is_quantized_leaf
    itemsize = jnp.dtype(config.dtype).itemsize
    dense = int8 = 0
    for leaf in jax.tree_util.tree_leaves(params,
                                          is_leaf=is_quantized_leaf):
        if is_quantized_leaf(leaf):
            dense += leaf["__q8__"].size * itemsize
            int8 += leaf["__q8__"].nbytes + leaf["scales"].nbytes
        elif hasattr(leaf, "size"):
            dense += leaf.size * itemsize
            # the quantizer's eligibility rule (≥2-D, ≥min_size, float)
            if (getattr(leaf, "ndim", 0) >= 2 and leaf.size >= 4096
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                int8 += leaf.size  # + scales, negligible at group 256
            else:
                int8 += leaf.size * itemsize
    try:
        from deepspeed_tpu.accelerator import get_accelerator
        hbm = int(get_accelerator().total_memory() or 0)
    except Exception:
        hbm = 0
    num_layers = getattr(engine.model_cfg, "num_hidden_layers", None) \
        or getattr(engine.model_cfg, "n_layer", 1)
    b = int(getattr(config, "max_batch_size", None) or 1)
    max_len = round_up_len(getattr(config, "max_out_tokens", 1024))
    kv_dtype = getattr(config, "kv_cache_dtype", None)
    spec = getattr(config, "speculative", None) or {}
    spec_bytes = 0
    if spec.get("enabled"):
        # the draft's serving residency (weight copy + draft KV) joins
        # the overhead term — a tree that fits resident WITHOUT a draft
        # may need layer_scan/capacity WITH one
        from deepspeed_tpu.inference.speculative import spec_draft_bytes
        spec_bytes = spec_draft_bytes(
            spec, engine.model_cfg, dense,
            kv_cache_bytes(engine.model_cfg, b, max_len,
                           config.dtype, kv_dtype=kv_dtype))
    return choose_serve_mode(
        quantized=engine._quantized, layout_ok=layout_ok,
        multi_device=multi_dev, dense_bytes=dense, int8_bytes=int8,
        layer_bytes=dense // max(1, int(num_layers)),
        # what the cache holds: K and V of the attention layers and, for a
        # model that has them, the recurrent layers' state
        kv_bytes=kv_cache_bytes(engine.model_cfg, b, max_len,
                                config.dtype, kv_dtype=kv_dtype)
        + recurrent_state_bytes(engine.model_cfg, b, config.dtype),
        workspace_bytes=decode_workspace_bytes(
            engine.model_cfg, b, max_len, config.dtype),
        hbm_bytes=hbm,
        # total_memory() is PER DEVICE — the mesh aggregates it (the
        # r7 bugfix: a 7B tree on 2+ chips picks layer_scan, not
        # capacity, because weights and KV shard over the mesh)
        n_devices=int(engine.mesh.devices.size),
        tp_shardable=tp_shardable, spec_bytes=spec_bytes)


def place_params(engine, params):
    """Resolve the serve mode, then place params for it: capacity mode
    parks the layer tiers HOST-side (never staging the whole tree into
    device memory — the point of the mode); the resident modes cast to
    the inference dtype and place with TP shardings. Writes
    `serve_mode`, `_quantized` and `_capacity` onto the engine; a
    degradation recovery pins the mode via `engine._forced_mode`
    instead of re-resolving (the resolver would re-pick the mode that
    OOMed)."""
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    model, cfg = engine.module, engine._config
    engine._quantized = bool(cfg.quant and cfg.quant.get("enabled"))
    engine._capacity = None
    # residency accounting: one owner per engine; re-placement (the
    # degradation ladder) drops the owner's prior rows first so the
    # plane never double-counts a replaced tree
    owner = owner_for(engine, type(engine).__name__)
    get_plane().release_owner(owner)
    # serve-mode resolution is pure size accounting — it runs on the
    # RAW tree so capacity mode can skip whole-tree device placement
    forced = getattr(engine, "_forced_mode", None)
    if forced is not None:
        engine.serve_mode = forced
    else:
        engine.serve_mode = resolve_serve_mode(engine, params)
    if engine.serve_mode == "capacity":
        from deepspeed_tpu.inference.capacity_scan import CapacityRunner
        group = int((cfg.quant or {}).get("group_size", 256))
        engine._capacity = CapacityRunner(
            engine.model_cfg, cfg, params, mesh=engine.mesh,
            quantized=engine._quantized, group_size=group,
            options=getattr(cfg, "capacity", None), memory_owner=owner)
        fault_point("param_placement", label="capacity")
        return engine._capacity.params_view()
    ids = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    _, specs = extract_params_and_specs(abstract)

    from deepspeed_tpu.inference.quantization import is_quantized_leaf

    def place(x, spec):
        if is_quantized_leaf(x):
            # PRE-quantized leaf (big-model path: quantized leaf-wise
            # during load so bf16 and int8 never fully coexist): the
            # int8 block takes the kernel's spec; the lower-rank
            # scales replicate
            return {"__q8__": jax.device_put(
                        jnp.asarray(x["__q8__"]),
                        NamedSharding(engine.mesh, spec)),
                    "scales": jax.device_put(
                        jnp.asarray(x["scales"]),
                        NamedSharding(engine.mesh, P()))}
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(cfg.dtype)
        return jax.device_put(x, NamedSharding(engine.mesh, spec))

    params = jax.tree_util.tree_map(place, params, specs,
                                    is_leaf=is_quantized_leaf)
    if engine._quantized:
        group = int(cfg.quant.get("group_size", 256))
        if engine.serve_mode == "layer_scan":
            # per-layer stacked quantization: scales keep a leading L
            # dim so the generate-time lax.scan slices one layer's
            # int8+scales per step (quantized_layer_scan serve mode)
            from deepspeed_tpu.inference.quantized_layer_scan import (
                quantize_layer_stacks)
            params = quantize_layer_stacks(params, group_size=group)
            if any(int(s) > 1 for s in engine.mesh.shape.values()):
                # TP layer scan: re-pin the quantized stacks — the
                # int8 block keeps the kernel's placement spec (the
                # at-rest layout the shard_map wrappers expect), the
                # lower-rank scales replicate (sliced for free inside
                # the manual regions)
                def repin(leaf, spec):
                    if is_quantized_leaf(leaf):
                        return {"__q8__": jax.device_put(
                                    leaf["__q8__"],
                                    NamedSharding(engine.mesh, spec)),
                                "scales": jax.device_put(
                                    leaf["scales"],
                                    NamedSharding(engine.mesh, P()))}
                    return leaf
                params = jax.tree_util.tree_map(
                    repin, params, specs, is_leaf=is_quantized_leaf)
        else:
            # ZeRO-Inference whole-tree int8 at rest
            # (inference/quantization.py); dequantized in one piece
            # inside the serving program
            from deepspeed_tpu.inference.quantization import (
                quantize_param_tree)
            params, _ = quantize_param_tree(params, group_size=group)
            params = jax.tree_util.tree_map(jax.device_put, params)
    # the placed tree's at-rest bytes (quantized forms included — the
    # leaves carry their own nbytes) — split by tier in case a leaf was
    # pinned to host memory
    get_plane().register_tree(f"{owner}:params", component="params",
                              tree=params, owner=owner)
    # sits AFTER full placement, so an injected OOM here leaves a
    # fully-placed tree in the raising frame — the degradation path's
    # drop-before-replace behavior is exercised for real
    fault_point("param_placement", label=engine.serve_mode)
    return params
