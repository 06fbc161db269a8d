"""Mesh-partitionable serving kernels — shard_map support + decode wrappers.

GSPMD cannot partition a `pallas_call`: on a multi-device mesh every
custom serving kernel previously bailed out of the one-mesh architecture
(megablox → ragged, fused int8 → whole-tree dequant, decode kernels →
masked XLA), silently. The wrappers here and in `grouped_gemm.py` /
`quantized_matmul.py` put each kernel inside a shard_map MANUAL region
instead — consistent with the invariant that manual regions appear
exactly where the wire format matters, which a Pallas call on sharded
operands is.

Three rules the regions follow (verified by the parity suite on the
virtual 8-device CPU mesh):

- FULL-manual regions only (never an ``axis_names`` subset).
- no ``jax.lax.axis_index``/``axis_size`` inside a region. Shard identity
  rides a SHARDED INPUT: ``jnp.arange(n_shards) * per_shard`` with spec
  ``P(axis)``, each shard reading element ``[0]`` — the SNIPPETS
  tpu_inference fused-MoE idiom. Axis sizes come statically from
  ``mesh.shape``.
- replicated operands get an explicit ``P()`` spec (trailing dims of a
  PartitionSpec are unsharded, so ``P()`` replicates any rank).

Supported matrix (docs/quantized_serving.md has the serving view):

| kernel                      | mesh axes   | sharding                     |
|-----------------------------|-------------|------------------------------|
| grouped GEMM (megablox)     | 'expert'    | experts over shards, per-    |
|                             |             | shard group_offset, psum     |
| fused int8 dequant-GEMM     | 'model'     | N-sharded (column-parallel)  |
|                             |             | or K-sharded + psum          |
| dense decode attn / write   | 'model'     | KV-head-sharded, no psum     |
| paged decode/prefill/write  | 'model'     | KV-head-sharded, no psum     |

Everything else (other axes nontrivial, non-divisible shapes, kernels
disabled) falls back to the XLA path — loudly, via `kernel_fallback`
(WARN + a `kernel_fallback` telemetry event; docs/telemetry.md).
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.utils.logging import WARNED_ONCE, logger, warn_once

# alias of the SHARED once-per-key registry (utils/logging.py) — the same
# dedup backs the resilience retry/degradation warnings, so there is one
# registry to clear in tests and one implementation of "warn once"
_WARNED: set = WARNED_ONCE


def kernel_fallback(kernel: str, reason: str) -> None:
    """A sharded-kernel path is falling back to XLA: log a warning (once
    per (kernel, reason) — the shared `warn_once` registry) and emit a
    `kernel_fallback` telemetry event — the r7 contract that multi-device
    fallbacks are never silent."""
    warn_once((kernel, reason),
              f"kernel_fallback: {kernel}: {reason} — using the "
              "XLA path (see docs/quantized_serving.md for the "
              "supported mesh matrix)")
    try:
        from deepspeed_tpu.telemetry import get_hub
        hub = get_hub()
        if hub.enabled:
            hub.emit("kernel_fallback", kernel=kernel, reason=reason)
    except Exception:  # telemetry must never break a trace
        pass


def kernel_shard_map(body, mesh, in_specs, out_specs):
    """Full-manual `jax.shard_map` around a Pallas call. `check_vma` is off:
    a `pallas_call`'s `out_shape` carries no varying-axes annotation, which
    the checker refuses outright; every wrapper here states its sharding
    in `out_specs` and takes no replication claim from the checker."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def sharded_kernels_supported() -> bool:
    """Gate for every sharded-kernel route: True unless
    DS_TPU_DISABLE_SHARDED_KERNELS=1, the kill switch (forces the pre-r7
    single-device-only dispatch everywhere)."""
    return not os.environ.get("DS_TPU_DISABLE_SHARDED_KERNELS")


def nontrivial_axes(mesh) -> Dict[str, int]:
    """{axis: size} for the mesh axes with size > 1."""
    if not hasattr(mesh, "axis_names"):
        return {}
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names
            if int(mesh.shape[a]) > 1}


def _topology_mesh():
    from deepspeed_tpu.utils import groups
    try:
        return groups.get_topology(create_default=False).mesh
    except RuntimeError:
        return None


def serving_mesh(axis: str) -> Tuple[Optional[object], int]:
    """(mesh, size-of-axis) when the installed topology's ONLY nontrivial
    axis is `axis` and sharded kernels are enabled; (None, 1) otherwise.
    The single-nontrivial-axis restriction is what lets the wrappers use
    full-manual regions with P() on every other dim: a second nontrivial
    axis (batch-parallel 'data', pipeline) would be forcibly replicated
    inside the region, fighting GSPMD's layout outside it."""
    if not sharded_kernels_supported():
        return None, 1
    mesh = _topology_mesh()
    if mesh is None:
        return None, 1
    nt = nontrivial_axes(mesh)
    if set(nt) != {axis}:
        return None, 1
    return mesh, nt[axis]


def mesh_fingerprint(mesh=None) -> str:
    """Stable mesh tag for ledger/recompile program names: "" on a
    single-device (or absent) mesh — existing row names are a stability
    contract and must not change — else the nontrivial axes in canonical
    order, e.g. "expert4_model2". Used as `name@fingerprint`."""
    if mesh is None:
        mesh = _topology_mesh()
    if mesh is None:
        return ""
    nt = nontrivial_axes(mesh)
    if not nt:
        return ""
    from deepspeed_tpu.utils.groups import MESH_AXES
    order = {a: i for i, a in enumerate(MESH_AXES)}
    return "_".join(f"{a}{nt[a]}"
                    for a in sorted(nt, key=lambda a: order.get(a, 99)))


# ---- decode-attention wrappers (tensor-parallel over 'model') ----
#
# Attention is per-head compute: sharding the (KV-)head dim needs no
# collective at all — each shard answers its own heads and out_specs
# reassemble the head axis. The GQA head-packing survives because H and
# Hkv shard by the same factor (n_rep is per-group, intact per shard).


def decode_heads_shardable(h: int, hkv: int, tp: int) -> bool:
    """True when the decode kernels can head-shard over a tp-way 'model'
    axis: both the query heads and the KV heads must divide."""
    return tp > 1 and h % tp == 0 and hkv % tp == 0


def sharded_decode_attention(q, k_cache, v_cache, lengths, mesh,
                             softmax_scale: Optional[float] = None,
                             block_k: Optional[int] = None,
                             k_scales=None, v_scales=None, layer=None,
                             k_new=None, v_new=None):
    """`decode_attention` with q (B,1,H,D) and the dense caches head-sharded
    over 'model': a layer's own (B,M,Hkv,D), or with `layer` (replicated)
    the stacked (L,B,Hkv,M,D), whose staged token `k_new`/`v_new` (B,Hkv,D)
    shards with the heads. int8 caches carry (B,M,Hkv) scale leaves sharded
    on the same head axis. Caller guarantees `decode_heads_shardable`."""
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    spec = P(None, None, "model", None)
    cspec = spec if layer is None else P(None, None, "model", None, None)
    quantized, staged = k_scales is not None, k_new is not None
    in_specs = [spec, cspec, cspec, P()]
    args = [q, k_cache, v_cache, lengths]
    if layer is not None:
        in_specs.append(P())
        args.append(jnp.asarray(layer, jnp.int32).reshape(1))
    if quantized:
        in_specs += [P(None, None, "model")] * 2
        args += [k_scales, v_scales]
    if staged:
        in_specs += [P(None, "model", None)] * 2
        args += [k_new, v_new]

    def body(q, kc, vc, ln, *rest):
        rest = list(rest)
        ly = None if layer is None else rest.pop(0)[0]
        ks, vs = (rest.pop(0), rest.pop(0)) if quantized else (None, None)
        kn, vn = (rest.pop(0), rest.pop(0)) if staged else (None, None)
        return decode_attention(q, kc, vc, ln, softmax_scale=softmax_scale,
                                block_k=block_k, k_scales=ks, v_scales=vs,
                                layer=ly, k_new=kn, v_new=vn)

    return kernel_shard_map(body, mesh, tuple(in_specs), spec)(*args)


def sharded_kv_write_dense(k_stack, v_stack, k_new, v_new, starts, mesh):
    """`kv_write_dense` with the stacks (L,B,Hkv,M,D) and the new tokens
    (L,B,Hkv,D) head-sharded over 'model': each shard writes its own heads
    in place; the cursors are replicated."""
    from deepspeed_tpu.ops.pallas.decode_attention import kv_write_dense
    sspec = P(None, None, "model", None, None)
    nspec = P(None, None, "model", None)
    return kernel_shard_map(kv_write_dense, mesh,
                            (sspec, sspec, nspec, nspec, P()),
                            (sspec, sspec))(k_stack, v_stack, k_new, v_new,
                                            starts)


def _paged_pool_operands(k_pool, v_pool, k_scales, v_scales, layer):
    """The stacked pools, their scales and the layer scalar as shard_map
    operands: KV heads over 'model', the layer replicated."""
    from deepspeed_tpu.ops.pallas.paged_attention import _stacked_pools
    k_pool, v_pool, k_scales, v_scales, layer = _stacked_pools(
        k_pool, v_pool, k_scales, v_scales, layer)
    pspec = P(None, "model", None, None, None)
    specs, args = [pspec, pspec, P()], [k_pool, v_pool, layer]
    if k_scales is not None:
        specs += [P(None, "model", None, None)] * 2
        args += [k_scales, v_scales]
    return specs, args


def sharded_paged_decode_attention(q, k_pool, v_pool, tables, lengths, mesh,
                                   softmax_scale: Optional[float] = None,
                                   k_new=None, v_new=None,
                                   window: Optional[int] = None,
                                   alibi=None,
                                   k_scales=None, v_scales=None, layer=None):
    """`paged_decode_attention` with q (B,1,H,D), pools ([L,]Hkv,NB,BS,D)
    and the (B,Hkv,D) staged token head-sharded over 'model'; tables,
    lengths and the layer scalar replicated. alibi slopes (H,) and the
    ([L,]Hkv,NB,BS) int8 scale leaves shard with the heads."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
    qspec = P(None, None, "model", None)
    quantized = k_scales is not None
    pool_specs, pool_args = _paged_pool_operands(k_pool, v_pool, k_scales,
                                                 v_scales, layer)
    in_specs = [qspec, P(), P()] + pool_specs
    args = [q, tables, lengths] + pool_args
    staged = k_new is not None
    if staged:
        in_specs += [P(None, "model", None)] * 2
        args += [k_new, v_new]
    has_alibi = alibi is not None
    if has_alibi:
        in_specs.append(P("model"))
        args.append(alibi)

    def body(q, tb, ln, kp, vp, ly, *rest):
        kn = vn = al = ks = vs = None
        rest = list(rest)
        if quantized:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        if staged:
            kn, vn = rest[0], rest[1]
            rest = rest[2:]
        if has_alibi:
            al = rest[0]
        return paged_decode_attention(q, kp, vp, tb, ln,
                                      softmax_scale=softmax_scale,
                                      k_new=kn, v_new=vn,
                                      window=window, alibi=al,
                                      k_scales=ks, v_scales=vs, layer=ly[0])

    return kernel_shard_map(body, mesh, tuple(in_specs), qspec)(*args)


def sharded_paged_prefill_attention(q, k_pool, v_pool, tables, starts, mesh,
                                    softmax_scale: Optional[float] = None,
                                    block_q: int = 256,
                                    window: Optional[int] = None,
                                    alibi=None,
                                    k_scales=None, v_scales=None, layer=None):
    """`paged_prefill_attention` head-sharded over 'model' (same layout
    contract as the decode wrapper; int8 scale leaves shard with the
    heads)."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_prefill_attention
    qspec = P(None, None, "model", None)
    quantized = k_scales is not None
    pool_specs, pool_args = _paged_pool_operands(k_pool, v_pool, k_scales,
                                                 v_scales, layer)
    in_specs = [qspec, P(), P()] + pool_specs
    args = [q, tables, starts] + pool_args
    has_alibi = alibi is not None
    if has_alibi:
        in_specs.append(P("model"))
        args.append(alibi)

    def body(q, tb, st, kp, vp, ly, *rest):
        rest = list(rest)
        ks = vs = None
        if quantized:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        al = rest[0] if has_alibi else None
        return paged_prefill_attention(q, kp, vp, tb, st,
                                       softmax_scale=softmax_scale,
                                       block_q=block_q, window=window,
                                       alibi=al, k_scales=ks, v_scales=vs,
                                       layer=ly[0])

    return kernel_shard_map(body, mesh, tuple(in_specs), qspec)(*args)


def sharded_paged_kv_write(k_pool, v_pool, k_new, v_new, tables, starts,
                           layer, mesh, k_scales=None, v_scales=None,
                           k_new_scales=None, v_new_scales=None):
    """`paged_kv_write` with the stacked pools (L,Hkv,NB,BS,D), their
    scales and the new tokens (NL,B,S,Hkv,D) head-sharded over 'model':
    each shard writes its own heads' blocks in place; tables, starts and
    the layer are replicated."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_kv_write
    pspec = P(None, "model", None, None, None)
    nspec = P(None, None, None, "model", None)
    in_specs = [pspec, pspec, nspec, nspec, P(), P(), P()]
    out_specs = [pspec, pspec]
    args = [k_pool, v_pool, k_new, v_new, tables, starts,
            jnp.asarray(layer, jnp.int32).reshape(1)]
    quantized = k_scales is not None
    if quantized:
        sspec = P(None, "model", None, None)
        in_specs += [sspec, sspec] + [P(None, None, None, "model")] * 2
        out_specs += [sspec, sspec]
        args += [k_scales, v_scales, k_new_scales, v_new_scales]

    def body(kp, vp, kn, vn, tb, st, ly, *scales):
        ks, vs, kns, vns = scales if quantized else (None,) * 4
        return paged_kv_write(kp, vp, kn, vn, tb, st, ly[0], k_scales=ks,
                              v_scales=vs, k_new_scales=kns,
                              v_new_scales=vns)[:len(out_specs)]

    out = kernel_shard_map(body, mesh, tuple(in_specs),
                           tuple(out_specs))(*args)
    return tuple(out) + (None,) * (4 - len(out))


# ---- training flash attention (batch over the data axes, heads over
# 'sequence'/'model') ----
#
# The chip's compiler refuses a bare Mosaic call in a partitioned program
# ("Mosaic kernels cannot be automatically partitioned. Please wrap the
# call in a shard_map") — so on ANY multi-device training mesh the flash
# kernel must sit in a manual region. Attention is independent per
# (batch row, head): both dims shard with no collective.


def flash_shard_specs(b: int, h: int, hkv: int):
    """How `flash_attention` rides the installed topology. Returns
    `(mesh, spec)`: `(None, None)` — call the bare kernel (single device,
    or already inside a region manual over every nontrivial axis);
    `(mesh, P(...))` — wrap it in a full-manual shard_map with this spec
    for q, k, v and the output; `(mesh, None)` — this mesh cannot carry
    the kernel (announced via `kernel_fallback`; the caller takes the XLA
    path)."""
    from deepspeed_tpu.utils.partitioning import (BATCH_AXES,
                                                  ambient_manual_mesh)
    mesh = _topology_mesh()
    nt = nontrivial_axes(mesh) if mesh is not None else {}
    _, manual = ambient_manual_mesh()  # axes an enclosing region took
    if not set(nt) - manual:
        return None, None
    if not sharded_kernels_supported():
        kernel_fallback("flash_attention", "sharded kernels disabled")
        return mesh, None
    if manual or "pipe" in nt:
        # a nested region over the remaining auto axes is not built here
        kernel_fallback("flash_attention",
                        f"inside a region manual over {sorted(manual)} of "
                        f"mesh axes {sorted(nt)}")
        return mesh, None
    batch = tuple(a for a in BATCH_AXES if a in nt)
    heads = tuple(a for a in ("sequence", "model") if a in nt)
    nb = math.prod(nt[a] for a in batch)
    nh = math.prod(nt[a] for a in heads)
    if b % nb or h % nh or hkv % nh:
        kernel_fallback("flash_attention",
                        f"batch {b} / heads (H={h}, Hkv={hkv}) don't divide "
                        f"mesh axes {nt}")
        return mesh, None
    return mesh, P(batch or None, None, heads or None, None)


def sharded_flash_attention(q, k, v, mesh, spec, causal: bool = True,
                            softmax_scale: Optional[float] = None):
    """`flash_attention` (custom-VJP, so differentiable through the region)
    with q (B,S,H,D) and k/v (B,S,Hkv,D) sharded by `spec` from
    `flash_shard_specs`."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def body(q, k, v):
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale)

    return kernel_shard_map(body, mesh, (spec, spec, spec), spec)(q, k, v)
