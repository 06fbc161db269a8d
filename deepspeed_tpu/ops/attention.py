"""Attention ops.

The compute core that the reference implements as CUDA/Triton kernels
(`csrc/transformer/inference/csrc/softmax.cu`, flash-attn links in
`inference/v2/kernels/ragged_ops/blocked_flash`). Dispatch order:
Pallas flash attention on TPU (ops/pallas/flash_attention.py), XLA reference
implementation elsewhere. Supports MHA/GQA/MQA and causal masking.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator import on_tpu


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) → (B, S, Hkv*n_rep, D) for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (reference softmax.cu's alibi path /
    transformers BloomModel.build_alibi_tensor): geometric sequence from
    2^(-8/n) for the nearest power of two, interleaved extras beyond it."""
    import math
    p2 = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(p2) - 3)))
    slopes = [base ** (i + 1) for i in range(p2)]
    if p2 < n_heads:
        extra = 2.0 ** (-(2.0 ** -(math.log2(2 * p2) - 3)))
        slopes += [extra ** (2 * i + 1) for i in range(n_heads - p2)]
    return jnp.asarray(slopes, jnp.float32)


def reference_attention(q, k, v, causal: bool = True,
                        segment_mask: Optional[jnp.ndarray] = None,
                        softmax_scale: Optional[float] = None,
                        window: Optional[int] = None,
                        alibi: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pure-XLA softmax attention. q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D).
    `window` bands the causal mask to the last `window` keys (Mistral
    sliding-window attention). `alibi` is a (H,) slopes vector: the bias
    slopes[h]*key_position is added to the logits — shift-invariance of the
    per-row softmax makes that equivalent to slopes[h]*(k−q), so the same
    form serves full sequences and KV-cache decode."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sk = k.shape[1]
    if alibi is not None:
        logits = logits + alibi[None, :, None, None] * \
            jnp.arange(sk, dtype=jnp.float32)[None, None, None, :]
    assert causal or window is None, "window requires causal attention"
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = ki <= qi
        if window is not None:
            keep = jnp.logical_and(keep, ki > qi - window)
        logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    if segment_mask is not None:
        logits = jnp.where(segment_mask[:, None, :, :] if segment_mask.ndim == 3
                           else segment_mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(q, k, v, causal: bool = True,
                        softmax_scale: Optional[float] = None,
                        block_q: int = 1024, block_k: int = 1024,
                        window: Optional[int] = None,
                        alibi: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Memory-efficient attention as pure XLA: double `lax.scan` over q/kv
    blocks with online-softmax state. O(block_q·block_k) live logits instead
    of O(Sq·Sk) — the compute core of the FPDT/long-context role (reference
    `sequence/fpdt_layer.py:971`, `update_out_and_lse:58`) and the portable
    fallback where the Pallas flash kernel can't run (CPU tests, odd shapes).
    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) → (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    hkv, sk = k.shape[2], k.shape[1]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    block_q = min(block_q, sq)
    while sq % block_q:
        block_q -= 1
    block_k = min(block_k, sk)
    while sk % block_k:
        block_k -= 1
    nq, nk = sq // block_q, sk // block_k
    assert causal or window is None, "window requires causal attention"
    offset = sk - sq  # bottom-right-aligned causal (decode-friendly)

    qt = jnp.swapaxes(q, 1, 2).reshape(b, h, nq, block_q, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b, h, nk, block_k, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b, h, nk, block_k, d)

    def q_block(carry, qi):
        q_blk = qt[:, :, qi] * scale  # (b, h, bq, d)

        def kv_block(state, ki):
            m, l, acc = state
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, kt[:, :, ki],
                           preferred_element_type=jnp.float32)
            if alibi is not None:  # per-key bias, added per block
                kpos = ki * block_k + jnp.arange(block_k, dtype=jnp.float32)
                s = s + alibi[None, :, None, None] * kpos[None, None, None, :]
            if causal:
                rows = offset + qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                cols = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                keep = cols <= rows
                if window is not None:
                    keep = jnp.logical_and(keep, cols > rows - window)
                s = jnp.where(keep, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # fully-masked rows: keep m finite so exp() stays well-defined
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe)
            alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vt.dtype), vt[:, :, ki],
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((b, h, block_q, 1), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, block_q, 1), jnp.float32),
                jnp.zeros((b, h, block_q, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_block, init, jnp.arange(nk))
        out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
        return carry, out

    body = jax.checkpoint(q_block, prevent_cse=False)
    _, blocks = jax.lax.scan(body, None, jnp.arange(nq))  # (nq, b, h, bq, d)
    out = jnp.moveaxis(blocks, 0, 2).reshape(b, h, sq, d)
    return jnp.swapaxes(out, 1, 2)


def banded_attention(q, k, v, window: int,
                     softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """Causal attention under a sliding `window` (a query at t sees keys
    t - window + 1 .. t) over whole sequences, as pure XLA, for sequences of
    several windows: queries go a block of `window` at a time and meet the
    two blocks of keys that hold their band, so the work is 2 x S x window
    where `blockwise_attention` scans all S x S / 2 block pairs and masks
    most of them (PERF.md, PR 45: 10.6 ms against 30.4 at 8 x 2048 tokens,
    40 heads of 128 on 10, window 512). q (B, S, H, D), k/v (B, S, Hkv, D),
    any S; grouped, so no head is repeated. Live logits are
    (B, H, window, 2 window) float32."""
    b, s, h, d = q.shape
    g = k.shape[2]
    w = window
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    pad = -s % w
    # keys: one window of nothing in front (block 0's "block before"), and
    # the tail padded like the queries'; padded queries' rows are cut off
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k, v = (jnp.pad(t, ((0, 0), (w, pad), (0, 0), (0, 0))) for t in (k, v))
    qi = jnp.arange(w)[:, None]
    kj = jnp.arange(2 * w)[None, :]
    band = (kj <= qi + w) & (kj > qi)       # key block starts a window back

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * w, w, 1).reshape(
            b, w, g, h // g, d)
        kb, vb = (jax.lax.dynamic_slice_in_dim(t, i * w, 2 * w, 1)
                  for t in (k, v))
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                            preferred_element_type=jnp.float32) * scale
        keep = band & (kj + i * w >= w)     # nothing before position 0
        probs = jax.nn.softmax(jnp.where(
            keep, logits, jnp.finfo(jnp.float32).min), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(q.dtype),
                          vb).reshape(b, w, h, d)

    out = jax.lax.map(block, jnp.arange((s + pad) // w))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, h, d)[:, :s]


def _use_pallas() -> bool:
    if os.environ.get("DS_TPU_DISABLE_PALLAS"):
        return False
    return on_tpu()


def _decode_tp_mesh(h: int, hkv: int, kernel: str):
    """Mesh routing for the head-sharded decode wrappers
    (ops/pallas/sharded.py). Returns (mesh, fallback):

      (mesh, False) — installed topology is pure-'model' TP and both head
                      counts divide: ride the shard_map wrapper.
      (None, False) — single-device topology (or none): bare kernel,
                      pre-r7 behavior unchanged.
      (None, True)  — topology is multi-device but the wrapper can't cover
                      it: the caller must take the masked XLA path (a bare
                      pallas_call would make GSPMD gather the whole cache
                      onto every device). Announced via kernel_fallback.
    """
    from deepspeed_tpu.ops.pallas.sharded import (
        _topology_mesh, decode_heads_shardable, kernel_fallback,
        nontrivial_axes, serving_mesh)
    mesh, tp = serving_mesh("model")
    if mesh is not None and decode_heads_shardable(h, hkv, tp):
        return mesh, False
    topo = _topology_mesh()
    nt = nontrivial_axes(topo) if topo is not None else {}
    if not nt:
        return None, False
    if mesh is None:
        kernel_fallback(kernel, f"mesh axes {nt} are not pure 'model' "
                                "tensor parallelism")
    else:
        kernel_fallback(kernel, f"heads (H={h}, Hkv={hkv}) don't divide "
                                f"model={tp}")
    return None, True


def attention(q, k, v, causal: bool = True, softmax_scale: Optional[float] = None,
              impl: str = "auto", window: Optional[int] = None,
              alibi: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Flash attention (Pallas) on TPU; XLA reference elsewhere; `blockwise`
    (or long sequences off-TPU) → memory-efficient XLA online-softmax.
    `window` (sliding-window attention) routes to the masked XLA paths,
    which have a backward: the Pallas kernel's band is FORWARD only
    (`flash_attention(window=)`, a prefill's: `banded_prefill` below), and
    `impl='pallas'` with a window is that kernel."""
    if alibi is not None:
        # positional bias lives in the logits — masked XLA paths only
        if impl == "pallas":
            raise NotImplementedError("the Pallas flash kernel has no alibi")
        if impl == "blockwise" or q.shape[1] * k.shape[1] > 4096 * 4096:
            return blockwise_attention(q, k, v, causal=causal,
                                       softmax_scale=softmax_scale,
                                       window=window, alibi=alibi)
        return reference_attention(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale,
                                   window=window, alibi=alibi)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale, window=window)
    if impl == "pallas" and window is not None:
        # forward only: differentiated, it raises by name
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        assert causal, "window requires causal attention"
        return flash_attention(q, k, v, causal=True,
                               softmax_scale=softmax_scale, window=window)

    def xla_attention():
        if q.shape[1] * k.shape[1] > 4096 * 4096:
            # (B,H,Sq,Sk) logits would dominate memory — go blockwise.
            return blockwise_attention(q, k, v, causal=causal,
                                       softmax_scale=softmax_scale,
                                       window=window)
        return reference_attention(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale, window=window)

    if impl == "reference" or (impl == "auto" and not _use_pallas()) \
            or window is not None:
        return xla_attention()
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.sharded import (flash_shard_specs,
                                                  sharded_flash_attention)
    mesh, spec = flash_shard_specs(q.shape[0], q.shape[2], k.shape[2])
    if mesh is None:
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale)
    if spec is None:  # announced by kernel_fallback
        return xla_attention()
    return sharded_flash_attention(q, k, v, mesh, spec, causal=causal,
                                   softmax_scale=softmax_scale)


def banded_prefill(q, k, v, window: int,
                   softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """Causal attention under a sliding `window` over WHOLE sequences,
    FORWARD only: what a prefill's window layers run. q (B, S, H, D), k/v
    (B, S, Hkv, D). On the chip, in a one-device program and where the
    tiling takes the shapes, the banded flash kernel (`flash_attention(
    window=)`, traced as `self_attn_flash_fwd_band`: only the band's key
    blocks are visited); elsewhere XLA's band (`banded_attention`). A window
    that covers the sequence bands nothing: the plain causal dispatch."""
    s, d = q.shape[1], q.shape[-1]
    if window >= s:
        return attention(q, k, v, causal=True, softmax_scale=softmax_scale)
    if s % 128 == 0 and d % 128 == 0:
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        if _one_device_kernel(fa.BAND_NAME):
            return fa.flash_attention(q, k, v, causal=True,
                                      softmax_scale=softmax_scale,
                                      window=window)
    return banded_attention(q, k, v, window, softmax_scale)


def chunk_prefill(q, k_cache, v_cache, row, start,
                  softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """A prefill CHUNK's causal attention over one row of the stacked dense
    cache, FORWARD only: what a full softmax layer runs where the prefill
    walks a row a chunk at a time (`models/hybrid.prefill_walk`). q (C, H,
    D), the queries of positions `start .. start + C - 1` of sequence `row`
    (both may be traced); k_cache / v_cache `DenseLayer` views with `layer`
    set, which hold the row's keys and values up to the chunk's end. Query i
    sees slots 0 .. start + i. On the chip in a one-device program the flash
    forward reads the stacks in place (`flash_prefill_chunk`); elsewhere
    `chunk_prefill_reference`."""
    c, _, d = q.shape
    if c % 128 == 0 and d % 128 == 0 and k_cache.stack.shape[3] % 128 == 0:
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        if _one_device_kernel(fa.FWD_NAME):
            return fa.flash_prefill_chunk(
                q, k_cache.stack, v_cache.stack, k_cache.layer, row, start,
                softmax_scale)
    return chunk_prefill_reference(q, k_cache, v_cache, row, start,
                                   softmax_scale)


def chunk_prefill_reference(q, k_cache, v_cache, row, start,
                            softmax_scale: Optional[float] = None):
    """`chunk_prefill` in plain `jax.numpy`: the row cut out of the stacks
    and masked."""
    c, h, d = q.shape
    m = k_cache.stack.shape[3]
    k, v = (jax.lax.dynamic_slice(
        t.stack, (t.layer, row, 0, 0, 0), (1, 1) + t.stack.shape[2:])[0, 0]
        for t in (k_cache, v_cache))                        # (Hkv, M, D)
    hkv = k.shape[0]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    qg = q.reshape(c, hkv, h // hkv, d)
    logits = jnp.einsum("cgrd,gmd->grcm", qg, k,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(m)[None, :] <= start + jnp.arange(c)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("grcm,gmd->cgrd", probs.astype(v.dtype),
                      v).reshape(c, h, d)


def _assert_prefix_mask(mask, index, m: int, s: int = 1):
    """Debug-mode contract check for the Pallas decode dispatch: `mask` must
    be the prefix mask implied by `index` (slots 0..index valid). Enabled by
    DS_TPU_CHECK_MASKS=1 (costs one comparison reduce per call) — the guard
    for callers handing a non-prefix mask (left-padded batches etc.) to the
    kernel path, which would otherwise silently mis-attend. Best-effort
    surfacing: the raise happens inside a debug callback, so under async
    dispatch it may arrive after the offending step (still attributed by
    the message) — a debugging aid, not a synchronous precondition."""
    if not os.environ.get("DS_TPU_CHECK_MASKS") or mask is None:
        return
    pos = index[:, None] + jnp.arange(s)[None, :]            # (B, S)
    expect = jnp.arange(m)[None, None, :] <= pos[:, :, None]

    def _host_assert(ok):
        if not bool(ok):
            raise ValueError(
                "cached_attention: mask is not the prefix mask implied by "
                "index — the Pallas decode kernel would mis-attend; pass "
                "impl='reference' or thread window= instead")
    jax.debug.callback(_host_assert, jnp.all(mask == expect))


def cached_attention(q, k_cache, v_cache, index, mask, impl: str = "auto",
                     window: Optional[int] = None,
                     alibi: Optional[jnp.ndarray] = None):
    """Attention of new tokens against the static KV cache (the
    softmax_context slot). Single-token decode on TPU routes to a Pallas
    decode kernel (skips blocks past each row's cursor); prefill and
    off-TPU use the masked XLA path.

    q: (B, S, H, D); caches (B, M, Hkv, D) dense arrays, `kv_cache.DenseLayer`
    views of the stacked dense cache (`_stacked_dense_attention`) OR
    `kv_cache.PagedLayer` views (block-paged pool + tables — the FastGen
    layout; with `layer` set the pool is the whole stacked one and the
    kernels fetch this layer's blocks out of it by index); index (B,) pre-insert cursors; mask (B, S, M) validity over
    logical positions.

    NOTE: the Pallas decode branches assume a PREFIX mask — slots 0..index
    valid, exactly what `kv_cache.decode_mask(positions)` produces (every
    in-tree caller). A sliding window over a FULL-LENGTH cache puts holes
    in the mask: pass it as `window` and the dispatcher keeps such calls on
    the XLA path that honors `mask` elementwise. A window layer whose cache
    is a RING (`DenseLayer.ring`: `KVCache.create_stacked(ring=True)`) has
    no holes: its slots hold the window and nothing else, and a single-token
    call reads it with the dense kernel (`self_attn_ring_decode`,
    `_stacked_dense_attention`); `mask` and `window` are not consulted
    there (callers with other non-prefix masks —
    left-padding etc. — must force impl='reference'; DS_TPU_CHECK_MASKS=1
    verifies the contract at runtime via a best-effort debug callback —
    see `_assert_prefix_mask` for its async-dispatch caveats).

    Dispatch (v5e, chained-loop measured at B=32, M=8192): the HEAD-PACKED
    Pallas kernel rides the whole GQA group per tile and beats the fused
    XLA path 3.3-3.6x for n_rep>=4 (2.7ms vs 8.7ms at n_rep=8) — 'auto'
    selects it there. MHA/small groups keep the XLA path (its (1..2, D)
    query slivers lose to the batched masked matmul, 4.7ms vs 3.4ms at the
    470m shape); impl='decode_pallas' forces the kernel. The PAGED layout
    always takes its kernel for decode on TPU — the XLA fallback would
    first gather the logical view, forfeiting the bandwidth the paging
    buys.

    Multi-device (r7): on a pure-'model' TP topology with H and Hkv both
    divisible by tp, every kernel branch rides its head-sharded shard_map
    wrapper (ops/pallas/sharded.py) — per-shard heads, no collectives.
    Any other nontrivial mesh takes the masked XLA path (GSPMD would
    gather the whole cache around a bare pallas_call), announced via
    `kernel_fallback` — even when impl forces the kernel.

    int8-at-rest caches (PagedLayer.scales / QuantizedKVLayer) keep their
    int8 form on every kernel branch — the per-token scales ride beside
    the pool and are folded in-register (docs/kv_cache.md); only the XLA
    fallback materializes a dequantized dense view."""
    from deepspeed_tpu.inference.kv_cache import (
        DenseLayer, PagedLayer, QuantizedKVLayer, dequantize_kv,
        gather_paged_layer)
    if isinstance(k_cache, DenseLayer):
        return _stacked_dense_attention(q, k_cache, v_cache, index, mask,
                                        impl, window, alibi)
    if isinstance(k_cache, PagedLayer):
        # staged decode (kv_cache.PagedLayer.stage): the new token's K/V is
        # in the stage buffer, not the pool, until the engine's apply_stage
        staged = k_cache.stage is not None and q.shape[1] == 1
        # alibi kernels validated on-chip at d>=128, block_size>=128 (real
        # bloom-7b shapes); Mosaic rejects some tiny-tile layouts below
        # that (bloom-tiny) — those sizes take the gather fallback, which
        # is cheap at tiny scale anyway
        alibi_kernel_ok = alibi is None or (
            q.shape[-1] >= 128 and k_cache.pool.shape[-2] >= 128)
        use_kernel = _use_pallas() and impl != "reference" and alibi_kernel_ok
        mesh = None
        if use_kernel:
            mesh, tp_fallback = _decode_tp_mesh(
                q.shape[2], k_cache.pool.shape[-4],
                "paged_decode_attention" if q.shape[1] == 1
                else "paged_prefill_attention")
            use_kernel = not tp_fallback
        if use_kernel:
            # sliding window and alibi ride the kernels too (r4): the r3
            # dispatcher fell back to the dense-view gather for bloom/
            # mistral-family models, forfeiting paging entirely
            if window is None:  # banded masks aren't prefix masks
                m_cap = k_cache.tables.shape[1] * k_cache.pool.shape[-2]
                _assert_prefix_mask(mask, index, m_cap, q.shape[1])
            if q.shape[1] == 1:
                if mesh is not None:
                    from deepspeed_tpu.ops.pallas.sharded import (
                        sharded_paged_decode_attention)
                    return sharded_paged_decode_attention(
                        q, k_cache.pool, v_cache.pool, k_cache.tables,
                        index + 1, mesh,
                        k_new=k_cache.stage if staged else None,
                        v_new=v_cache.stage if staged else None,
                        window=window, alibi=alibi,
                        k_scales=k_cache.scales, v_scales=v_cache.scales,
                        layer=k_cache.layer)
                from deepspeed_tpu.ops.pallas.paged_attention import (
                    paged_decode_attention)
                return paged_decode_attention(
                    q, k_cache.pool, v_cache.pool, k_cache.tables, index + 1,
                    k_new=k_cache.stage if staged else None,
                    v_new=v_cache.stage if staged else None,
                    window=window, alibi=alibi,
                    k_scales=k_cache.scales, v_scales=v_cache.scales,
                    layer=k_cache.layer)
            # chunked prefill rides the paged flash kernel — the r3 XLA
            # fallback (token-gather + f32 (B,H,S,M) logits) measured
            # ~140 ms/layer at serving shape and WAS the FastGen prefill
            if mesh is not None:
                from deepspeed_tpu.ops.pallas.sharded import (
                    sharded_paged_prefill_attention)
                return sharded_paged_prefill_attention(
                    q, k_cache.pool, v_cache.pool, k_cache.tables, index,
                    mesh, window=window, alibi=alibi,
                    k_scales=k_cache.scales, v_scales=v_cache.scales,
                    layer=k_cache.layer)
            from deepspeed_tpu.ops.pallas.paged_attention import (
                paged_prefill_attention)
            return paged_prefill_attention(q, k_cache.pool, v_cache.pool,
                                           k_cache.tables, index,
                                           window=window, alibi=alibi,
                                           k_scales=k_cache.scales,
                                           v_scales=v_cache.scales,
                                           layer=k_cache.layer)
        # XLA fallback: materialize the dense logical view, then the masked
        # path (CPU tests, alibi/window models). A staged token overlays
        # its row's cursor slot (the pool copy there is stale). int8 pools
        # dequantize into the view at the compute dtype.
        dense_k = gather_paged_layer(k_cache, dtype=q.dtype)
        dense_v = gather_paged_layer(v_cache, dtype=q.dtype)
        if staged:
            rows = jnp.arange(q.shape[0])
            dense_k = dense_k.at[rows, index].set(
                k_cache.stage.astype(dense_k.dtype), mode="drop")
            dense_v = dense_v.at[rows, index].set(
                v_cache.stage.astype(dense_v.dtype), mode="drop")
        return reference_attention(q, dense_k, dense_v, causal=False,
                                   segment_mask=mask, alibi=alibi)
    quant = isinstance(k_cache, QuantizedKVLayer)

    def _dense_view(layer):
        # the only place an int8 dense cache materializes in full precision
        # (the masked-XLA fallback); kernels fold the scales in-register
        return dequantize_kv(layer.data, layer.scales, q.dtype)

    if alibi is not None:
        if quant:
            return reference_attention(q, _dense_view(k_cache),
                                       _dense_view(v_cache), causal=False,
                                       segment_mask=mask, alibi=alibi)
        return reference_attention(q, k_cache, v_cache, causal=False,
                                   segment_mask=mask, alibi=alibi)
    kernel, mesh = dense_decode_route(impl, window, q.shape[2],
                                      k_cache.shape[2], q.shape[1])
    if kernel:
        _assert_prefix_mask(mask, index, k_cache.shape[1])
        kd = k_cache.data if quant else k_cache
        vd = v_cache.data if quant else v_cache
        ks = k_cache.scales if quant else None
        vs = v_cache.scales if quant else None
        if mesh is not None:
            from deepspeed_tpu.ops.pallas.sharded import (
                sharded_decode_attention)
            return sharded_decode_attention(q, kd, vd, index + 1, mesh,
                                            k_scales=ks, v_scales=vs)
        from deepspeed_tpu.ops.pallas.decode_attention import (
            decode_attention)
        return decode_attention(q, kd, vd, index + 1,
                                k_scales=ks, v_scales=vs)
    if quant:
        return reference_attention(q, _dense_view(k_cache),
                                   _dense_view(v_cache), causal=False,
                                   segment_mask=mask)
    return reference_attention(q, k_cache, v_cache, causal=False,
                               segment_mask=mask)


def _decode_kernel_wanted(impl: str, window, n_rep: int) -> bool:
    """The dense decode kernel's dispatch rule for a single-token call
    (`cached_attention` tells where the crossover was measured): a forced
    impl, or 'auto' from a GQA group of 4 up. `window` is a band over a
    FULL-LENGTH cache, whose live slots are no prefix: never the kernel,
    which masks by a count of live slots. A window layer that keeps a RING
    is not such a call: its reader passes `window=None` (every slot of a
    ring is live up to a count; `_stacked_dense_attention`)."""
    if impl == "decode_pallas" and window is not None:
        raise NotImplementedError(
            "the Pallas decode kernel masks by a COUNT of live slots: a "
            "sliding window over a full-length cache needs the XLA path "
            "(impl='auto'/'reference'), or a ring cache "
            "(KVCache.create_stacked(ring=True)), which the kernel reads")
    # impl='pallas' is the shared attn_impl knob (training flash kernel) —
    # for a windowed decode it degrades to the masked XLA path instead of
    # raising, so one config value can serve both phases
    # The n_rep>=4 auto-dispatch crossover was measured on v5e (CLAUDE.md
    # perf ledger); other TPU generations can move it —
    # DS_TPU_DECODE_NREP_THRESHOLD overrides without a code change
    # (re-measure with a chained fori_loop, not repeated same-input calls).
    thresh = int(os.environ.get("DS_TPU_DECODE_NREP_THRESHOLD", "4"))
    return window is None and _use_pallas() and (
        impl in ("decode_pallas", "pallas")
        or (impl == "auto" and n_rep >= thresh))


def ring_live(index, m: int):
    """(the COUNT of live slots, the slot the staged token stands in) of a
    ring of `m` slots whose rows have cached `index` positions before this
    step's token (docs/kv_cache.md, "A ring's contract"): all a softmax
    reader needs of a ring."""
    return jnp.minimum(index + 1, m), index % m


def dense_decode_route(impl: str, window, h: int, hkv: int, tokens: int = 1):
    """(whether a call of `tokens` new tokens a row over a dense cache runs
    the Pallas decode kernel here, the mesh to shard it over or None): the
    dispatch rule and the mesh routing in one answer, for both views of the
    cache and for the v1 engine's count of what the kernel fetches."""
    if not (_decode_kernel_wanted(impl, window, h // hkv) and tokens == 1):
        return False, None
    mesh, tp_fallback = _decode_tp_mesh(h, hkv, "decode_attention")
    return not tp_fallback, mesh


def _stacked_dense_attention(q, k_cache, v_cache, index, mask, impl, window,
                             alibi):
    """`cached_attention` over `DenseLayer` views: layer `k_cache.layer` of
    the stacked (L, B, Hkv, M, D) cache, by index. Single-token decode under
    the dense kernel's dispatch rule hands the kernel the WHOLE stack and
    the layer (and the staged token, if the layer staged one); everything
    else cuts this layer's K/V out (one layer's worth, a transient) and
    attends under `mask` in the stack's own axis order.

    A RING's views (`DenseLayer.ring`; one staged token a row, no alibi):
    `index` gives the count of live slots, `min(index + 1, M)`, and the
    staged token's slot, `index mod M`; `mask` and `window` are not
    consulted (the ring holds the window and nothing else, and a key
    rotated before it was cached needs no position). The kernel under its
    ring name where the dense kernel would run, else the same count as a
    mask over the slots."""
    b, s, h, d = q.shape
    hkv, m = k_cache.stack.shape[2], k_cache.stack.shape[3]
    n_rep = h // hkv
    staged = k_cache.stage is not None
    ring = k_cache.ring
    at = index
    if ring:
        if s != 1 or not staged or alibi is not None:
            raise NotImplementedError(
                "a ring is read one staged token a row (a prefill attends "
                "its own tokens: ops.attention.banded_prefill)")
        window = None
        count, at = ring_live(index, m)
        mask = (jnp.arange(m) < count[:, None])[:, None]
    kernel, mesh = dense_decode_route(impl, window, h, hkv, s)
    if kernel and alibi is None and (mesh is None or not ring):
        kw = dict(layer=k_cache.layer, k_new=k_cache.stage,
                  v_new=v_cache.stage)
        from deepspeed_tpu.ops.pallas.decode_attention import (
            decode_attention)
        if ring:
            return decode_attention(q, k_cache.stack, v_cache.stack, count,
                                    slots=at, **kw)
        _assert_prefix_mask(mask, index, m)
        if mesh is not None:
            from deepspeed_tpu.ops.pallas.sharded import (
                sharded_decode_attention)
            return sharded_decode_attention(
                q, k_cache.stack, v_cache.stack, index + 1, mesh, **kw)
        return decode_attention(q, k_cache.stack, v_cache.stack,
                                index + 1, **kw)
    k, v = (jax.lax.dynamic_index_in_dim(c.stack, c.layer, 0, keepdims=False)
            for c in (k_cache, v_cache))                     # (B, Hkv, M, D)
    if staged:  # the staged token overlays its row's slot (its cursor's)
        rows = jnp.arange(b)
        k = k.at[rows, :, at].set(k_cache.stage, mode="drop")
        v = v.at[rows, :, at].set(v_cache.stage, mode="drop")
    # grouped, so no head is repeated: head g*n_rep+r is member r of group g
    logits = jnp.einsum("bqgrd,bgkd->bgrqk", q.reshape(b, s, hkv, n_rep, d),
                        k).astype(jnp.float32) * (1.0 / (d ** 0.5))
    if alibi is not None:
        logits = logits + alibi.reshape(hkv, n_rep)[None, :, :, None, None] \
            * jnp.arange(m, dtype=jnp.float32)
    logits = jnp.where(mask[:, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bgkd->bqgrd", probs, v).reshape(b, s, h, d)


def _one_device_kernel(name: str) -> bool:
    """Whether a bare Pallas kernel may run here: on the chip, in a program
    that is one device's (a bare Mosaic call cannot be partitioned; a mesh
    with a nontrivial axis is announced and takes the `jax.numpy` path)."""
    if not _use_pallas():
        return False
    from deepspeed_tpu.ops.pallas.sharded import (_topology_mesh,
                                                  kernel_fallback,
                                                  nontrivial_axes)
    topo = _topology_mesh()
    if topo is not None and nontrivial_axes(topo):
        kernel_fallback(name, f"mesh axes {nontrivial_axes(topo)}: the "
                              "kernel is one device's")
        return False
    return True


def diff_decode(q, k_cache, v_cache, lengths, lam, softmax_scale: float,
                eps: float, k_new=None, v_new=None, slots=None,
                ring: bool = False):
    """One decode step of differential attention over a stacked cache of
    paired heads (`ops/pallas/diff_attention.py` has the layout): q
    (B, G, 2r, W), `k_cache`/`v_cache` `DenseLayer` views of the
    (L, B, G, M, W) stacks, `lengths` (B,) valid slots, and the row's staged
    token `k_new`/`v_new` (B, G, W) standing in slot `slots[b]`. Returns
    `RMSNorm(a1 - lam a2)` (B, G, r, W) float32, without a weight.

    The Pallas kernel on the chip, where a pair is whole lanes and the
    program is one device's (a bare Mosaic call cannot be partitioned);
    elsewhere the same in plain `jax.numpy`."""
    from deepspeed_tpu.ops.pallas import diff_attention as da
    kernel = q.shape[-1] % 128 == 0 and _one_device_kernel(
        "diff_decode_attention")
    fn = da.diff_decode_attention if kernel \
        else da.diff_decode_attention_reference
    return fn(q, k_cache.stack, v_cache.stack, k_cache.layer, lengths, lam,
              softmax_scale, eps, k_new=k_new, v_new=v_new, slots=slots,
              ring=ring)


def latent_decode(q_lat, q_rope, latent, lengths, softmax_scale: float,
                  new=None, slots=None):
    """One decode step of latent attention (MLA) in its ABSORBED form over a
    stacked latent cache (`ops/pallas/mla.py` has the layout): q_lat (B, H,
    rank), the queries' nope parts taken through the key half of the
    up-projection, q_rope (B, H, rope), `latent` a `DenseLayer` view of the
    (L, B, 1, M, rank + rope) stack, `lengths` (B,) valid slots, and the
    row's staged token `new` (B, rank + rope) standing in slot `slots[b]`.
    Returns the weighted sum of the cached LATENTS (B, H, rank) float32.

    The Pallas kernel on the chip in a one-device program; elsewhere the
    same in plain `jax.numpy`."""
    from deepspeed_tpu.ops.pallas import mla
    fn = mla.mla_latent_decode if _one_device_kernel(mla.KERNEL_NAME) \
        else mla.mla_latent_decode_reference
    return fn(q_lat, q_rope, latent.stack, latent.layer, lengths,
              softmax_scale, new=new, slots=slots)


def kda_update(state, layer, q, k, v, g, beta):
    """One decode step of the gated delta rule on layer `layer` of the
    stacked float32 state (`ops/pallas/kda.py` has the layout and the
    operands): `(o (B, H, dv) float32, state)`; `g` (B, H, dk) is a decay a
    channel, (B, H) a decay a HEAD (`gdn_state_update`). The Pallas kernel,
    one read and one write of the layer's state in place, on the chip in a
    one-device program; elsewhere the same in plain `jax.numpy`."""
    from deepspeed_tpu.ops.pallas import kda
    name = kda.HEAD_DECAY_NAME if g.ndim == 2 else kda.KERNEL_NAME
    fn = kda.kda_state_update if _one_device_kernel(name) \
        else kda.kda_state_update_reference
    return fn(state, layer, q, k, v, g, beta)


def sparse_select(q_index, w, index_keys, lengths, topk: int, new):
    """A decode step's learned choice of cached positions
    (`ops/pallas/sparse_select.py` has the scores and the layout): q_index
    (B, Hi, Di), w (B, Hi), `index_keys` a `DenseLayer` view of the (L, B, 1,
    M, Di) stack, `lengths` (B,) live slots whose last is the step's own
    token, its index key `new` (B, Di) staged. Returns the bias (B, M)
    float32, 0 at the `min(topk, length)` slots of largest score: the set
    `jax.lax.top_k` gives, on both paths; and (B,) int32, the slots kept a
    row, counted where the bias is written.

    The Pallas kernel on the chip in a one-device program; elsewhere the
    same in plain `jax.numpy`."""
    from deepspeed_tpu.ops.pallas import sparse_select as ss
    fn = ss.sparse_index_select if _one_device_kernel(ss.SELECT_NAME) \
        else ss.sparse_index_select_reference
    with jax.named_scope("choose"):   # for the program map
        return fn(q_index, w, index_keys.stack, index_keys.layer, lengths,
                  topk, new)


def sparse_decode(q, k_cache, v_cache, lengths, bias, softmax_scale: float,
                  k_new, v_new):
    """One decode step of attention over the CHOSEN slots of the stacked
    dense cache: q (B, H, D), `k_cache`/`v_cache` `DenseLayer` views of the
    (L, B, Hkv, M, D) stacks, the step's own token staged as `k_new`/`v_new`
    (B, Hkv, D) in slot `lengths[b] - 1`, `bias` (B, M) from `sparse_select`.
    Returns (B, H, D). Kernel and plain form as `sparse_select`."""
    from deepspeed_tpu.ops.pallas import sparse_select as ss
    fn = ss.sparse_attn_decode if _one_device_kernel(ss.DECODE_NAME) \
        else ss.sparse_attn_decode_reference
    return fn(q, k_cache.stack, v_cache.stack, k_cache.layer, lengths, bias,
              softmax_scale, k_new, v_new)


def sparse_prefill(q, q_index, w, k_cache, v_cache, index_keys, row, start,
                   topk: int, softmax_scale: float):
    """A chunk of ONE sequence's queries (q (C, H, D), q_index (C, Hi, Di),
    w (C, Hi), positions `start ..`) against sequence `row`'s slabs of the
    stacked caches, which already hold the chunk: each query's choice of
    `topk` positions up to its own, and attention over them. Returns (C, H,
    D) and (C,) int32, the slots each query kept. The kernels where the
    chip's tiling takes the shapes (whole lane tiles of slots and of
    queries: `models/hybrid.prefill_chunks` cuts every prompt of 128 tokens
    or more into such chunks), else the plain form."""
    from deepspeed_tpu.ops.pallas import sparse_select as ss
    aligned = k_cache.stack.shape[3] % 128 == 0 and q.shape[0] % 128 == 0 \
        and q.shape[-1] % 128 == 0
    fn = ss.sparse_attn_prefill if aligned and _one_device_kernel(
        ss.PREFILL_NAME) else ss.sparse_attn_prefill_reference
    return fn(q, q_index, w, k_cache.stack, v_cache.stack, index_keys.stack,
              k_cache.layer, row, start, topk, softmax_scale)


def latent_sparse_decode(q_lat, q_rope, latent, lengths, bias, kept,
                         topk: int, softmax_scale: float, new):
    """One decode step of ABSORBED latent attention over the rows a learned
    choice kept (`ops/pallas/mla_sparse.py`): q_lat (B, H, rank), q_rope (B,
    H, rope), `latent` a `DenseLayer` view of the (L, B, 1, M, rank + rope)
    stack, `lengths` (B,) live slots whose last is the step's own token,
    staged as `new` (B, rank + rope); `bias` (B, M) and `kept` (B,) from
    `sparse_select`. Returns the weighted sum of the chosen LATENTS (B, H,
    rank) float32. On the chip in a one-device program the kernel, over the
    chosen rows GATHERED where the choice drops any (`topk` under the
    slots); elsewhere the same in plain `jax.numpy`."""
    from deepspeed_tpu.ops.pallas import mla_sparse as ms
    if not _one_device_kernel(ms.DECODE_NAME):
        return ms.mla_sparse_decode_reference(
            q_lat, q_rope, latent.stack, latent.layer, lengths, bias,
            softmax_scale, new)
    if ms.DECODE_GATHERS and topk < latent.stack.shape[3]:
        return ms.mla_sparse_decode_gathered(
            q_lat, q_rope, latent.stack, latent.layer, lengths, bias, kept,
            topk, softmax_scale, new)
    return ms.mla_sparse_decode(q_lat, q_rope, latent.stack, latent.layer,
                                lengths, bias, softmax_scale, new)


def latent_sparse_prefill(q_nope, q_rope, w_kvb, q_index, w, latent,
                          index_keys, row, start, topk: int,
                          softmax_scale: float):
    """A chunk of ONE sequence's queries (q_nope (C, H, dn), q_rope (C, H,
    rope), q_index (C, Hi, Di), w (C, Hi), positions `start ..`) against
    sequence `row`'s slabs of the latent cache and of the index keys, which
    already hold the chunk: each query's choice of `topk` positions up to
    its own (`sparse_select.py`'s), and EXPANDED attention over them through
    the up-projection `w_kvb` (rank, H, dn + dv). Returns (C, H, dv) and (C,)
    int32, the slots each query kept. The kernels where the chip's tiling
    takes the shapes (whole lane tiles of slots and of queries), else the
    plain form."""
    from deepspeed_tpu.ops.pallas import mla_sparse as ms
    from deepspeed_tpu.ops.pallas import sparse_select as ss
    aligned = latent.stack.shape[3] % 128 == 0 and q_nope.shape[0] % 128 == 0
    # `choose`: the scope the program map reads (docs/telemetry.md)
    if aligned and _one_device_kernel(ms.PREFILL_NAME):
        with jax.named_scope("choose"):
            bias, kept = ss.sparse_prefill_choice(
                q_index, w, index_keys.stack, index_keys.layer, row, start,
                topk)
        fn = ms.mla_sparse_prefill
    else:
        with jax.named_scope("choose"):
            bias, kept = ss.choice_plain(
                q_index, w,
                ss.row_of(index_keys.stack, index_keys.layer, row)[0],
                jnp.asarray(start, jnp.int32) + jnp.arange(q_nope.shape[0]),
                topk)
        fn = ms.mla_sparse_prefill_reference
    return fn(q_nope, q_rope, w_kvb, bias, latent.stack, latent.layer, row,
              start, softmax_scale), kept


def latent_dense_prefill(q_nope, q_rope, w_kvb, latent, row, start,
                         softmax_scale: float):
    """A chunk of ONE sequence's queries (q_nope (C, H, dn), q_rope (C, H,
    rope), positions `start ..`) against EVERY row of sequence `row`'s slab
    of the latent cache up to each query's own, the slab already holding the
    chunk: EXPANDED attention through the up-projection `w_kvb` (rank, H, dn
    + dv), no choice and no bias (`ops/pallas/mla_sparse.mla_dense_prefill`:
    what lies above the diagonal is neither fetched nor computed). Returns
    (C, H, dv). The kernel where the chip's tiling takes the shapes (whole
    lane tiles of slots and of queries), else the plain form."""
    from deepspeed_tpu.ops.pallas import mla_sparse as ms
    aligned = latent.stack.shape[3] % 128 == 0 and q_nope.shape[0] % 128 == 0
    fn = ms.mla_dense_prefill if aligned and _one_device_kernel(
        ms.DENSE_PREFILL_NAME) else ms.mla_dense_prefill_reference
    return fn(q_nope, q_rope, w_kvb, latent.stack, latent.layer, row, start,
              softmax_scale)


def rms_norm_ref(x, weight, eps: float = 1e-6):
    """RMSNorm reference (csrc/transformer/inference/csrc/rms_norm.cu analog)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched `factor` times
    (`0.1 mscale ln(factor) + 1`; 1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, scaling) -> jnp.ndarray:
    """The rotary pairs' frequencies under YaRN (`rope_scaling` of type
    `yarn`: `factor`, `original_max_position_embeddings`, `beta_fast`,
    `beta_slow`, read as attributes): a pair that turns more than
    `beta_fast` times over the original context keeps its frequency, one
    that turns fewer than `beta_slow` times has it divided by `factor`, a
    linear ramp in the pair's index between the two (the published
    `find_correction_range` / `linear_ramp_factor`)."""
    def pair_turning(turns):        # the (fractional) pair that turns so often
        return head_dim * math.log(scaling.original_max_position_embeddings
                                   / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_turning(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_turning(scaling.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    return inv_freq / scaling.factor * ramp + inv_freq * (1.0 - ramp)


def rope_cos_sin(positions, head_dim: int, theta: float = 10000.0,
                 dtype=jnp.float32, scaling=None):
    """cos/sin tables for rotary embedding; positions (B, S) or (S,).
    `scaling`: None, or a YaRN `rope_scaling` (`yarn_inv_freq`): the
    frequencies are scaled and the tables times `yarn_mscale(factor, mscale)
    / yarn_mscale(factor, mscale_all_dim)`, which is 1 where the two are
    equal (the softmax's own `mscale ** 2` is the caller's)."""
    if scaling is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        m = 1.0
    else:
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        m = yarn_mscale(scaling.factor, scaling.mscale) \
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, D/2)
    if m != 1.0:
        return (jnp.cos(angles) * m).astype(dtype), \
            (jnp.sin(angles) * m).astype(dtype)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary_emb(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2).
    Counterpart of csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
