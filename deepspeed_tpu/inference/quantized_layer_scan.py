"""quantized_layer_scan serve mode — ZeRO-Inference int8 decode at scale.

The v1 engine's whole-tree dequant holds int8 + bf16 trees live together
(OOM at 7B on a 16 GB v5e); an r5 harness
(since deleted) proved the fix: an engine-LEVEL
`lax.scan` whose xs are the per-layer-stacked int8+scales leaves, so the
dequantized form of ONE layer is the only transient and peak HBM ≈ int8
tree + KV cache + one layer. This module lifts that structure into the
engine as a first-class serve mode and adds the second half of the story:
the q/k/v/o and MLP matmuls ride the FUSED dequant-GEMM Pallas kernel
(`ops/pallas/quantized_matmul.py`), so decode reads the int8 bytes
(~6.8 GB/step at 7B) instead of materializing ~2.6 GB/layer/step of
dequantized weights that made the naive path 4x slower than bf16.

Scope: models whose param tree is the llama layer layout (llama, qwen2,
mistral, internlm, phi3 post-converter — q/k/v/o + gate/up/down + two
RMSNorms). `layer_scan_supported` gates it; the engine's `auto` serve
mode falls back to whole-tree dequant elsewhere. The forward mirrors
`LlamaForCausalLM`'s cached path op-for-op (same rope/update_layer/
cached_attention/decode_mask building blocks), so with the naive matmul
(`fused=False`, the CPU default) its generate() is EXACTLY the whole-tree
engine's output — the parity contract tests/unit/inference pins.

`make_block_fn` (one layer's decode step over possibly-quantized leaves)
is the shared block body of THREE consumers: this module's in-program
`lax.scan`, the benchmark A/B harnesses, and the r7 capacity serve mode
(`inference/capacity_scan.py`), whose host-driven layer loop jits the
same function once and streams host-parked slices through it — which is
why capacity generate() is bit-exact vs the resident layer scan.
`quantize_layer_stacks` is likewise shared: the capacity runner calls it
on the host backend so int8 values match the resident engine's exactly.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.inference.quantization import is_quantized_leaf
from deepspeed_tpu.ops.quantization import (
    dequantize_int8_blockwise, quantize_int8_blockwise)

# llama-tree layer keys the scan body consumes
_ATTN_KEYS = ("q_proj", "k_proj", "v_proj", "o_proj")
_MLP_KEYS = ("gate_proj", "up_proj", "down_proj")


def layer_scan_supported(params: Any) -> bool:
    """True when `params` is a llama-layout tree the scan body understands:
    stacked `layers` with self_attn/mlp/norm children plus top-level
    embed_tokens and norm (lm_head optional — tied embeddings)."""
    try:
        layers = params["layers"]
        for k in _ATTN_KEYS:
            _ = layers["self_attn"][k]["kernel"]
        for k in _MLP_KEYS:
            _ = layers["mlp"][k]["kernel"]
        _ = layers["input_layernorm"]["weight"]
        _ = layers["post_attention_layernorm"]["weight"]
        _ = params["embed_tokens"]
        _ = params["norm"]["weight"]
        return True
    except (KeyError, TypeError, IndexError):
        return False


def quantize_layer_stacks(params: Any, group_size: int = 256,
                          min_size: int = 4096,
                          big_leaf_bytes: int = 1 << 30) -> Any:
    """Quantize the stacked layer kernels PER LAYER (scales keep a leading
    L dim so `lax.scan` slices them); norms/biases and the non-layer leaves
    (embed/head) stay full precision — the r5 review contract. Pre-quantized
    stacked leaves (the big-model leaf-wise load path) are normalized to the
    per-layer scale layout instead of requantized; pre-quantized NON-layer
    leaves are dequantized back (embed/head serve in bf16).

    Leaf-wise REBINDING keeps peak memory at tree + one leaf; stacked
    leaves above `big_leaf_bytes` quantize one layer at a time (the
    whole-stack vmap's f32 temps are 2x the leaf — measured OOM during the
    7B quantization phase itself)."""
    import jax.tree_util as jtu

    q_one = jax.jit(lambda t: quantize_int8_blockwise(t, group_size))
    q_stack = jax.jit(jax.vmap(
        lambda t: quantize_int8_blockwise(t, group_size)))

    def q_stacked(x):
        if is_quantized_leaf(x):
            q, s = x["__q8__"], jnp.asarray(x["scales"])
            if q.ndim < 3:
                # pre-quantized NORM/bias stacks (an over-eager loader):
                # the scan body wants them full precision — dequantize back
                return dequantize_int8_blockwise(q, s.reshape(-1))
            if s.ndim == 1 and s.shape[0] % q.shape[0] == 0:
                # whole-stack flat blocks never span layers when they tile
                # the stack — reshaping the scales IS the per-layer layout
                s = s.reshape(q.shape[0], -1)
            return {"__q8__": q, "scales": s}
        if not (hasattr(x, "ndim") and x.ndim >= 3 and x[0].size >= min_size
                and jnp.issubdtype(x.dtype, jnp.floating)):
            return x
        if getattr(x, "nbytes", 0) > big_leaf_bytes:
            qs, ss = [], []
            for l in range(x.shape[0]):
                q_l, s_l = q_one(jnp.asarray(x[l]))
                jax.block_until_ready((q_l, s_l))
                qs.append(q_l)
                ss.append(s_l)
            return {"__q8__": jnp.stack(qs), "scales": jnp.stack(ss)}
        qv, s = q_stack(x)
        return {"__q8__": qv, "scales": s}

    layers_leaves, treedef = jtu.tree_flatten(
        params["layers"], is_leaf=is_quantized_leaf)
    rest = {k: v for k, v in params.items() if k != "layers"}
    del params
    for i in range(len(layers_leaves)):
        q = q_stacked(layers_leaves[i])
        jax.block_until_ready(q)
        layers_leaves[i] = q

    def dq_rest(leaf):
        if is_quantized_leaf(leaf):  # embed/head landed pre-quantized
            return dequantize_int8_blockwise(
                leaf["__q8__"], jnp.asarray(leaf["scales"]).reshape(-1))
        return leaf

    rest = jtu.tree_map(dq_rest, rest, is_leaf=is_quantized_leaf)
    return dict(rest, layers=jtu.tree_unflatten(treedef, layers_leaves))


def weight_bytes_per_step(params: Any) -> int:
    """At-rest weight bytes a decode step READS under the layer scan: every
    layer leaf (int8 + scales + norms) plus final norm and lm_head. The
    embedding is a B-row gather, not a full read — excluded."""
    import jax.tree_util as jtu
    total = sum(getattr(x, "nbytes", 0)
                for x in jtu.tree_leaves(params.get("layers", {})))
    total += sum(getattr(x, "nbytes", 0)
                 for x in jtu.tree_leaves(params.get("norm", {})))
    head = params.get("lm_head")
    if head is not None:
        total += sum(getattr(x, "nbytes", 0)
                     for x in jtu.tree_leaves(head))
    return int(total)


def at_rest_bytes(params: Any) -> dict:
    """Residency-plane accounting of a (possibly layer-stacked-quantized)
    tree's at-rest form: {'int8', 'scales', 'full_precision', 'total'}
    bytes from leaf metadata only. This is the formula side of the int8
    weight reconciliation (docs/memory.md worked example — the r6
    7.63-vs-7.10 GB class of mismatch becomes a measured drift)."""
    import jax.tree_util as jtu
    out = {"int8": 0, "scales": 0, "full_precision": 0}
    for leaf in jtu.tree_leaves(params, is_leaf=is_quantized_leaf):
        if is_quantized_leaf(leaf):
            out["int8"] += int(leaf["__q8__"].nbytes)
            out["scales"] += int(leaf["scales"].nbytes)
        else:
            out["full_precision"] += int(getattr(leaf, "nbytes", 0))
    out["total"] = out["int8"] + out["scales"] + out["full_precision"]
    return out


def dense_bytes_per_step(params: Any, dtype) -> int:
    """The same accounting for the dense (dequantized) serving form — what
    a bf16 engine reads per step; the telemetry baseline field."""
    import jax.tree_util as jtu
    itemsize = jnp.dtype(dtype).itemsize

    def nbytes(leaf):
        if is_quantized_leaf(leaf):
            return leaf["__q8__"].size * itemsize
        return getattr(leaf, "size", 0) * jnp.dtype(
            getattr(leaf, "dtype", dtype)).itemsize

    total = 0
    for sub in ("layers", "norm"):
        for leaf in jtu.tree_leaves(params.get(sub, {}),
                                    is_leaf=is_quantized_leaf):
            total += nbytes(leaf)
    head = params.get("lm_head")
    if head is not None:
        total += nbytes(head)
    return int(total)


def _rmsnorm(x, w, eps, dtype):
    # exact RMSNorm math from models.llama.RMSNorm
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * lax.rsqrt(var + eps)) * w).astype(dtype)


def make_matmul(dtype, fused: bool = True, mesh=None):
    """x @ W (+ bias) over a projection dict, W either a plain leaf or
    int8+scales. The fused kernel streams int8; the naive path dequantizes
    — SAME values either way (the kernel folds the identical scale into
    the contraction), different rounding only.

    With a multi-device `mesh` (nontrivial 'model' axis) the fused kernel
    rides `sharded_quantized_matmul` — int8 blocks + scales sharded over
    'model' inside a shard_map manual region (GSPMD cannot partition the
    pallas_call). `hint` is the flavor preference per projection: 'n'
    (column-parallel) for q/k/v/gate/up, 'k' (row-parallel + psum) for
    o/down — matching the at-rest placement specs. Shapes whose scale
    blocks can't split over the axis fall back to the naive dequant
    matmul with a `kernel_fallback` WARN (GSPMD partitions that fine)."""
    from deepspeed_tpu.ops.pallas.quantized_matmul import (
        quantized_matmul, scale_group_width, sharded_quantized_matmul,
        tp_shard_flavor)
    tp = 1
    if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
        tp = int(mesh.shape["model"])

    def matmul(x, proj, hint: str = "n"):
        w = proj["kernel"]
        if is_quantized_leaf(w):
            q, sc = w["__q8__"], w["scales"]
            flavor = tp_shard_flavor(q.shape[0], q.shape[1], sc.shape[0],
                                     tp, prefer=hint) if fused else None
            if fused and tp > 1 and flavor is not None:
                y = sharded_quantized_matmul(x, q, sc, mesh, flavor=flavor)
            elif fused and tp <= 1 and scale_group_width(
                    q.shape[0], q.shape[1], sc.shape[0]) is not None:
                y = quantized_matmul(x, q, sc)
            else:
                if fused and tp > 1:
                    from deepspeed_tpu.ops.pallas.sharded import kernel_fallback
                    kernel_fallback(
                        "quantized_matmul",
                        f"({q.shape[0]}, {q.shape[1]}) int8 weight: scale "
                        f"blocks don't divide model={tp}")
                y = x @ dequantize_int8_blockwise(q, sc, dtype)
        else:
            y = x @ w.astype(dtype)
        bias = proj.get("bias")
        if bias is not None:
            y = y + bias.astype(dtype)
        return y

    return matmul


def make_block_fn(model_cfg: Any, fused: bool = True, mesh=None):
    """LlamaBlock's decode path, functionally, over ONE layer's (possibly
    per-layer-quantized) leaves: block(h, lp, (cos, sin, index, mask),
    (k_cache, v_cache)) → (h, (k_cache, v_cache)). Shared by the engine's
    layer-scan generate and the benchmark A/B harnesses so both measure
    the same program. `mesh` (multi-device, 'model' nontrivial) routes
    the fused matmuls through their TP shard_map wrappers — see
    `make_matmul`; single-device callers (capacity mode, the harnesses)
    pass nothing and get the identical r6 program."""
    from deepspeed_tpu.inference.kv_cache import update_layer
    from deepspeed_tpu.ops.attention import apply_rotary_emb, cached_attention

    cfg = model_cfg
    dtype = cfg.dtype
    hd, nh = cfg.head_dim, cfg.num_attention_heads
    nkv = cfg.num_key_value_heads
    eps = cfg.rms_norm_eps
    window = getattr(cfg, "sliding_window", None)
    attn_impl = getattr(cfg, "attn_impl", "auto")
    matmul = make_matmul(dtype, fused=fused, mesh=mesh)

    def block(h, lp, aux, kv):
        cos, sin, index, mask = aux
        bsz, sl = h.shape[:2]
        attn_p, mlp_p = lp["self_attn"], lp["mlp"]
        hn = _rmsnorm(h, lp["input_layernorm"]["weight"], eps, dtype)
        q = matmul(hn, attn_p["q_proj"]).reshape(bsz, sl, nh, hd)
        k = matmul(hn, attn_p["k_proj"]).reshape(bsz, sl, nkv, hd)
        v = matmul(hn, attn_p["v_proj"]).reshape(bsz, sl, nkv, hd)
        q = apply_rotary_emb(q, cos, sin)
        k = apply_rotary_emb(k, cos, sin)
        k_cache, v_cache = update_layer(kv[0], kv[1], k, v, index)
        ctx = cached_attention(q, k_cache, v_cache, index, mask,
                               impl=attn_impl, window=window)
        h = h + matmul(ctx.reshape(bsz, sl, nh * hd), attn_p["o_proj"],
                       hint="k")
        hn = _rmsnorm(h, lp["post_attention_layernorm"]["weight"], eps, dtype)
        g = matmul(hn, mlp_p["gate_proj"])
        u = matmul(hn, mlp_p["up_proj"])
        h = h + matmul(jax.nn.silu(g) * u, mlp_p["down_proj"], hint="k")
        return h, (k_cache, v_cache)

    return block


def make_scan_apply(model_cfg: Any, fused: bool = False, mesh=None):
    """`model.apply`-shaped forward over a per-layer-stacked llama tree:
    `apply(params, ids, cache) → (logits, cache)` with `cache` a dense
    `KVCache` — the layer-scan analog of the zoo models' cached path, and
    the adapter that lets the v2 continuous-batching engine drive its
    bucketed prefill/decode programs through the SAME `make_block_fn`
    body the v1 layer scan and capacity runner execute (bit-exact parity
    by construction, the r7 contract). Works on the full (L, B, M, H, D)
    cache and on the v2 engine's single-row views alike, and on any
    leading layer count L' (speculative draft sub-stacks); the returned
    cache keeps the caller's cursors (`index` unchanged — every v2 call
    site owns cursor advancement explicitly)."""
    from deepspeed_tpu.inference.kv_cache import KVCache, decode_mask
    from deepspeed_tpu.ops.attention import rope_cos_sin

    cfg = model_cfg
    dtype = cfg.dtype
    hd = cfg.head_dim
    eps = cfg.rms_norm_eps
    window = getattr(cfg, "sliding_window", None)
    block = make_block_fn(cfg, fused=fused, mesh=mesh)

    def apply(params, ids, cache):
        layers = params["layers"]
        embed = params["embed_tokens"].astype(dtype)
        head = params.get("lm_head")
        ids = jnp.asarray(ids, jnp.int32)
        bsz, sl = ids.shape
        max_len = cache.k.shape[2]
        index = cache.index
        h = jnp.take(embed, ids, axis=0)
        positions = index[:, None] + jnp.arange(sl)[None, :]
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, dtype)
        mask = decode_mask(positions, max_len, window=window)
        aux = (cos, sin, index, mask)

        def body(h, xs):
            lp, k_l, v_l = xs
            h, (k_new, v_new) = block(h, lp, aux, (k_l, v_l))
            return h, (k_new, v_new)

        h, (ck, cv) = lax.scan(body, h, (layers, cache.k, cache.v))
        h = _rmsnorm(h, params["norm"]["weight"], eps, dtype)
        if head is None:
            logits = jnp.einsum("bsd,vd->bsv", h, embed)
        else:
            logits = h @ head.astype(dtype)
        return logits, KVCache(k=ck, v=cv, index=index)

    return apply


def build_layer_scan_generate(model_cfg: Any, infer_cfg: Any,
                              b: int, s: int, max_new_tokens: int,
                              temperature: float, top_k: int, top_p: float,
                              eos_token_id: Optional[int],
                              pad_token_id: int,
                              fused: bool = True,
                              auto_layout: bool = False,
                              mesh=None, name: Optional[str] = None):
    """One compiled prefill + decode-scan program over a per-layer-quantized
    llama tree — the layer-scan analog of `InferenceEngine._build_generate`
    (same sampling/eos semantics, same KV-cache shapes). `name`: the jitted
    function's, so that the device trace names the module after the
    program's `compile` span."""
    from deepspeed_tpu.inference.kv_cache import decode_mask
    from deepspeed_tpu.ops.attention import rope_cos_sin
    from deepspeed_tpu.ops.sampling import sample_logits

    cfg = model_cfg
    dtype = cfg.dtype
    hd = cfg.head_dim
    nkv = cfg.num_key_value_heads
    num_layers = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    window = getattr(cfg, "sliding_window", None)
    max_len = -(-(s + max_new_tokens) // 128) * 128
    block = make_block_fn(cfg, fused=fused, mesh=mesh)

    def sample(logits, rng):
        return sample_logits(logits, rng, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def gen(params, ids, rng):
        layers = params["layers"]
        embed = params["embed_tokens"].astype(dtype)
        head = params.get("lm_head")

        def forward(ids_cur, cache_k, cache_v, index):
            bsz, sl = ids_cur.shape
            h = jnp.take(embed, ids_cur, axis=0)
            positions = index[:, None] + jnp.arange(sl)[None, :]
            cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, dtype)
            mask = decode_mask(positions, max_len, window=window)
            aux = (cos, sin, index, mask)

            def body(h, xs):
                lp, k_l, v_l = xs
                h, (k_new, v_new) = block(h, lp, aux, (k_l, v_l))
                return h, (k_new, v_new)

            h, (cache_k, cache_v) = lax.scan(
                body, h, (layers, cache_k, cache_v))
            h = _rmsnorm(h, params["norm"]["weight"], eps, dtype)
            if head is None:
                logits = jnp.einsum("bsd,vd->bsv", h, embed)
            else:
                logits = h @ head.astype(dtype)
            return logits, cache_k, cache_v

        cache_k = jnp.zeros((num_layers, b, max_len, nkv, hd),
                            infer_cfg.dtype)
        cache_v = jnp.zeros_like(cache_k)
        index = jnp.zeros((b,), jnp.int32)
        logits, cache_k, cache_v = forward(ids, cache_k, cache_v, index)
        rng, sub = jax.random.split(rng)
        tok = sample(logits[:, -1, :], sub)
        done = jnp.zeros((b,), jnp.bool_)
        if eos_token_id is not None:
            done = tok == eos_token_id

        def step(carry, rng_i):
            cache_k, cache_v, tok, done, index = carry
            logits, cache_k, cache_v = forward(
                tok[:, None], cache_k, cache_v, index)
            nxt = sample(logits[:, -1, :], rng_i)
            if eos_token_id is not None:
                nxt = jnp.where(done, pad_token_id, nxt)
                done = done | (nxt == eos_token_id)
            return (cache_k, cache_v, nxt, done, index + 1), tok

        keys = jax.random.split(rng, max_new_tokens - 1) \
            if max_new_tokens > 1 else jnp.zeros((0, 2), jnp.uint32)
        carry = (cache_k, cache_v, tok, done, jnp.full((b,), s, jnp.int32))
        (_, _, last, _, _), toks = lax.scan(step, carry, keys)
        new = jnp.concatenate([toks.T, last[:, None]], axis=1) \
            if max_new_tokens > 1 else last[:, None]
        return jnp.concatenate([ids, new], axis=1)

    if name:
        gen.__name__ = gen.__qualname__ = name
    if auto_layout:
        from deepspeed_tpu.utils.layouts import auto_input_format
        return jax.jit(gen, in_shardings=auto_input_format())
    return jax.jit(gen)
