"""int8 serving with PER-LAYER in-scan dequantization — the ZeRO-Inference
at-rest-quantized path at 7B scale (VERDICT r4 #1 / r5's named r6 fix).

The v1 engine dequantizes the WHOLE tree before model.apply, so int8 7 GB
+ bf16 13.5 GB coexist → OOM at 7B on a 16 GB v5e (measured,
benchmarks/hf7b_decode.py). This harness proves the fix: an engine-LEVEL
layer loop (`lax.scan` whose xs are the stacked int8+scales leaves — the
same per-layer slicing the pipeline chunk fns ride) dequantizes ONE
layer's weights inside the scan body, so the bf16 form is a ~0.4 GB
transient and peak HBM ≈ int8 tree + cache + one layer. Decode also
becomes weight-READ-bound at the int8 footprint: ~6.8 GB/step vs
13.5 GB/step for bf16 — the capacity win doubles as a throughput win.

Phases (combine freely on the CLI):
  (default)  7B layer-scan decode, NAIVE per-layer dequant (the r5 path)
  fused      7B layer-scan decode with the fused dequant-GEMM Pallas
             kernel on every matmul (ops/pallas/quantized_matmul.py)
  ab         single-process whole-LAYER A/B: fused vs naive decode-step
             layer forward, chained n_iter≥16 per the r5 measurement
             rules (compare whole layers, not single matmuls)
  cpu        small-shape exact-parity check vs the whole-tree engine

MEASURED (r5, 1×v5e): CPU parity EXACT vs the engine over dequantized
params. 7B: int8 tree 7.63 GB on device and the layer-scan decode RUNS —
the capacity claim holds (a 13B int8 would fit where bf16 cannot). That
run predated two review fixes (norm stacks were also quantized; embed/head
landed f32 not bf16); post-fix the tree is 7.10 GB by exact accounting
(L·(int8 + scales/256·4B + bf16 norms) + bf16 embed/head — this harness
prints `quantized_tree_gb` to confirm on device). NAIVE throughput
40.8 tok/s @ b4 vs 162 bf16: the per-layer dequant MATERIALIZES f32/bf16
intermediates (~2.6 GB of HBM traffic per layer per step ≈ 98 ms/step,
matching measurement) because XLA does not fuse the block-reshape dequant
into the matmul operand read. The r6 `fused` phase removes exactly that:
decode weight reads drop to the at-rest bytes (6.84 GB/step vs 13.21
bf16-dense — see telemetry weight_bytes_step), so fused int8 targets
~2x FASTER than bf16, not 4x slower. r6 numbers pend the next on-chip
run (this round's sandbox has no TPU attached); the engine-integrated
path is benchmarked end-to-end by `hf7b_decode.py --int8`.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_quantized_decode(cfg, b: int, prompt: int, new: int, max_len: int,
                           fused: bool = False):
    """Compiled greedy generate over a layer-quantized llama param tree.

    Expects params with `layers` leaves quantized ({'__q8__', 'scales'}
    dicts, stacked (L, ...) on axis 0) and embed/norm/lm_head unquantized.
    `fused` swaps the naive dequantize-then-matmul layer body for the
    shared fused-kernel block (inference/quantized_layer_scan.py) — the
    same body the engine's quantized_layer_scan serve mode scans.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.inference.kv_cache import decode_mask
    from deepspeed_tpu.inference.quantization import dequantize_param_tree
    from deepspeed_tpu.models.llama import LlamaBlock, RMSNorm
    from deepspeed_tpu.ops.attention import rope_cos_sin

    block = LlamaBlock(cfg)
    final_norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
    hd = cfg.head_dim

    if fused:
        from deepspeed_tpu.inference.quantized_layer_scan import make_block_fn
        fused_block = make_block_fn(cfg, fused=True)

        def layer_step(h, aux, layer_q, kv):
            return fused_block(h, layer_q, aux, kv)
    else:
        def layer_step(h, aux, layer_q, kv):
            lp = dequantize_param_tree(layer_q, dtype=cfg.dtype)
            out, new_kv = block.apply({"params": lp}, h, aux, kv=kv)
            return out, new_kv

    def forward(params, ids, cache_k, cache_v, index):
        embed = params["embed_tokens"].astype(cfg.dtype)
        h = jnp.take(embed, ids, axis=0)
        bsz, s = ids.shape
        positions = index[:, None] + jnp.arange(s)[None, :]
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, cfg.dtype)
        mask = decode_mask(positions, max_len)
        aux = (cos, sin, index, mask)

        def body(h, xs):
            layer_q, k_l, v_l = xs
            h, (k_new, v_new) = layer_step(h, aux, layer_q, (k_l, v_l))
            return h, (k_new, v_new)

        h, (k_new, v_new) = lax.scan(
            body, h, (params["layers"], cache_k, cache_v))
        h = final_norm.apply({"params": params["norm"]}, h)
        head = params.get("lm_head")
        if head is None:
            logits = h @ embed.T
        else:
            logits = h @ head.astype(cfg.dtype)
        return logits, k_new, v_new

    def gen(params, ids):
        bsz = ids.shape[0]
        L = cfg.num_hidden_layers
        cache_k = jnp.zeros((L, bsz, max_len, cfg.num_key_value_heads, hd),
                            cfg.dtype)
        cache_v = jnp.zeros_like(cache_k)
        index0 = jnp.zeros((bsz,), jnp.int32)
        logits, cache_k, cache_v = forward(params, ids, cache_k, cache_v,
                                           index0)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

        def step(carry, _):
            cache_k, cache_v, tok, index = carry
            logits, cache_k, cache_v = forward(
                params, tok[:, None], cache_k, cache_v, index)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return (cache_k, cache_v, nxt, index + 1), tok

        carry = (cache_k, cache_v, tok, jnp.full((bsz,), prompt, jnp.int32))
        (cache_k, cache_v, last, _), toks = lax.scan(
            step, carry, None, length=new - 1)
        return jnp.concatenate([toks.T, last[:, None]], axis=1)

    return gen


def ab_phase(on_cpu: bool, n_iter: int = 32, repeats: int = 3):
    """Single-process whole-LAYER A/B: one decode-step layer forward
    (7 matmuls + rope + cached attention + norms) chained `n_iter` times
    inside ONE jit, fused dequant-GEMM vs naive dequantize-then-matmul
    over the SAME quantized leaves. Per the r5 rules: whole layers, one
    process, best-of-`repeats`, real fetch at the end of each chain."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.inference.kv_cache import decode_mask
    from deepspeed_tpu.inference.quantized_layer_scan import make_block_fn
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.attention import rope_cos_sin
    from deepspeed_tpu.ops.quantization import quantize_int8_blockwise

    if on_cpu:  # functional smoke only — interpret-mode Pallas is slow
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, remat=False,
                          attn_impl="xla", dtype=jnp.float32)
        b, n_iter, repeats = 2, 2, 1
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                          intermediate_size=11008, num_hidden_layers=1,
                          num_attention_heads=32, num_key_value_heads=32,
                          max_position_embeddings=4096, remat=False,
                          dtype=jnp.bfloat16)
        b = 4
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    max_len = 128
    tile = (np.arange(1 << 14) % 251).astype(np.float32) * 1e-3

    def mk(shape):
        n = int(np.prod(shape))
        reps = -(-n // tile.size)
        return jnp.asarray(np.tile(tile, reps)[:n].reshape(shape), cfg.dtype)

    def qz(x):
        qv, s = quantize_int8_blockwise(x)
        return {"kernel": {"__q8__": qv, "scales": s}}

    kvd = cfg.num_key_value_heads * hd
    lp = {"self_attn": {"q_proj": qz(mk((d, d))),
                        "k_proj": qz(mk((d, kvd))),
                        "v_proj": qz(mk((d, kvd))),
                        "o_proj": qz(mk((d, d)))},
          "mlp": {"gate_proj": qz(mk((d, f))), "up_proj": qz(mk((d, f))),
                  "down_proj": qz(mk((f, d)))},
          "input_layernorm": {"weight": jnp.ones((d,), jnp.float32)},
          "post_attention_layernorm": {"weight": jnp.ones((d,), jnp.float32)}}
    jax.block_until_ready(lp)

    h0 = mk((b, 1, d))
    kv0 = (jnp.zeros((b, max_len, cfg.num_key_value_heads, hd), cfg.dtype),
           jnp.zeros((b, max_len, cfg.num_key_value_heads, hd), cfg.dtype))
    index = jnp.full((b,), 64, jnp.int32)  # mid-cache decode position
    positions = index[:, None]
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, cfg.dtype)
    mask = decode_mask(positions, max_len)
    aux = (cos, sin, index, mask)

    def chain(block):
        def run(lp, h, kv):
            def body(_, carry):
                h, kv = carry
                h, kv = block(h, lp, aux, kv)
                return (h, kv)
            h, kv = lax.fori_loop(0, n_iter, body, (h, kv))
            return h.astype(jnp.float32).sum()  # tiny fetch forces the work
        return jax.jit(run)

    row = {}
    for name, fused in (("naive", False), ("fused", True)):
        fn = chain(make_block_fn(cfg, fused=fused))
        _ = float(fn(lp, h0, kv0))  # compile + warm
        best = 1e9
        for _ in range(repeats):
            t0 = time.time()
            _ = float(fn(lp, h0, kv0))
            best = min(best, time.time() - t0)
        row[name + "_ms_per_layer"] = round(best / n_iter * 1e3, 3)
    row["fused_speedup"] = round(
        row["naive_ms_per_layer"] / max(row["fused_ms_per_layer"], 1e-9), 2)
    row["n_iter"] = n_iter
    print(json.dumps({"layer_ab": row}), flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu
    from deepspeed_tpu.inference.quantization import quantize_param_tree
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    from deepspeed_tpu.utils import groups

    on_cpu = "cpu" in sys.argv[1:]
    fused = "fused" in sys.argv[1:]
    if "ab" in sys.argv[1:]:
        ab_phase(on_cpu)
        return
    if on_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = flags + \
                " --xla_force_host_platform_device_count=1"
        jax.config.update("jax_platforms", "cpu")
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, remat=False,
                          attn_impl="xla", dtype=jnp.float32)
        b, prompt, new = 2, 8, 6
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                          intermediate_size=11008, num_hidden_layers=32,
                          num_attention_heads=32, num_key_value_heads=32,
                          max_position_embeddings=4096, remat=False,
                          dtype=jnp.bfloat16)
        b, prompt, new = 4, 64, 32
    max_len = 128

    groups.reset_topology()
    model = LlamaForCausalLM(cfg)

    def init_params():
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
        raw, _ = extract_params_and_specs(variables)
        return jtu.tree_map(lambda x: x.astype(cfg.dtype), raw)

    if on_cpu:
        params = jax.jit(init_params)()
        jax.block_until_ready(params)
    else:
        # build by SHAPE on the host: a 13.5 GB bf16 device tree would
        # leave the lazy allocator unable to serve the generate phase
        # even after frees (CLAUDE.md bench gotcha), and a real host-side
        # random init costs 10+ min on this 1-core box. Values are a
        # cheap tiled ramp — the measurement is weight-READ-bound perf
        # (numeric parity is proven exactly on the CPU path above).
        shapes = jax.eval_shape(init_params)
        tile = (np.arange(1 << 16) % 251).astype(np.float32) * 1e-3

        def mk(sd):
            n = int(np.prod(sd.shape))
            reps = -(-n // tile.size)
            return np.tile(tile, reps)[:n].reshape(sd.shape).astype(sd.dtype)
        params = jtu.tree_map(mk, shapes)

    # quantize ONLY the layer stacks, PER LAYER (vmap over the stacked
    # axis) so scales carry a leading L dim and lax.scan can slice them;
    # embed/norm/head stay unquantized
    from deepspeed_tpu.ops.quantization import quantize_int8_blockwise

    q_one = jax.jit(lambda t: quantize_int8_blockwise(t))

    def q_stacked(x):
        # kernels are 3-D stacked (L, in, out); 2-D stacks are the
        # per-layer NORM weights, which stay full precision (the engine's
        # quantize_param_tree skips norms/biases too)
        if x.ndim >= 3 and x[0].size >= 4096:
            if on_cpu:
                qv, s = jax.jit(jax.vmap(
                    lambda t: quantize_int8_blockwise(t)))(x)
                return {"__q8__": qv, "scales": s}
            # 7B path: one layer at a time — the whole-stack vmap's f32
            # temps are 2x the leaf (5.4 GB for the mlp stacks) and OOM
            # the chip during the quantization phase itself
            qs, ss = [], []
            for l in range(x.shape[0]):
                q_l, s_l = q_one(jnp.asarray(x[l]))
                jax.block_until_ready((q_l, s_l))
                qs.append(q_l)
                ss.append(s_l)
            return {"__q8__": jnp.stack(qs), "scales": jnp.stack(ss)}
        return x

    # leaf-wise REPLACEMENT: rebinding each leaf frees its bf16 form
    # before the next quantizes, so peak HBM ≈ bf16 tree + one leaf
    leaves, treedef = jtu.tree_flatten(params["layers"])
    rest = {k: v for k, v in params.items() if k != "layers"}
    del params
    for i in range(len(leaves)):
        q = q_stacked(leaves[i])
        jax.block_until_ready(q)
        leaves[i] = q
    qparams = dict(rest, layers=jtu.tree_unflatten(treedef, leaves))
    del leaves
    q_bytes = sum(getattr(l, "nbytes", 0)
                  for l in jtu.tree_leaves(qparams))
    print(json.dumps({"quantized_tree_gb": round(q_bytes / 1e9, 2)}),
          flush=True)

    gen = build_quantized_decode(cfg, b, prompt, new, max_len, fused=fused)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (b, prompt)), jnp.int32)
    t0 = time.time()
    if on_cpu:
        jfn = jax.jit(gen)
    else:
        # AUTO input layouts + leaf-wise re-placement (the
        # InferenceEngine._compile_auto_layout recipe, duplicated here
        # because this harness bypasses the engine; see that method's
        # NOTE for the sole-reference caveat): without it XLA copies the
        # int8 stacks to its preferred tiling in-program and OOMs
        from deepspeed_tpu.utils.layouts import (
            auto_input_format, compiled_input_formats)
        jitted = jax.jit(gen, in_shardings=auto_input_format())
        abstract = jtu.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), qparams)
        compiled = jitted.lower(
            abstract, jax.ShapeDtypeStruct(ids.shape, ids.dtype)).compile()
        fmts = compiled_input_formats(compiled)[0]
        qleaves, qdef = jtu.tree_flatten(qparams)
        fmt_leaves = jtu.tree_leaves(fmts[0])
        del qparams
        for i, fmt in enumerate(fmt_leaves):
            new_leaf = jax.device_put(qleaves[i], fmt)
            new_leaf.block_until_ready()
            qleaves[i] = new_leaf
        qparams = jtu.tree_unflatten(qdef, qleaves)
        del qleaves
        ids = jax.device_put(ids, fmts[1])
        jfn = compiled
    out = np.asarray(jfn(qparams, ids))
    compile_s = round(time.time() - t0, 1)
    best = 1e9
    for _ in range(3):
        t0 = time.time()
        out = np.asarray(jfn(qparams, ids))
        best = min(best, time.time() - t0)
    print(json.dumps({"int8_layer_scan_decode": {
        "impl": "fused" if fused else "naive",
        "batch": b, "new_tokens": new,
        "full_gen_s": round(best, 3),
        "decode_tokens_per_sec": round(b * new / best, 1),
        "compile_s": compile_s,
        "distinct": int(len(np.unique(out)))}}), flush=True)

    if on_cpu:
        # parity vs the zoo model with DEQUANTIZED params (same weights);
        # the stacked (L-leading) form dequantizes per layer via vmap
        from deepspeed_tpu.inference.quantization import is_quantized_leaf
        from deepspeed_tpu.ops.quantization import dequantize_int8_blockwise

        def dq_stacked(leaf):
            if is_quantized_leaf(leaf):
                return jax.vmap(lambda q, s: dequantize_int8_blockwise(
                    q, s, cfg.dtype))(leaf["__q8__"], leaf["scales"])
            return leaf

        dq = dict(qparams, layers=jtu.tree_map(
            dq_stacked, qparams["layers"], is_leaf=is_quantized_leaf))
        import deepspeed_tpu
        eng = deepspeed_tpu.init_inference(model, params=dq, dtype="fp32",
                                           auto_layouts=False)
        ref = eng.generate(np.asarray(ids), max_new_tokens=new)
        np.testing.assert_array_equal(out, np.asarray(ref)[:, prompt:])
        print(json.dumps({"cpu_parity": "exact"}), flush=True)


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
