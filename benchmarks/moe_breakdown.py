"""MoE on-chip breakdown (VERDICT r3 weak #3 / item 3).

Answers "is the one-hot/ragged dispatch the bottleneck, and is a
megablocks-style grouped-GEMM Pallas kernel needed?" with chained-loop
measurements at a mixtral-small-proxy shape on the real chip:

  1. experts-only batched GEMM at (E, C, D)        — the MXU floor
  2. ragged dispatch+combine with identity experts — scatter/gather cost
  3. einsum dispatch+combine with identity experts — one-hot matmul cost
  4. full MoE layer fwd (gate + dispatch + experts + combine), both impls
  5. full qwen2_moe-proxy TRAIN step MFU (the bench.py MoE row's source)

Usage: python benchmarks/moe_breakdown.py [pieces] [train]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_here = os.path.dirname(os.path.abspath(
    globals().get("__file__", "benchmarks/x")))
sys.path.insert(0, os.path.dirname(_here))


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.accelerator import on_tpu as _on_tpu

    phases = set(sys.argv[1:]) or {"pieces", "train"}
    on_tpu = _on_tpu()
    peak = 197e12

    # mixtral-small proxy: T tokens through E experts, top-2
    T, E, K, D, F = (8192, 8, 2, 1024, 2048) if on_tpu else (64, 4, 2, 32, 64)
    CF = 1.25
    key = jax.random.PRNGKey(0)

    if "pieces" in phases:
        from deepspeed_tpu.moe.sharded_moe import (
            _capacity, dispatch_combine, dispatch_combine_ragged, topkgating,
            topkgating_ragged)
        cap = _capacity(T, E, CF, 8, K)
        x = jax.random.normal(key, (T, D), jnp.bfloat16)
        wg = jax.random.normal(key, (D, E), jnp.float32) * 0.02
        w_up = jax.random.normal(key, (E, D, F), jnp.bfloat16) * 0.02
        w_gate = jax.random.normal(key, (E, D, F), jnp.bfloat16) * 0.02
        w_down = jax.random.normal(key, (E, F, D), jnp.bfloat16) * 0.02
        n_iter = 64 if on_tpu else 2
        res = {"tokens": T, "experts": E, "k": K, "capacity": cap}

        def experts_fn(ei):  # (E, C, D) -> (E, C, D), mixtral-style gated FFN
            import flax.linen as nn
            h = nn.silu(jnp.einsum("ecd,edf->ecf", ei, w_gate)) * \
                jnp.einsum("ecd,edf->ecf", ei, w_up)
            return jnp.einsum("ecf,efd->ecd", h, w_down)

        def chain(fn, x0):
            @jax.jit
            def run(xc):
                def body(i, xc):
                    return fn(xc).astype(xc.dtype)
                return jax.lax.fori_loop(0, n_iter, body, xc)
            float(run(x0).astype(jnp.float32).sum())
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                float(run(x0).astype(jnp.float32).sum())
                best = min(best, (time.perf_counter() - t0) / n_iter)
            return best

        ei = jax.random.normal(key, (E, cap, D), jnp.bfloat16)
        dt = chain(lambda v: experts_fn(v) * 1e-2, ei)
        gemm_flops = 6 * E * cap * D * F
        res["experts_gemm_ms"] = round(1e3 * dt, 2)
        res["experts_gemm_mfu"] = round(gemm_flops / dt / peak, 3)

        def ragged_path(xc, ident):
            logits = xc.astype(jnp.float32) @ wg
            l_aux, gate_k, topk_idx, pos_k, kept, cap_ = topkgating_ragged(
                logits, K, CF, 8)
            fn = (lambda v: v) if ident else experts_fn
            return dispatch_combine_ragged(xc, gate_k, topk_idx, pos_k, kept,
                                           cap_, E, fn) * 1e-2 + xc * 0.99

        def einsum_path(xc, ident):
            logits = xc.astype(jnp.float32) @ wg
            l_aux, combine, dispatch, _ = topkgating(logits, K, CF, 8)
            fn = (lambda v: v) if ident else experts_fn
            return dispatch_combine(xc, combine, dispatch, fn) * 1e-2 + xc * 0.99

        res["ragged_identity_ms"] = round(1e3 * chain(
            lambda v: ragged_path(v, True), x), 2)
        res["einsum_identity_ms"] = round(1e3 * chain(
            lambda v: einsum_path(v, True), x), 2)
        res["ragged_full_ms"] = round(1e3 * chain(
            lambda v: ragged_path(v, False), x), 2)
        res["einsum_full_ms"] = round(1e3 * chain(
            lambda v: einsum_path(v, False), x), 2)

        # grouped-GEMM (megablox) path: sort + 3 grouped matmuls + combine.
        # Its floor is the same 3 matmuls at fixed even groups — the
        # dispatch-overhead ratio gmm_full/gmm_gemm is what the CUTLASS
        # moe_gemm kernel exists to minimize.
        from deepspeed_tpu.moe.sharded_moe import (dispatch_combine_gmm,
                                                   topkgating_ragged)
        from deepspeed_tpu.ops.pallas.grouped_gemm import grouped_gemm

        def grouped_fn(rows, gs):
            import flax.linen as nn
            h = nn.silu(grouped_gemm(rows, w_gate, gs)) * \
                grouped_gemm(rows, w_up, gs)
            return grouped_gemm(h, w_down, gs)

        def gmm_path(xc):
            logits = xc.astype(jnp.float32) @ wg
            _, gate_k, topk_idx, _, _, _ = topkgating_ragged(logits, K, CF, 8)
            return dispatch_combine_gmm(xc, gate_k, topk_idx, E,
                                        grouped_fn) * 1e-2 + xc * 0.99

        res["gmm_full_ms"] = round(1e3 * chain(gmm_path, x), 2)
        rows = jax.random.normal(key, (T * K, D), jnp.bfloat16)
        gs_even = jnp.full((E,), T * K // E, jnp.int32)
        dt = chain(lambda v: grouped_fn(v, gs_even) * 1e-2 + v * 0.99, rows)
        res["gmm_gemm_ms"] = round(1e3 * dt, 2)
        res["gmm_gemm_mfu"] = round(6 * T * K * D * F / dt / peak, 3)
        res["gmm_dispatch_overhead"] = round(
            res["gmm_full_ms"] / max(res["gmm_gemm_ms"], 1e-9), 3)
        print(json.dumps({"pieces": res}))

    if "grad" in phases:
        # fwd+bwd of the FULL MoE layer per dispatch impl, chained in one
        # process — isolates where the train-step gap lives (the bwd).
        from deepspeed_tpu.moe.layer import MoE
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (1, T, D), jnp.bfloat16)
        out = {}
        for impl in ("ragged", "gmm", "einsum"):
            moe = MoE(hidden_size=D, num_experts=E, k=K,
                      intermediate_size=F, capacity_factor=CF,
                      dtype=jnp.bfloat16, dispatch_impl=impl)
            params = moe.init({"params": jax.random.PRNGKey(0)}, x)["params"]
            n_iter = 16 if on_tpu else 2

            def step(p, v):
                def loss(p):
                    o, _ = moe.apply({"params": p}, v, mutable=["aux_loss"])
                    return (o.astype(jnp.float32) ** 2).mean()
                return jax.grad(loss)(p)

            @jax.jit
            def run(p, v):
                def body(i, p):
                    g = step(p, v)
                    return jax.tree_util.tree_map(
                        lambda a, b: (a - 1e-6 * b.astype(a.dtype)), p, g)
                return jax.lax.fori_loop(0, n_iter, body, p)
            r = run(params, x)
            jax.block_until_ready(r)
            float(jax.tree_util.tree_leaves(r)[0].astype(jnp.float32).sum())
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                r = run(params, x)
                float(jax.tree_util.tree_leaves(r)[0]
                      .astype(jnp.float32).sum())
                best = min(best, (time.perf_counter() - t0) / n_iter)
            out[impl] = {"ms": round(1e3 * best, 3)}
        print(json.dumps({"grad": out}))

    if "gmmtune" in phases:
        # time the FULL grouped FFN (3 grouped GEMMs, same-shape feedback —
        # the experts_gemm harness form) per candidate tiling
        import flax.linen as nn
        from deepspeed_tpu.ops.pallas.grouped_gemm import grouped_gemm
        key = jax.random.PRNGKey(0)
        rows = jax.random.normal(key, (T * K, D), jnp.bfloat16)
        w_up = jax.random.normal(key, (E, D, F), jnp.bfloat16) * 0.02
        w_gate = jax.random.normal(key, (E, D, F), jnp.bfloat16) * 0.02
        w_down = jax.random.normal(key, (E, F, D), jnp.bfloat16) * 0.02
        gs_even = jnp.full((E,), T * K // E, jnp.int32)
        n_iter = 32 if on_tpu else 2
        out = {}
        for tiling in ((512, 512, 512), (512, 1024, 1024),
                       (1024, 512, 512), (1024, 1024, 1024),
                       (512, 1024, 2048), (1024, 1024, 2048),
                       (2048, 1024, 2048)):
            def ffn(v, tiling=tiling):
                h = nn.silu(grouped_gemm(v, w_gate, gs_even, tiling=tiling)) \
                    * grouped_gemm(v, w_up, gs_even, tiling=tiling)
                o = grouped_gemm(h, w_down, gs_even, tiling=tiling)
                return (o * 1e-2 + v * 0.99).astype(v.dtype)

            @jax.jit
            def run(v, ffn=ffn):
                return jax.lax.fori_loop(0, n_iter,
                                         lambda i, v: ffn(v), v)
            try:
                float(run(rows).astype(jnp.float32).sum())
                best = 1e9
                for _ in range(3):
                    t0 = time.perf_counter()
                    float(run(rows).astype(jnp.float32).sum())
                    best = min(best, (time.perf_counter() - t0) / n_iter)
                out[str(tiling)] = {
                    "ms": round(1e3 * best, 3),
                    "mfu": round(6 * T * K * D * F / best / peak, 3)}
            except Exception as e:
                out[str(tiling)] = {"error": str(e)[:120]}
        print(json.dumps({"gmmtune": out}))

    if "train" in phases:
        print(json.dumps({"train": moe_train_proxy(on_tpu)}))

    if "ab" in phases:
        # dispatch impl A/B in ONE process (cross-process timings swing ±25%)
        for impl, policy in (("ragged", "checkpoint_dots"),
                             ("gmm", "checkpoint_dots"),
                             ("gmm", "checkpoint_dots_gmm")):
            row = moe_train_proxy(on_tpu, dispatch_impl=impl,
                                  remat_policy=policy)
            print(json.dumps({f"train_{impl}_{policy}": row}))


def moe_train_proxy(on_tpu: bool, peak_tflops: float = 197.0,
                    dispatch_impl: str = "auto",
                    remat_policy: str = "checkpoint_dots",
                    mbs: int = 4, gas: int = 16,
                    remat: bool = True) -> dict:
    """Train the qwen2-moe one-chip proxy (BASELINE driver config 4's
    stand-in) and return the measured row. ONE source of truth — bench.py's
    MoE row and this harness's 'train' phase both call it."""
    import json
    import time

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.qwen2_moe import (
        Qwen2MoeConfig, init_qwen2_moe, qwen2_moe_loss_fn)
    from deepspeed_tpu.utils import groups

    if on_tpu:
        # ~550M params (250M active): one-chip proxy for BASELINE driver
        # config 4 (Mixtral-8x7B ZeRO-2 EP); fp32 master+Adam for the full
        # expert set must fit HBM alongside bf16 params+grads
        cfg = Qwen2MoeConfig(
            vocab_size=32000, hidden_size=1024,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=2048,
            shared_expert_intermediate_size=2048,
            max_position_embeddings=2048, remat=remat,
            remat_policy=remat_policy, dispatch_impl=dispatch_impl,
            dtype=jnp.bfloat16)
        # mbs4 is the HBM ceiling (mbs6/8 OOM, r5). GAS16 amortizes the
        # ~36 ms/batch fixed cost (FusedAdam update over the FULL 552M
        # params + overflow reduce): 40.6% at GAS2 -> 45.7% GAS8 -> 46.4%
        # GAS16 (r5 one-process sweep)
        seq, steps, warmup = 2048, 4 if gas >= 8 else 8, 2
    else:
        cfg = Qwen2MoeConfig(
            vocab_size=512, hidden_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=64, shared_expert_intermediate_size=64,
            max_position_embeddings=128, remat=remat,
            remat_policy=remat_policy, dispatch_impl=dispatch_impl,
            dtype=jnp.float32)
        mbs, seq, steps, warmup, gas = min(mbs, 2), 64, 2, 1, min(gas, 2)

    import numpy as np
    groups.reset_topology()
    model, params, specs = init_qwen2_moe(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": mbs,
                "gradient_accumulation_steps": gas, "steps_per_print": 0,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": bool(on_tpu)},
                "zero_optimization": {"stage": 2}},
        loss_fn=qwen2_moe_loss_fn(model), base_param_specs=specs)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(gas * mbs, seq)).astype(np.int32)}
    for _ in range(warmup):
        engine.train_batch(batch=batch)
    jax.block_until_ready(engine.state)
    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready((engine.state, loss))
    dt = time.time() - t0
    tps = gas * mbs * seq * steps / dt
    # ACTIVE FLOPs/token: dense non-expert params + shared expert +
    # k-of-E routed experts (+ attention)
    n_total = engine.total_params
    expert_p = 3 * cfg.hidden_size * cfg.moe_intermediate_size * \
        cfg.num_experts * cfg.num_hidden_layers
    active = n_total - expert_p + expert_p * cfg.num_experts_per_tok \
        / cfg.num_experts
    fpt = 6.0 * active + 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = tps * fpt / 1e12 / peak_tflops if on_tpu else 0.0
    row = {"model": "qwen2moe-8x2048-proxy", "zero_stage": 2,
           "tokens_per_sec": round(tps, 1),
           "active_params_m": round(active / 1e6, 1),
           "total_params_m": round(n_total / 1e6, 1),
           "mfu_active": round(mfu, 4),
           "loss": round(float(loss), 4)}
    # free device state before whatever runs next
    engine.state = None
    engine._jit_cache.clear()
    del engine
    return row


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
