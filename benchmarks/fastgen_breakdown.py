"""FastGen serving-path breakdown (VERDICT r3 weak #1).

Splits the continuous-batching gap (499 decode tok/s vs 3594 plain decode)
into its parts on the real chip:

  gen      — instrumented generate(): per-compiled-program wall time + call
             counts (sync timing), host-side scheduling remainder.
  dispatch — warm dispatch latency of the decode-scan program: async submit
             time vs synced round-trip.
  kernels  — chained fori_loop micro-bench (CLAUDE.md method): paged decode
             kernel vs dense decode kernel vs XLA masked path vs the paged
             scatter (update_layer), at the serving shape.

Usage: python benchmarks/fastgen_breakdown.py [gen] [dispatch] [kernels]
                                              [--serve-mode=MODE]

--serve-mode routes the engine through a big-model serve mode
(dequant | layer_scan | capacity); the streamed modes quantize the tree
(quant enabled) and ride the dense 'slot' KV layout the engine forces.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.accelerator import on_tpu as _on_tpu
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, materialize_params
    from deepspeed_tpu.utils import groups

    serve_mode = None
    argv = []
    for a in sys.argv[1:]:
        if a.startswith("--serve-mode="):
            serve_mode = a.split("=", 1)[1]
        else:
            argv.append(a)
    phases = set(argv) or {"gen", "dispatch", "kernels"}
    on_tpu = _on_tpu()

    # Program ledger: every v2 serving program this harness compiles gets a
    # cost/memory/roofline row (captured at first dispatch — compile time,
    # not the timed loops). Diff across runs with
    # `python -m deepspeed_tpu.telemetry --diff-ledger old new`.
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    ledger_path = os.environ.get("DS_TPU_LEDGER_JSONL",
                                 "ledger_fastgen.jsonl")
    ledger = ledger_mod.set_ledger(
        ledger_mod.ProgramLedger(path=ledger_path, enabled=True))

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=4096, num_hidden_layers=24,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=2048, remat=False,
                          dtype=jnp.bfloat16)
        n_q, mb, msl, plen, new, blocks, chunk = 96, 64, 1024, 256, 64, 96, 256
    else:
        cfg = LlamaConfig(vocab_size=1024, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, remat=False,
                          dtype=jnp.float32)
        n_q, mb, msl, plen, new, blocks, chunk = 6, 4, 64, 12, 4, 6, 8

    rng = np.random.default_rng(0)
    groups.reset_topology()
    model, params = materialize_params(cfg)

    report = {}

    def make_engine():
        groups.reset_topology()
        kw = dict(max_batch=mb, max_seq_len=msl, split_fuse_chunk=chunk)
        if serve_mode in (None, "dequant"):
            kw.update(kv_layout="paged", num_cache_blocks=blocks)
        else:
            # streamed modes force the dense 'slot' layout and need a
            # quantized tree (layer_scan) / stream host slices (capacity)
            kw.update(quant={"enabled": True})
        if serve_mode is not None:
            kw.update(serve_mode=serve_mode)
        return InferenceEngineV2(model, params=params, **kw)

    prompts = [list(rng.integers(0, cfg.vocab_size, plen)) for _ in range(n_q)]

    if "gen" in phases:
        if os.environ.get("DS_BENCH_LOG_COMPILES"):
            jax.config.update("jax_log_compiles", True)
        stats = {}
        percall = {}

        class TimingDict(dict):
            def __setitem__(self, key, fn):
                @functools.wraps(fn)
                def wrapped(*a, **k):
                    t0 = time.perf_counter()
                    out = fn(*a, **k)
                    jax.block_until_ready(out)
                    dt = time.perf_counter() - t0
                    s = stats.setdefault(str(key), [0.0, 0])
                    s[0] += dt
                    s[1] += 1
                    percall.setdefault(str(key), []).append(round(dt, 3))
                    return out
                super().__setitem__(key, wrapped)

        v2 = make_engine()
        v2._jits = TimingDict()
        host = {}

        def wrap(obj, name):
            fn = getattr(obj, name)
            def wrapped(*a, **k):
                t0 = time.perf_counter()
                out = fn(*a, **k)
                host.setdefault(name, [0.0, 0])
                host[name][0] += time.perf_counter() - t0
                host[name][1] += 1
                return out
            setattr(obj, name, wrapped)
        for name in ("_flush_batch", "_maybe_sync_tables", "_reserve", "put"):
            wrap(v2, name)
        v2.generate(prompts[:4], max_new_tokens=new)  # compile
        stats.clear()
        host.clear()
        t0 = time.perf_counter()
        v2.generate(prompts, max_new_tokens=new)
        wall = time.perf_counter() - t0
        dispatch_total = sum(s[0] for s in stats.values())
        report["gen"] = {
            "wall_s": round(wall, 3),
            "decode_tok_s": round(n_q * new / wall, 1),
            "dispatch_s": round(dispatch_total, 3),
            "host_s": round(wall - dispatch_total, 3),
            "programs": {k: {"s": round(s[0], 3), "calls": s[1],
                             "ms_per_call": round(1e3 * s[0] / s[1], 1),
                             "per_call": percall[k]}
                         for k, s in sorted(stats.items())},
            "host_sections": {k: {"s": round(s[0], 3), "calls": s[1]}
                              for k, s in sorted(host.items())},
        }
        v2.cache = None
        del v2

    if "dispatch" in phases:
        v2 = make_engine()
        # warm the decode-scan program via a tiny generate
        v2.generate(prompts[:4], max_new_tokens=new)
        k = 16 if on_tpu else 4
        fn = v2._decode_scan_fn(k)
        tokens = jnp.zeros((mb, 1), jnp.int32)
        active = jnp.ones((mb,), bool)
        # park all cursors at 256 so steps write in-bounds
        v2.cache = v2.cache.replace(
            index=jnp.full((mb,), plen, jnp.int32))
        if v2.kv_layout == "paged":
            v2._tables_np[:] = np.arange(
                mb * v2._tables_np.shape[1]).reshape(mb, -1) % blocks
            v2._tables_dirty = True
            v2._maybe_sync_tables()
        rng = jax.random.PRNGKey(0)
        fold = jnp.asarray(v2._slot_uids, jnp.int32)
        cache, toks = fn(v2.params, v2.cache, tokens, active, rng, fold)
        jax.block_until_ready(toks)
        reps = 6
        # synced round-trips
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            cache, toks = fn(v2.params, cache, tokens, active, rng, fold)
            jax.block_until_ready(toks)
            ts.append(time.perf_counter() - t0)
        # async submit cost (dispatch only)
        t0 = time.perf_counter()
        for _ in range(reps):
            cache, toks = fn(v2.params, cache, tokens, active, rng, fold)
        submit = (time.perf_counter() - t0) / reps
        jax.block_until_ready(toks)
        report["dispatch"] = {
            "decode_scan_k": k,
            "sync_ms": round(1e3 * float(np.median(ts)), 1),
            "per_token_ms": round(1e3 * float(np.median(ts)) / k, 2),
            "async_submit_ms": round(1e3 * submit, 1),
        }
        # measured wall onto the scan program's ledger row (the engine's
        # _track owns the name — streamed modes carry an @serve_mode
        # suffix, int8 caches @kv_int8)
        ledger.observe_measured(f"v2:{fn._ds_program}",
                                1e3 * float(np.median(ts)))
        v2.cache = None
        del v2

    if "kernels" in phases:
        from deepspeed_tpu.ops.attention import reference_attention
        from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention)

        L = 1  # single layer shapes; model has 24 of these per step
        hkv = cfg.num_key_value_heads
        h = cfg.num_attention_heads
        d = cfg.head_dim
        bs = 256 if on_tpu else 16
        t = msl // bs
        length = plen + new  # 320: the serving steady state
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (mb, 1, h, d), cfg.dtype)
        k_pool = jax.random.normal(key, (hkv, blocks, bs, d), cfg.dtype)
        v_pool = jax.random.normal(key, (hkv, blocks, bs, d), cfg.dtype)
        # realistic tables: each row owns ceil(length/bs) blocks
        own = -(-length // bs)
        tables = np.full((mb, t), -1, np.int32)
        nxt = 0
        for b in range(mb):
            for j in range(own):
                tables[b, j] = nxt % blocks
                nxt += 1
        tables = jnp.asarray(tables)
        lengths = jnp.full((mb,), length, jnp.int32)

        k_dense = jax.random.normal(key, (mb, msl, hkv, d), cfg.dtype)
        v_dense = jax.random.normal(key, (mb, msl, hkv, d), cfg.dtype)
        mask = (jnp.arange(msl)[None, None, :] <
                lengths[:, None, None])

        # big enough that one dispatch + fetch per run() is noise per-iter
        n_iter = 512 if on_tpu else 2

        def chain(fn):
            @jax.jit
            def run(q0):
                def body(i, q):
                    o = fn(q)
                    return o.astype(q.dtype)
                return jax.lax.fori_loop(0, n_iter, body, q0)
            run(q).block_until_ready()  # compile
            t0 = time.perf_counter()
            run(q).block_until_ready()
            return 1e3 * (time.perf_counter() - t0) / n_iter

        res = {}
        res["paged_kernel_ms"] = round(chain(
            lambda q: paged_decode_attention(q, k_pool, v_pool, tables,
                                             lengths)), 3)
        res["dense_kernel_ms"] = round(chain(
            lambda q: decode_attention(q, k_dense, v_dense, lengths)), 3)
        res["xla_masked_ms"] = round(chain(
            lambda q: reference_attention(q, k_dense, v_dense, causal=False,
                                          segment_mask=mask)), 3)

        # the paged scatter (update_layer) at decode shape
        from deepspeed_tpu.inference.kv_cache import (PagedLayer,
                                                      _update_paged_layer)
        layer = PagedLayer(pool=k_pool, tables=tables)
        kn = jax.random.normal(key, (mb, 1, hkv, d), cfg.dtype)

        @jax.jit
        def scat(layer, kn):
            def body(i, lay):
                return _update_paged_layer(lay, kn, lengths)
            return jax.lax.fori_loop(0, n_iter, body, layer)
        scat(layer, kn).pool.block_until_ready()
        t0 = time.perf_counter()
        scat(layer, kn).pool.block_until_ready()
        res["paged_scatter_ms"] = round(
            1e3 * (time.perf_counter() - t0) / n_iter, 3)

        # dense scatter comparison
        @jax.jit
        def scat_d(kc, kn):
            def body(i, kc):
                rows = jnp.arange(mb)[:, None]
                cols = lengths[:, None] + jnp.arange(1)[None, :]
                return kc.at[rows, cols].set(kn, mode="drop")
            return jax.lax.fori_loop(0, n_iter, body, kc)
        scat_d(k_dense, kn).block_until_ready()
        t0 = time.perf_counter()
        scat_d(k_dense, kn).block_until_ready()
        res["dense_scatter_ms"] = round(
            1e3 * (time.perf_counter() - t0) / n_iter, 3)
        report["kernels"] = res

    if "prefill" in phases:
        # Isolate the chunk_batch program's pieces at serving shape.
        import jax
        from deepspeed_tpu.inference.kv_cache import (PagedLayer,
                                                      _update_paged_layer)
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_prefill_attention)
        res = {}
        hkv = cfg.num_key_value_heads
        h = cfg.num_attention_heads
        d = cfg.head_dim
        bs = 256 if on_tpu else 16
        t = msl // bs
        key = jax.random.PRNGKey(0)
        C = chunk
        q = jax.random.normal(key, (mb, C, h, d), cfg.dtype)
        k_pool = jax.random.normal(key, (hkv, blocks, bs, d), cfg.dtype)
        v_pool = jax.random.normal(key, (hkv, blocks, bs, d), cfg.dtype)
        tables = jnp.asarray(
            (np.arange(mb * t).reshape(mb, t) % blocks).astype(np.int32))
        starts = jnp.zeros((mb,), jnp.int32)
        n_iter = 64 if on_tpu else 2

        @jax.jit
        def pf_chain(q0):
            def body(i, q):
                return paged_prefill_attention(q, k_pool, v_pool, tables,
                                               starts).astype(q.dtype)
            return jax.lax.fori_loop(0, n_iter, body, q0)
        pf_chain(q).block_until_ready()
        t0 = time.perf_counter()
        pf_chain(q).block_until_ready()
        res["prefill_kernel_ms"] = round(
            1e3 * (time.perf_counter() - t0) / n_iter, 3)

        kn = jax.random.normal(key, (mb, C, hkv, d), cfg.dtype)
        layer = PagedLayer(pool=k_pool, tables=tables)
        for name, idx in (("chunk_scatter_aligned_ms", starts),
                          ("chunk_scatter_misaligned_ms",
                           jnp.full((mb,), 3, jnp.int32))):
            @jax.jit
            def sc_chain(lay, kn, idx=idx):
                def body(i, lay):
                    return _update_paged_layer(lay, kn, idx)
                return jax.lax.fori_loop(0, n_iter, body, lay)
            sc_chain(layer, kn).pool.block_until_ready()
            t0 = time.perf_counter()
            sc_chain(layer, kn).pool.block_until_ready()
            res[name] = round(1e3 * (time.perf_counter() - t0) / n_iter, 3)

        # the whole chunk_batch program, sync-timed warm, vs a plain
        # full-model forward on the same token count (the compute floor)
        v2 = make_engine()
        v2._tables_np[:] = np.asarray(tables)
        v2._tables_dirty = True
        v2._maybe_sync_tables()
        fn = v2._chunk_batch_fn()
        ids = jnp.zeros((mb, C), jnp.int32)
        slots = jnp.arange(mb, dtype=jnp.int32)
        valids = jnp.full((mb,), C, jnp.int32)
        cache, last = fn(v2.params, v2.cache, ids, slots, starts, valids)
        jax.block_until_ready(last)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            cache, last = fn(v2.params, cache, ids, slots, starts, valids)
            jax.block_until_ready(last)
            ts.append(time.perf_counter() - t0)
        res["chunk_batch_sync_ms"] = round(1e3 * float(np.median(ts)), 1)

        model_fwd = jax.jit(lambda p, i: model.apply({"params": p}, i))
        logits = model_fwd(v2.params, ids)
        jax.block_until_ready(logits)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            logits = model_fwd(v2.params, ids)
            jax.block_until_ready(logits)
            ts.append(time.perf_counter() - t0)
        res["plain_fwd_same_tokens_ms"] = round(1e3 * float(np.median(ts)), 1)
        report["prefill"] = res

    report["serve_mode"] = serve_mode or "dequant"
    report["ledger"] = {"path": ledger_path,
                        "programs": ledger.programs()}
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
