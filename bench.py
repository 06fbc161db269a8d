"""Benchmark: flagship Llama-style causal-LM training step on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric = model FLOPs utilization (MFU) of a bf16 train step (fwd+bwd+Adam),
vs_baseline = MFU / 0.45 (the BASELINE.md north-star: ZeRO-3 Llama at >=45%
MFU, which itself mirrors DeepSpeed-Ulysses' >54%-of-peak A100 claim).

Measures on a TPU only: with any other default backend it exits non-zero
before doing work. `--rehearsal` runs the same control flow off the chip at
a toy size to check paths and arguments; it reports no rate, MFU or
utilization (those fields are null and `"rehearsal": true`). A phase that
raises fails the run.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def fastgen_sla_detail(last_timing, n_q, dt, plen, new, mb, blocks):
    """FastGen effective-throughput accounting (reference
    blogs/deepspeed-fastgen/README.md:163): a query COUNTS only if it met
    the SLA — first-token latency <= max(2 s, 3 s per 512 prompt tokens)
    and a per-query generation rate >= 4 tok/s. Queries missing their
    'first'/'done' stamps are SLA MISSES in the denominator (they were
    admitted but never served to completion), not silently dropped."""
    ok, ftls, rates, tpots, unstamped = 0, [], [], [], 0
    for uid, rec in last_timing.items():
        if "done" not in rec or "first" not in rec:
            unstamped += 1
            continue
        # TTFT from SUBMISSION (all queries arrive at t_start=0, the
        # reference accounting) — queue wait in `pending` counts
        ftl = rec["first"]
        ftls.append(ftl)
        ftl_ok = ftl <= max(2.0, 3.0 * plen / 512)
        if rec["new_tokens"] > 1 and rec["done"] - rec["first"] > 1e-6:
            rate = (rec["new_tokens"] - 1) / (rec["done"] - rec["first"])
            rates.append(rate)
            tpots.append(1.0 / rate)
            ok += ftl_ok and rate >= 4.0
        else:
            # single-token query (immediate eos) or zero-width generation
            # window (all tokens in one stamp): no rate to measure — SLA
            # reduces to the first-token bound
            ok += ftl_ok
    ftls.sort()
    rates.sort()
    tpots.sort()
    total = len(last_timing)  # stamped AND unstamped queries
    pct = lambda a, q: a[min(len(a) - 1, int(q * len(a)))] if a else None
    return {"queries_per_sec": round(n_q / dt, 2),
            "effective_qps_at_sla": round(ok / dt, 2),
            "sla": "first_token<=max(2s,3s/512tok), gen>=4tok/s",
            "sla_met_pct": round(100.0 * ok / max(total, 1), 1),
            "sla_unstamped": unstamped,
            "first_token_p50_s": round(pct(ftls, 0.5), 3)
            if ftls else None,
            "first_token_p95_s": round(pct(ftls, 0.95), 3)
            if ftls else None,
            "gen_tok_s_p50": round(pct(rates, 0.5), 1)
            if rates else None,
            # SLA percentiles in ms (round-over-round comparable; same
            # stamps the engine's RequestTracer feeds its histograms)
            "ttft_p50_ms": round(pct(ftls, 0.5) * 1e3, 1)
            if ftls else None,
            "ttft_p99_ms": round(pct(ftls, 0.99) * 1e3, 1)
            if ftls else None,
            "tpot_p50_ms": round(pct(tpots, 0.5) * 1e3, 2)
            if tpots else None,
            "decode_tokens_per_sec": round(n_q * new / dt, 1),
            "batch_slots": mb, "prompt_len": plen,
            "new_tokens": new, "cache_blocks": blocks}


def _ledger_round() -> int:
    """This run's round number for the ledger filename: DS_TPU_BENCH_ROUND
    when set, else one past the newest BENCH_rXX.json / ledger_rXX.jsonl
    already on disk (the driver archives one per round)."""
    env = os.environ.get("DS_TPU_BENCH_ROUND")
    if env:
        return int(env)
    import glob
    import re
    rounds = [0]
    for pattern, rx in (("BENCH_r*.json", r"BENCH_r(\d+)\.json$"),
                        ("ledger_r*.jsonl", r"ledger_r(\d+)\.jsonl$")):
        for p in glob.glob(pattern):
            m = re.match(rx, os.path.basename(p))
            if m:
                rounds.append(int(m.group(1)))
    return max(rounds) + 1


def _previous_ledger(round_n: int):
    """Newest ledger_rXX.jsonl with XX < round_n, or None."""
    import glob
    import re
    best = None
    for p in glob.glob("ledger_r*.jsonl"):
        m = re.match(r"ledger_r(\d+)\.jsonl$", os.path.basename(p))
        if m and int(m.group(1)) < round_n:
            if best is None or int(m.group(1)) > best[0]:
                best = (int(m.group(1)), p)
    return best[1] if best else None


def _registered_tiers():
    """Registered residency per tier at this instant (MemoryPlane; for a
    serving/train phase this is also the phase's registered peak — the
    engine's registrations are monotone within one phase)."""
    from deepspeed_tpu.telemetry.memory import get_plane
    return {t: b for t, b in get_plane().tier_totals().items() if b}


def _phase_mem(telemetry, phase, start_hbm):
    """End-of-phase residency bookkeeping: a memory_snapshot at the phase
    boundary (→ per-tier counter tracks in the trace), then the
    cross-phase leak check — more registered HBM at phase end than start
    means an engine's allocations outlived its teardown (the bench
    phase-order OOM lesson, made mechanical). Returns the end-of-phase
    registered HBM bytes (the next phase's baseline)."""
    import gc

    from deepspeed_tpu.telemetry.memory import get_plane
    gc.collect()  # engines sit in ref cycles; owners release via finalizer
    plane = get_plane()
    plane.emit_snapshot(f"bench:{phase}")
    end = plane.total("hbm")
    if end > start_hbm:
        telemetry.emit("residency_leak", phase=phase,
                       leak_bytes=end - start_hbm,
                       start_bytes=start_hbm, end_bytes=end)
    return end


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="off the chip: run the control flow at a toy size "
                         "and report no device metric")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator, on_tpu as _on_tpu
    from deepspeed_tpu.models.llama import (
        LlamaConfig, init_params_and_specs, llama_loss_fn, materialize_params)
    from deepspeed_tpu.utils import groups

    platform = jax.devices()[0].platform
    on_tpu = _on_tpu()
    if on_tpu == args.rehearsal:
        sys.exit(f"bench.py: default backend is {platform!r}; it measures on "
                 "a TPU only (--rehearsal checks the control flow off the "
                 "chip and is refused on it)")

    if on_tpu:
        # ~470M-param model: fits one v5e chip with fp32 master+Adam state.
        # mbs=2 + GAS=8 (same 16x2048-token global batch as the old mbs=4
        # GAS=4) lets the 'checkpoint_dots' remat policy fit — matmul
        # outputs saved, no MXU recompute in backward: 59.5% MFU vs 54.1%
        # with whole-block remat (v5e sweep, round 2).
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=4096,
                          num_hidden_layers=24, num_attention_heads=8,
                          num_key_value_heads=8, max_position_embeddings=2048,
                          remat=True, remat_policy="checkpoint_dots",
                          dtype=jnp.bfloat16)
        mbs, seq, steps, warmup = 2, 2048, 10, 2
    else:  # --rehearsal: control flow only, nothing below is a measurement
        cfg = LlamaConfig(vocab_size=1024, hidden_size=128, intermediate_size=256,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=4, max_position_embeddings=256,
                          remat=False, dtype=jnp.float32)
        mbs, seq, steps, warmup = 2, 128, 3, 1

    gas = 8 if on_tpu else 2
    groups.reset_topology()
    model, params = materialize_params(cfg)
    _, specs = init_params_and_specs(cfg)
    # The measured program is the program the framework sells (VERDICT r1
    # item 10): ZeRO stage 3 + gradient accumulation, fused train_batch.
    # On one chip the ZeRO shardings are degenerate (dp=1) but the compiled
    # step is the stage-3 graph.
    # Telemetry JSONL next to the bench output (summarize with
    # `python -m deepspeed_tpu.telemetry --summarize <path>`). flush_every=0
    # → the timed loop defers device fetches entirely; one batched fetch
    # happens at the explicit flush below, so the headline MFU pays zero
    # extra round-trips.
    tele_path = os.environ.get("DS_TPU_TELEMETRY_JSONL",
                               "bench_telemetry.jsonl")
    # Program ledger (telemetry/ledger.py): every phase's compiled programs
    # captured at compile time into ledger_rXX.jsonl next to the JSON line;
    # the diff vs the previous round's ledger runs automatically below, so
    # a per-program perf drift is a red line in every round's bench output.
    # DS_TPU_BENCH_LEDGER=0 skips (saves one extra AOT compile/program).
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    ledger = None
    round_n = _ledger_round()
    ledger_path = f"ledger_r{round_n:02d}.jsonl"
    if os.environ.get("DS_TPU_BENCH_LEDGER", "1") != "0":
        open(ledger_path, "w").close()  # fresh file per run
        ledger = ledger_mod.set_ledger(
            ledger_mod.ProgramLedger(path=ledger_path, enabled=True))
    ds_config = {
        "train_micro_batch_size_per_gpu": mbs,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 0,
        "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": bool(on_tpu)},
        "zero_optimization": {"stage": 3},
        "telemetry": {"enabled": True, "jsonl_path": tele_path,
                      "flush_every": 0},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config,
        loss_fn=llama_loss_fn(model), base_param_specs=specs)

    n_params = engine.total_params
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(gas * mbs, seq)).astype(np.int32)}

    for _ in range(warmup):
        engine.train_batch(batch=batch)
    jax.block_until_ready(engine.state)
    # DS_TPU_TRACE=<dir> → perfetto trace of the timed loop (phases
    # annotated ds:train_batch / ds:fetch), one flag away for any run
    import contextlib
    trace_dir = os.environ.get("DS_TPU_TRACE")
    with engine.trace(trace_dir) if trace_dir else contextlib.nullcontext():
        t0 = time.time()
        for _ in range(steps):
            loss = engine.train_batch(batch=batch)
        jax.block_until_ready((engine.state, loss))
        dt = time.time() - t0

    tokens_per_s = gas * mbs * seq * steps / dt
    # fwd+bwd FLOPs/token: 6N dense + causal attention 6*L*d*s (12*L*d*s/2).
    flops_per_token = 6.0 * n_params + 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    achieved_tflops = tokens_per_s * flops_per_token / 1e12
    # off the chip there is no peak to divide by and no rate worth the name
    peak = get_accelerator().peak_tflops("bfloat16") if on_tpu else None
    mfu = achieved_tflops / peak if on_tpu else None
    loss_f = float(loss)

    # One batched fetch of the deferred per-step metrics + a phase summary
    # row (step time / MFU / memory — the summarizer's headline fields).
    telemetry = engine.telemetry
    telemetry.flush()
    mem = telemetry.memory_event()
    # Registered residency per phase (MemoryPlane): captured at phase end
    # BEFORE teardown (= the phase's registered peak), reported in the
    # detail JSON; _phase_mem after each teardown runs the cross-phase
    # leak check.
    residency_by_phase = {"train_flagship": _registered_tiers()}
    telemetry.emit("bench_phase", phase="train_flagship",
                   step_time_s=round(dt / steps, 4),
                   mfu=round(mfu, 4) if on_tpu else None,
                   tokens_per_sec=round(tokens_per_s, 1), loss=loss_f,
                   peak_hbm_gb=mem.get("peak_hbm_gb"),
                   registered_bytes_by_tier=residency_by_phase[
                       "train_flagship"])
    if ledger is not None:
        # measured step time onto the fused train program's ledger row →
        # its measured-vs-roofline / MFU-gap fields
        ledger.observe_measured("train:train_batch", 1e3 * dt / steps)

    # HBM hygiene: each phase frees its predecessor's device state (the
    # training engine's fp32 master+moments alone are ~5.6 GB; stacking
    # phases OOMs the chip). Inference phases keep only the bf16 params.
    infer_params = engine.state.params
    engine.state = None
    engine._jit_cache.clear()
    del engine, params
    hbm_floor = _phase_mem(telemetry, "train_flagship", 0)

    # Decode throughput of the same model through the inference engine
    # (config-3 slot: tokens/s, greedy, KV-cache decode loop).
    decode_tok_s = None
    engine_inf = deepspeed_tpu.init_inference(
        model, params=infer_params, dtype="bf16" if on_tpu else "fp32")
    gen_b, gen_s, gen_new = (32, 128, 128) if on_tpu else (2, 16, 8)
    ids = rng.integers(0, cfg.vocab_size, size=(gen_b, gen_s))
    engine_inf.generate(ids, max_new_tokens=gen_new)  # compile
    t0 = time.time()
    engine_inf.generate(ids, max_new_tokens=gen_new)
    decode_tok_s = gen_b * gen_new / (time.time() - t0)
    residency_by_phase["decode"] = _registered_tiers()
    engine_inf.cache = None
    del engine_inf
    hbm_floor = _phase_mem(telemetry, "decode", hbm_floor)

    # Speculative decode on the same model/params (self-draft, greedy —
    # lossless, so tok/s is directly comparable to the vanilla row above).
    # Detail keys are config-free on purpose (the r2 naming lesson): draft
    # depth is a VALUE, so the best k can move between rounds without
    # breaking the row. Ledger rows (v1:spec:*) are captured by the engine.
    spec_decode = None
    from deepspeed_tpu.utils import groups as _groups
    _groups.reset_topology()
    spec_k = 4
    eng_spec = deepspeed_tpu.init_inference(
        model, params=infer_params, dtype="bf16" if on_tpu else "fp32",
        speculative={"enabled": True, "k": spec_k})
    eng_spec.generate(ids, max_new_tokens=gen_new)  # compile
    t0 = time.time()
    eng_spec.generate(ids, max_new_tokens=gen_new)
    spec_tok_s = gen_b * gen_new / (time.time() - t0)
    acc = eng_spec._spec.last_acceptance_rate
    spec_decode = {
        "tokens_per_sec": round(spec_tok_s, 1),
        "speedup_vs_vanilla": round(spec_tok_s / decode_tok_s, 3)
        if decode_tok_s else None,
        "acceptance_rate": round(acc, 4) if acc is not None else None,
        "spec_k": spec_k,
    }
    residency_by_phase["spec_decode"] = _registered_tiers()
    eng_spec.cache = None
    del eng_spec
    hbm_floor = _phase_mem(telemetry, "spec_decode", hbm_floor)

    # int8-at-rest KV decode on the same model/params (dequant serve mode,
    # docs/kv_cache.md): per-(head, slot) scales quantized in the cache
    # write, dequantized in-register by the attention kernels. Cache dtype
    # is a VALUE in the row, never part of the metric name (the r1/r2
    # naming lesson) — if the best at-rest dtype changes, the row survives.
    kv_int8_decode = None
    from deepspeed_tpu.utils import groups as _groups
    _groups.reset_topology()
    eng_kv = deepspeed_tpu.init_inference(
        model, params=infer_params, dtype="bf16" if on_tpu else "fp32",
        kv_cache_dtype="int8")
    eng_kv.generate(ids, max_new_tokens=gen_new)  # compile
    t0 = time.time()
    eng_kv.generate(ids, max_new_tokens=gen_new)
    kv_tok_s = gen_b * gen_new / (time.time() - t0)
    from deepspeed_tpu.inference.capacity_scan import (kv_cache_bytes,
                                                       round_up_len)
    ml = round_up_len(gen_s + gen_new)
    kv_int8_decode = {
        "kv_dtype": "int8",
        "tokens_per_sec": round(kv_tok_s, 1),
        "speedup_vs_dense_kv": round(kv_tok_s / decode_tok_s, 3)
        if decode_tok_s else None,
        "kv_bytes": kv_cache_bytes(cfg, gen_b, ml, eng_kv._config.dtype,
                                   kv_dtype="int8"),
        "kv_bytes_dense": kv_cache_bytes(cfg, gen_b, ml,
                                         eng_kv._config.dtype),
    }
    residency_by_phase["kv_int8_decode"] = _registered_tiers()
    eng_kv.cache = None
    del eng_kv
    hbm_floor = _phase_mem(telemetry, "kv_int8_decode", hbm_floor)

    # FastGen-analog continuous batching (BASELINE FastGen rows: queries/s
    # at scale): paged KV cache, mixed prefill/decode, more queries than
    # slots so sequences join/leave continuously.
    fastgen = None
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    groups.reset_topology()
    if on_tpu:
        # pool budgeted to tokens in flight (the paged layout's point):
        # 64 slots × 320-token worst case = 80 blocks @256, + headroom
        n_q, mb, msl, plen, new, blocks = 96, 64, 1024, 256, 64, 96
    else:
        n_q, mb, msl, plen, new, blocks = 6, 4, 64, 12, 4, None
    v2 = InferenceEngineV2(model, params=infer_params,
                           max_batch=mb, max_seq_len=msl,
                           kv_layout="paged", num_cache_blocks=blocks,
                           split_fuse_chunk=256 if on_tpu else 8)
    prompts = [list(rng.integers(0, cfg.vocab_size, plen))
               for _ in range(n_q)]
    # compile warmup with the FULL workload: the chunk-batch and scan
    # programs bucket by batch width, so a narrow warmup leaves the
    # wide buckets to compile inside the timed run (~1.5 s spikes that
    # read as first-token latency)
    v2.generate(prompts, max_new_tokens=new)
    t0 = time.time()
    v2.generate(prompts, max_new_tokens=new)
    dt = time.time() - t0
    # Tokens are stamped at host materialization (wave end for
    # scan-decoded tokens), so the scan's latency cost is charged,
    # not hidden. Unstamped queries count as SLA misses (ADVICE r5).
    fastgen = fastgen_sla_detail(v2.last_timing, n_q, dt, plen, new,
                                 mb, blocks)
    fastgen["kv_util_peak"] = round(v2._kv_util_peak, 4)
    fastgen["pinned_recompiles"] = v2.recompiles.pinned_misses
    # serve_mode / kv_dtype ride as VALUES (the r2 lesson: keys that
    # bake the config break the round-over-round diff when the best
    # config changes)
    fastgen["serve_mode"] = v2.serve_mode
    fastgen["kv_dtype"] = v2.telemetry_snapshot()["kv_dtype"]
    residency_by_phase["fastgen"] = _registered_tiers()
    v2.cache = None
    del v2
    del infer_params
    hbm_floor = _phase_mem(telemetry, "fastgen", hbm_floor)

    # Decode-kernel micro table (VERDICT r3 item 1: the paged-vs-dense
    # proof belongs in BENCH detail). Live chained-loop measurement at the
    # serving shape — ms per LAYER per decode step. DS_BENCH_SKIP_KMICRO=1
    # skips (saves ~2 min of compiles).
    kernel_micro = None
    if on_tpu and not os.environ.get("DS_BENCH_SKIP_KMICRO"):
        from deepspeed_tpu.ops.attention import reference_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            decode_attention)
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention)
        kB, khkv, kd, kbs, kt, knb, klen = 64, 8, 128, 256, 4, 96, 320
        kkey = jax.random.PRNGKey(0)
        kq = jax.random.normal(kkey, (kB, 1, khkv, kd), jnp.bfloat16)
        kpool = jax.random.normal(kkey, (khkv, knb, kbs, kd), jnp.bfloat16)
        ktab = jnp.asarray((np.arange(kB * kt).reshape(kB, kt) % knb)
                           .astype(np.int32))
        klens = jnp.full((kB,), klen, jnp.int32)
        kdense = jax.random.normal(kkey, (kB, kt * kbs, khkv, kd),
                                   jnp.bfloat16)
        kmask = jnp.arange(kt * kbs)[None, None, :] < klens[:, None, None]
        kn = 512  # chained iterations per timing

        def _chain(fn):
            @jax.jit
            def run(q0):
                return jax.lax.fori_loop(
                    0, kn, lambda i, qq: fn(qq).astype(qq.dtype), q0)
            float(run(kq).astype(jnp.float32).sum())
            t0 = time.time()
            float(run(kq).astype(jnp.float32).sum())
            return round(1e3 * (time.time() - t0) / kn, 3)

        kernel_micro = {
            "method": "chained fori_loop, ms/layer at B=64 Hkv=8 "
                      "ctx=320/1024",
            "paged_decode_kernel_ms": _chain(
                lambda q: paged_decode_attention(q, kpool, kpool, ktab,
                                                 klens)),
            "dense_decode_kernel_ms": _chain(
                lambda q: decode_attention(q, kdense, kdense, klens)),
            "xla_masked_decode_ms": _chain(
                lambda q: reference_attention(q, kdense, kdense,
                                              causal=False,
                                              segment_mask=kmask)),
        }
        if ledger is not None:
            # ms/layer onto per-kernel ledger rows — the r4→r5 paged
            # 0.46→0.91 ms drift becomes a --diff-ledger red line
            for kname, kv in kernel_micro.items():
                if kname != "method" and kv is not None:
                    ledger.observe_measured(f"kernel:{kname[:-3]}", kv)
        del kq, kpool, ktab, klens, kdense, kmask  # free before MoE

    # MoE row (BASELINE driver config 4's single-chip proxy: qwen2-moe
    # shapes, ZeRO-2, ep degenerate on one chip). MFU is ACTIVE-param MFU
    # (top-k routing: only k/E of expert FLOPs run per token).
    # DS_BENCH_SKIP_MOE=1 skips. Kernel decision data (r5, v5e, chained
    # loops — benchmarks/moe_breakdown.py): the megablox grouped GEMM
    # closes the fwd dispatch overhead to 1.065x (gmm_full 2.79 ms vs
    # ragged 3.35 ms), but its bwd kernels lose the TRAIN step 1.03-1.04x,
    # so training keeps the ragged buffer dispatch and 'auto' reserves
    # gmm for off-mesh inference; the train row's r5 gain (41.4→46.2%)
    # is GAS16 amortizing the ~36 ms/batch whole-tree optimizer cost.
    moe = None
    if on_tpu and not os.environ.get("DS_BENCH_SKIP_MOE"):
        from benchmarks.moe_breakdown import moe_train_proxy
        moe = moe_train_proxy(True, peak_tflops=peak)

    # FPDT long-context row (BASELINE config 5 / VERDICT r2 #3): 128k ctx
    # on ONE chip via host-offloaded residuals + chunked FFN/CE, optimizer
    # state device-resident. DS_BENCH_SKIP_LONGCTX=1 skips (saves ~4 min).
    long_ctx = None
    if on_tpu and not os.environ.get("DS_BENCH_SKIP_LONGCTX"):
        from deepspeed_tpu.utils import groups
        seq_l = 131072
        groups.reset_topology()
        lcfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=4096,
            num_hidden_layers=24, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=seq_l,
            remat=True, remat_policy="host_offload",
            loss_chunk_size=2048, mlp_chunk_size=16384,
            dtype=jnp.bfloat16)
        lmodel, lparams = materialize_params(lcfg)
        _, lspecs = init_params_and_specs(lcfg)
        # Optimizer state DEVICE-resident (r4 sweep,
        # benchmarks/longctx_sweep.py): the fp32 master+moments (~5.6
        # GB) fit beside the 128k activations, and dropping the host
        # Adam step buys 52.3% -> 53.5% MFU. The sweep also showed the
        # residual offload is fully overlapped (all-HBM residuals at
        # 64k are NOT faster once the host-step delta is removed) and
        # mlp/ce chunk sizes are flat — the remaining gap to the
        # kernel's own 80% fwd+bwd MFU is the whole-block remat's
        # dense recompute, which cannot be saved at this context
        # length (S-proportional dot outputs OOM HBM). r5 closed the
        # question by measurement: offloading the named dense
        # intermediates to pinned host instead (host_offload_dense*)
        # REGRESSES 48.1% -> 39.9%/23.8% at 32k — PCIe cannot stage
        # the ~75 GB of saves the recompute replaces, so at 16 GB HBM
        # the dense re-fwd is the information-theoretic optimum; the
        # reference FPDT >55% figure rides 80 GB parts.
        lengine, *_ = deepspeed_tpu.initialize(
            model=lmodel, model_parameters=lparams,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 0,
                    "optimizer": {"type": "FusedAdam",
                                  "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 3}},
            loss_fn=llama_loss_fn(lmodel), base_param_specs=lspecs)
        lb = {"input_ids": rng.integers(
            0, 32000, size=(1, seq_l)).astype(np.int32)}
        lengine.train_batch(batch=lb)
        jax.block_until_ready(lengine.state)
        t0 = time.time()
        lsteps = 2
        for _ in range(lsteps):
            lloss = lengine.train_batch(batch=lb)
        jax.block_until_ready((lengine.state, lloss))
        ldt = time.time() - t0
        ltok = seq_l * lsteps / ldt
        lfpt = 6.0 * lengine.total_params + \
            6.0 * lcfg.num_hidden_layers * lcfg.hidden_size * seq_l
        long_ctx = {"seq_len": seq_l,
                    "tokens_per_sec": round(ltok, 1),
                    "mfu": round(ltok * lfpt / 1e12 / peak, 4)}
        residency_by_phase["long_ctx"] = _registered_tiers()
        telemetry.emit("bench_phase", phase="long_ctx",
                       step_time_s=round(ldt / lsteps, 4),
                       mfu=long_ctx["mfu"],
                       tokens_per_sec=long_ctx["tokens_per_sec"],
                       registered_bytes_by_tier=residency_by_phase[
                           "long_ctx"])
        lengine.state = None
        del lengine, lparams
        hbm_floor = _phase_mem(telemetry, "long_ctx", hbm_floor)

    # Ledger diff vs the previous round (the automatic perf-trajectory
    # check): human-readable report on stderr, regressions in the JSON
    # detail so a drift is a red line in the bench output itself.
    ledger_detail = None
    if ledger is not None:
        ledger_detail = {"path": ledger_path,
                         "programs": len(ledger.programs())}
        prev = _previous_ledger(round_n)
        if prev:
            diff = ledger_mod.diff_ledgers(ledger_mod.load_rows(prev),
                                           ledger_mod.load_rows(ledger_path))
            print(ledger_mod.format_diff(diff, prev, ledger_path),
                  file=sys.stderr)
            ledger_detail["diff_vs"] = prev
            ledger_detail["regressions"] = [
                f"{r['program']}: {r['field']} {r['old']:g} → {r['new']:g} "
                f"({r['ratio']}x)" for r in diff["regressions"]]

    if not on_tpu:
        # the phases ran; their timings are the CPU's and name no metric
        print(json.dumps({
            "metric": "llama-470m bf16 ZeRO-3 train MFU (1 chip)",
            "value": None, "unit": "MFU", "vs_baseline": None,
            "rehearsal": True,
            "detail": {"platform": platform, "loss": round(loss_f, 4),
                       "phases": sorted(residency_by_phase)}}))
        return

    print(json.dumps({
        "metric": "llama-470m bf16 ZeRO-3 train MFU (1 chip)",
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / 0.45, 4),
        "detail": {
            "platform": platform,
            "tokens_per_sec": round(tokens_per_s, 1),
            "achieved_tflops": round(achieved_tflops, 2),
            "peak_tflops": peak,
            "params_m": round(n_params / 1e6, 1),
            "loss": round(loss_f, 4),
            "step_time_s": round(dt / steps, 4),
            "zero_stage": 3,
            "gradient_accumulation_steps": gas,
            "decode_tokens_per_sec": round(decode_tok_s, 1) if decode_tok_s else None,
            "spec_decode": spec_decode,
            "kv_int8_decode": kv_int8_decode,
            "fastgen_continuous_batching": fastgen,
            "fastgen_kernel_micro": kernel_micro,
            "long_ctx": long_ctx,
            "moe": moe,
            "registered_residency": residency_by_phase,
            "ledger": ledger_detail,
        },
    }))


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
