"""ZeRO-Inference capacity serving — engine-path harness + A/B
(reference `deepspeed/inference/` ZeRO-Inference: weights live on CPU/NVMe
and stream to the accelerator per layer, trading bandwidth for capacity —
the path that serves models LARGER than device memory; Aminabadi et al.
2022, Rajbhandari et al. 2021).

HISTORY — the r5 PROBE this harness grew from measured outcome (b) on
1×v5e: with params truly placed `pinned_host`, the first gather fails to
compile ("memory_space of all inputs passed to `gather` must be the
same") — XLA does NOT auto-stage host operands into compute, and even
slicing a host-memory-space Array enters compute with a host operand. A
TPU capacity mode therefore needs an EXPLICIT per-layer `jax.device_put`
inside a host-driven layer loop. That engine now exists
(`inference/capacity_scan.py`, `serve_mode="capacity"`): host-parked
per-layer numpy slices, double-buffered H2D prefetch (layer l+1's
transfer dispatched while layer l's block computes), optional int8 via
the per-layer quantizer (halves PCIe bytes; fused dequant-GEMM consumes
int8 directly) and an NVMe tier on the striped aio engine.

Phases (run on the real chip; CPU-mesh runs are functional proxies only —
host→device "transfers" are memcpys, so overlap ratios there understate
the chip):

  serve  — capacity-mode decode via the ENGINE: tok/s, per-step H2D
           bytes, prefetch stall, host-residency check
  ab     — the acceptance A/B: double-buffered prefetch vs synchronous
           stage-then-compute staging (`capacity={"double_buffer":
           False}`), same process, best-of-3 — target ≥1.3x on chip
  nvme   — half the layers parked on NVMe through the aio engine
  probe  — the legacy r5 pinned_host placement probe (kept for reference;
           expected to FAIL compile with the gather memory_space error)

Usage: python benchmarks/capacity_serve.py [small|7b] [serve|ab|nvme|probe]
       [--int8]  (defaults: small serve)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cfg(big: bool):
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaConfig
    if big:
        return LlamaConfig(vocab_size=32000, hidden_size=4096,
                           intermediate_size=11008, num_hidden_layers=32,
                           num_attention_heads=32, num_key_value_heads=32,
                           max_position_embeddings=4096, remat=False,
                           dtype=jnp.bfloat16)
    return LlamaConfig(vocab_size=32000, hidden_size=1024,
                       intermediate_size=4096, num_hidden_layers=24,
                       num_attention_heads=8, num_key_value_heads=8,
                       max_position_embeddings=2048, remat=False,
                       dtype=jnp.bfloat16)


def _host_params(model):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
        raw, _ = extract_params_and_specs(variables)
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), raw)


def _timed_decode(eng, ids, new, iters=3):
    """Best-of-N generate wall time (generate fetches its output — a real
    materialization, so the program has finished)."""
    eng.generate(ids, max_new_tokens=new)  # compile + warm transfers
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        eng.generate(ids, max_new_tokens=new)
        best = min(best, time.time() - t0)
    return best


def main():
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.utils import groups

    args = sys.argv[1:]
    big = "7b" in args
    int8 = "--int8" in args
    phase = next((a for a in args if a in ("serve", "ab", "nvme", "probe")),
                 "serve")

    # Program ledger: the capacity block program (and per-key generate
    # measured rows) land in a JSONL for round-over-round diffing, and the
    # CapacityPlan is checked against the compiled block's memory_analysis
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    ledger_path = os.environ.get("DS_TPU_LEDGER_JSONL",
                                 "ledger_capacity.jsonl")
    ledger_mod.set_ledger(
        ledger_mod.ProgramLedger(path=ledger_path, enabled=True))
    cfg = _cfg(big)
    model = LlamaForCausalLM(cfg)
    params = _host_params(model)
    print(json.dumps({"phase": phase, "model": "7b" if big else "small",
                      "int8": int8, "params_gb": round(sum(
                          v.nbytes for v in jax.tree_util.tree_leaves(params))
                          / 1e9, 2),
                      "platform": jax.devices()[0].platform}), flush=True)
    b, s, new = 4, 64, 16
    ids = np.random.default_rng(1).integers(0, 32000, (b, s))
    quant = {"enabled": True} if int8 else None

    def capacity_engine(**capacity_opts):
        groups.reset_topology()
        return deepspeed_tpu.init_inference(
            model, params=params, dtype="bf16", serve_mode="capacity",
            quant=quant, capacity=capacity_opts or None)

    if phase == "serve":
        eng = capacity_engine()
        r = eng._capacity
        dt = _timed_decode(eng, ids, new)
        print(json.dumps({"capacity_decode": {
            "tokens_per_sec": round(b * new / dt, 1),
            "h2d_gb_step": round(r.last_h2d_bytes_step / 1e9, 3),
            "prefetch_stall_ms_total": round(r.last_prefetch_stall_ms, 1),
            "host_resident": r.host_resident(),
            "planned_peak_gb": round(r.plan.peak_hbm_bytes / 1e9, 2),
            "plan_vs_compiled_ok": r.check_plan(),
            "ledger": ledger_path}}),
            flush=True)

    elif phase == "ab":
        # the acceptance A/B: one process, same weights, best-of-3 each.
        # Synchronous staging FIRST so its cold compile doesn't pollute
        # the double-buffer row (the block program is shared either way).
        rows = {}
        for name, opts in (("sync", {"double_buffer": False}),
                           ("double_buffer", {})):
            eng = capacity_engine(**opts)
            dt = _timed_decode(eng, ids, new)
            rows[name] = {"tokens_per_sec": round(b * new / dt, 1),
                          "stall_ms": round(
                              eng._capacity.last_prefetch_stall_ms, 1)}
            eng.params = None
            del eng
        rows["speedup"] = round(rows["double_buffer"]["tokens_per_sec"]
                                / max(rows["sync"]["tokens_per_sec"], 1e-9),
                                2)
        print(json.dumps({"capacity_ab": rows}), flush=True)

    elif phase == "nvme":
        swap = os.environ.get("DS_TPU_SWAP_DIR", "/tmp/ds_capacity_swap")
        eng = capacity_engine(nvme_dir=swap,
                              nvme_layers=cfg.num_hidden_layers // 2)
        dt = _timed_decode(eng, ids, new)
        print(json.dumps({"capacity_nvme_decode": {
            "tokens_per_sec": round(b * new / dt, 1),
            "nvme_layers": eng._capacity.plan.nvme_layers,
            "nvme_gb": round(eng._capacity.plan.nvme_bytes / 1e9, 2),
            "stall_ms": round(eng._capacity.last_prefetch_stall_ms, 1)}}),
            flush=True)

    elif phase == "probe":
        # the r5 measurement, unchanged: pinned_host placement + plain jit
        # generate — documents WHY the engine stages explicitly
        from jax.sharding import NamedSharding, PartitionSpec as P
        groups.reset_topology()
        topo = groups.initialize(tp=1, dp=1, devices=jax.devices()[:1])
        host = NamedSharding(topo.mesh, P(), memory_kind="pinned_host")
        try:
            hparams = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, host), params)
            groups.reset_topology()
            eng = deepspeed_tpu.init_inference(model, params=hparams,
                                               dtype="bf16",
                                               auto_layouts=False)
            eng.params = hparams  # restore the residency under test
            out = eng.generate(ids, max_new_tokens=new)
            print(json.dumps({"probe": {"unexpectedly_ok": True,
                                        "distinct": int(len(np.unique(
                                            np.asarray(out))))}}),
                  flush=True)
        except Exception as e:
            print(json.dumps({"probe": {"outcome_b_error":
                                        str(e)[:220].replace("\n", " ")}}),
                  flush=True)


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
