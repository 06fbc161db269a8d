"""7B-class HF checkpoint → v5e decode (VERDICT r4 missing #1).

The reference fork's own harnesses serve real 7-13B models
(`/root/reference/zero.py:38-60` Qwen-3B ZeRO-offload inference,
`/root/reference/benchmark.py:181-292` kernel-injected 7-13B). This box
has zero egress, so no real weights exist locally; the at-scale claims
this harness DOES validate with a synthesized llama-2-7b checkpoint in
the real HF on-disk format (sharded fp16 safetensors + index json,
exactly what `load_state_dict` walks):

  1. the converter at real scale: 6.7B params through `_convert_llama`'s
     stack/transpose path and bf16 device placement (~12.6 GB HBM);
  2. KV-cache greedy decode throughput of the v1 engine at 7B — rides
     the engine's AUTO-layout path (r5): without it XLA copies the
     q/k/v stacks to its preferred tiling in-program (+3 GB, OOM);
  3. the int8 ZeRO-Inference path at scale, END-TO-END through the
     engine (checkpoint → converter → engine quantization → serve):
     with `quant={"enabled": True}` the serve-mode selector picks
     `quantized_layer_scan` at 7B (the whole-tree dequant residency
     would crowd HBM), the engine quantizes the layer stacks per layer
     on device, and generate scans them with the fused dequant-GEMM
     kernel (docs/quantized_serving.md).

MEASURED (r5, 1×v5e): load 6.74 B params in ~9 min (disk-bound);
bf16 decode 162 tok/s @ b4 (~16.5 ms/step — the 13.5 GB/step weight
read is the bound, ~80% of HBM bandwidth); int8 whole-tree dequant
RESOURCE_EXHAUSTED as predicted — which is why the engine now serves
7B int8 via the layer scan (int8 reads 6.84 GB/step vs 13.21 dense —
the fused kernel makes that a throughput WIN, not just capacity;
r6 on-chip numbers pend the next TPU-attached run).

CAPACITY mode (r7): `--capacity` serves the same checkpoint with the
layers parked in HOST memory and streamed per layer with double-buffered
`jax.device_put` prefetch (`inference/capacity_scan.py`) — the engine
lift of an r5 probe's (b) outcome: XLA refuses to
auto-stage pinned_host params into compute ("memory_space of all inputs
passed to `gather` must be the same"), so staging must be an explicit
per-layer transfer. At 7B this bounds HBM to ~2 layer slices (~0.4 GB
bf16 / ~0.2 GB int8) + KV + workspace instead of the 12.6 GB resident
tree; decode becomes PCIe-bound (~13.5 GB/step bf16 over the wire,
~6.8 GB/step with --int8 — int8 halves PCIe traffic exactly as it
halves HBM reads). Expect capacity decode well BELOW the resident
162 tok/s — the mode's point is serving trees that can't be resident
at all (docs/capacity_serving.md has the throughput model).

SPECULATIVE decoding (r8): `--spec` layers k-token draft-and-verify
(docs/speculative_decoding.md) over whichever serve mode the other
flags select — greedy, so the output chain is bit-exact vs the
non-spec run and tok/s is directly comparable. The self-draft is a
half-depth layer slice sharing the checkpoint (no second model on
disk); each target weight pass — HBM read resident, PCIe stream under
--capacity — then emits `acceptance·k + 1` tokens instead of 1, which
is the weight-read-bound breaker at exactly these 7B shapes. Rows gain
`acceptance_rate` (the tiled synthetic checkpoint accepts unusually
well — real-weights acceptance is the number that matters on chip).

INT8 KV (r8, `--kv-int8`): the cache itself goes int8-at-rest
(`kv_cache_dtype='int8'`, docs/kv_cache.md) — per-(kv-head, slot) f32
scales, dequantized in-register by the attention kernels. At 7B/4k the
KV pool halves (the `model_kv_budget` max-batch doubler); at this
harness's b4/s96 shapes the win is bytes, not tok/s (weights dominate
the step read). Composes with --spec (greedy spec stays bit-exact vs
the non-spec run AT THE SAME kv dtype); under --int8/--capacity the
streamed modes keep dense KV and the engine warns (rows record the
effective kv dtype).

Usage: python benchmarks/hf7b_decode.py [ckpt_dir] [--int8]
[--capacity] [--spec] [--kv-int8] (default dir /tmp/llama7b-synth;
synthesized on first run, ~13 GB on disk. --int8 skips the bf16 phase
and runs only the engine-integrated quantized_layer_scan serve path;
--capacity streams host-parked layers instead of resident serving, and
combines with --int8 for the int8-over-PCIe variant; --spec and
--kv-int8 compose with both)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CFG = dict(model_type="llama", vocab_size=32000, hidden_size=4096,
           intermediate_size=11008, num_hidden_layers=32,
           num_attention_heads=32, num_key_value_heads=32,
           max_position_embeddings=4096, rope_theta=10000.0,
           rms_norm_eps=1e-5, tie_word_embeddings=False,
           torch_dtype="float16")


def synthesize(path: str) -> None:
    """Write a llama-2-7b-shaped checkpoint: fp16 sharded safetensors +
    index, 4 layers per shard. Values tile a random block — realistic
    per-block statistics for the int8 quantizer without minutes of RNG."""
    from safetensors.numpy import save_file
    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(0)
    tile = (rng.standard_normal(1 << 20).astype(np.float16) * 0.02)

    def mat(shape):
        n = int(np.prod(shape))
        reps = -(-n // tile.size)
        return np.tile(tile, reps)[:n].reshape(shape)

    d, f, L = CFG["hidden_size"], CFG["intermediate_size"], CFG["num_hidden_layers"]
    weight_map = {}
    shard_id = 0

    def write(shard, tensors):
        nonlocal shard_id
        name = f"model-{shard_id:05d}.safetensors"
        save_file(tensors, os.path.join(path, name))
        for k in tensors:
            weight_map[k] = name
        shard_id += 1

    write(0, {"model.embed_tokens.weight": mat((CFG["vocab_size"], d)),
              "model.norm.weight": np.ones((d,), np.float16),
              "lm_head.weight": mat((CFG["vocab_size"], d))})
    for base in range(0, L, 4):
        tensors = {}
        for i in range(base, min(base + 4, L)):
            p = f"model.layers.{i}."
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                tensors[f"{p}self_attn.{proj}.weight"] = mat((d, d))
            tensors[f"{p}mlp.gate_proj.weight"] = mat((f, d))
            tensors[f"{p}mlp.up_proj.weight"] = mat((f, d))
            tensors[f"{p}mlp.down_proj.weight"] = mat((d, f))
            tensors[f"{p}input_layernorm.weight"] = np.ones((d,), np.float16)
            tensors[f"{p}post_attention_layernorm.weight"] = \
                np.ones((d,), np.float16)
        write(0, tensors)
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as fh:
        json.dump({"metadata": {}, "weight_map": weight_map}, fh)
    with open(os.path.join(path, "config.json"), "w") as fh:
        json.dump(CFG, fh)


def main():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    from deepspeed_tpu.utils import groups

    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    int8_only = "--int8" in sys.argv[1:]
    capacity = "--capacity" in sys.argv[1:]
    # --spec: k-token draft-and-verify over the selected serve mode
    # (greedy → bit-exact, tok/s directly comparable to the plain run)
    spec_cfg = ({"enabled": True, "k": 4}
                if "--spec" in sys.argv[1:] else None)
    # --kv-int8: int8-at-rest KV cache (dequant serve mode; the streamed
    # modes warn and keep dense KV — rows record the effective dtype)
    kv_int8 = "--kv-int8" in sys.argv[1:]
    kv_kw = {"kv_cache_dtype": "int8"} if kv_int8 else {}

    def _kv_dtype(eng):
        return ("int8" if kv_int8 and eng.serve_mode == "dequant"
                else "bf16")

    def _acc(eng):
        s = getattr(eng, "_spec", None)
        return (round(s.last_acceptance_rate, 4)
                if s is not None and s.last_acceptance_rate is not None
                else None)

    def _residency():
        # registered MemoryPlane residency per tier (nonzero tiers only) —
        # the formula/ledger number the on-chip memory_stats() reconcile
        # compares against (docs/memory.md)
        from deepspeed_tpu.telemetry.memory import get_plane
        return {t: b for t, b in get_plane().tier_totals().items() if b}
    path = args[0] if args else "/tmp/llama7b-synth"
    if not os.path.exists(os.path.join(path, "model.safetensors.index.json")):
        t0 = time.time()
        synthesize(path)
        print(json.dumps({"synthesized": path,
                          "seconds": round(time.time() - t0, 1)}))

    import jax.tree_util as jtu

    groups.reset_topology()
    t0 = time.time()
    # load HOST-side (the converter's stack/transpose at real scale);
    # device placement is staged per phase below
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model, hparams = load_hf_checkpoint(path, dtype=jnp.bfloat16,
                                            param_dtype=jnp.bfloat16)
    n = sum(v.size for v in jtu.tree_leaves(hparams))
    load_s = time.time() - t0
    print(json.dumps({"loaded_params_b": round(n / 1e9, 2),
                      "load_seconds": round(load_s, 1)}), flush=True)

    tpu = jax.devices()[0]
    b, prompt, new = 4, 64, 32
    ids = np.random.default_rng(1).integers(0, CFG["vocab_size"], (b, prompt))

    # ---- capacity mode (--capacity [--int8]): layers stay HOST-parked
    # (numpy tier, quantized per layer under --int8) and stream through
    # the double-buffered per-layer device_put loop — HBM holds only
    # embed/norm/head + ~2 layer slices + KV + workspace. The engine owns
    # the only param reference, same as the resident phases.
    if capacity:
        try:
            t0 = time.time()
            eng = deepspeed_tpu.init_inference(
                model, params=hparams, dtype="bf16", serve_mode="capacity",
                quant={"enabled": True} if int8_only else None,
                speculative=spec_cfg, **kv_kw)
            del hparams
            stage_s = time.time() - t0
            r = eng._capacity
            print(json.dumps({"capacity_mode": {
                "int8": int8_only, "stage_s": round(stage_s, 1),
                "h2d_gb_step": round(r.h2d_bytes_pass() / 1e9, 2),
                "planned_peak_gb": round(r.plan.peak_hbm_bytes / 1e9, 2),
                "host_resident": r.host_resident()}}), flush=True)
            t0 = time.time()
            out = eng.generate(ids, max_new_tokens=new)
            compile_s = time.time() - t0
            t0 = time.time()
            out = eng.generate(ids, max_new_tokens=new)
            dt = time.time() - t0
            toks = np.asarray(out)[:, prompt:]
            print(json.dumps({"capacity_decode": {
                "int8": int8_only, "spec": spec_cfg is not None,
                "kv_dtype": _kv_dtype(eng),
                "acceptance_rate": _acc(eng),
                "decode_tokens_per_sec": round(b * new / dt, 1),
                "compile_s": round(compile_s, 1),
                "prefetch_stall_ms": round(r.last_prefetch_stall_ms, 1),
                "registered_bytes_by_tier": _residency(),
                "distinct_tokens": int(len(np.unique(toks)))}}), flush=True)
        except Exception as e:
            print(json.dumps({"capacity_decode": {
                "error": str(e)[:160].replace("\n", " ")}}), flush=True)
        return

    # ---- bf16 greedy decode (12.6 GB of weights on HBM). The engine
    # gets the HOST tree and owns the only device reference — its
    # AUTO-layout relayout frees each default-layout leaf as it re-places
    # it, which a second caller-held reference would defeat (13.5 GB × 2).
    eng = None
    try:
        if int8_only:
            raise RuntimeError("skipped (--int8)")
        t0 = time.time()
        eng = deepspeed_tpu.init_inference(model, params=hparams,
                                           dtype="bf16",
                                           speculative=spec_cfg, **kv_kw)
        h2d_s = time.time() - t0
        t0 = time.time()
        out = eng.generate(ids, max_new_tokens=new)   # compile + relayout
        compile_s = time.time() - t0
        t0 = time.time()
        out = eng.generate(ids, max_new_tokens=new)
        dt = time.time() - t0
        toks = np.asarray(out)[:, prompt:]
        row = {"model": "llama7b-synth bf16", "batch": b,
               "spec": spec_cfg is not None, "kv_dtype": _kv_dtype(eng),
               "acceptance_rate": _acc(eng),
               "decode_tokens_per_sec": round(b * new / dt, 1),
               "h2d_s": round(h2d_s, 1), "compile_s": round(compile_s, 1),
               "registered_bytes_by_tier": _residency(),
               "distinct_tokens": int(len(np.unique(toks)))}
        print(json.dumps({"bf16_decode": row}), flush=True)
    except Exception as e:
        print(json.dumps({"bf16_decode": {
            "error": str(e)[:160].replace("\n", " ")}}), flush=True)
    finally:
        if eng is not None:
            eng.params = None
            eng.cache = None
        del eng
        import gc
        gc.collect()

    # ---- int8, engine-integrated (the r6 quantized_layer_scan path):
    # the engine places the bf16 tree, quantizes the layer stacks PER
    # LAYER on device (leaf-wise rebinding — peak HBM ≈ bf16 tree + one
    # int8 leaf, falling to the 7.1 GB int8 tree as bf16 leaves free),
    # and generate runs the layer scan with the fused dequant-GEMM
    # kernel. serve_mode='auto' must pick layer_scan at this size.
    eng = None
    try:
        t0 = time.time()
        eng = deepspeed_tpu.init_inference(
            model, params=hparams, dtype="bf16", quant={"enabled": True},
            speculative=spec_cfg, **kv_kw)
        q_s = time.time() - t0
        del hparams  # the engine owns the only reference (see bf16 note)
        wb, wb_dense = eng._weight_bytes_per_step()
        print(json.dumps({"int8_serve_mode": eng.serve_mode,
                          "quantize_s": round(q_s, 1),
                          "weight_gb_step_int8": round(wb / 1e9, 2),
                          "weight_gb_step_dense": round(wb_dense / 1e9, 2)}),
              flush=True)
        t0 = time.time()
        out = eng.generate(ids, max_new_tokens=new)
        compile_s = time.time() - t0
        t0 = time.time()
        out = eng.generate(ids, max_new_tokens=new)
        dt = time.time() - t0
        toks = np.asarray(out)[:, prompt:]
        print(json.dumps({"int8_decode": {
            "serve_mode": eng.serve_mode,
            "spec": spec_cfg is not None, "kv_dtype": _kv_dtype(eng),
            "acceptance_rate": _acc(eng),
            "decode_tokens_per_sec": round(b * new / dt, 1),
            "compile_s": round(compile_s, 1),
            "registered_bytes_by_tier": _residency(),
            "distinct_tokens": int(len(np.unique(toks)))}}), flush=True)
    except Exception as e:
        print(json.dumps({"int8_decode": {
            "error": str(e)[:160].replace("\n", " ")}}), flush=True)
    finally:
        if eng is not None:
            eng.params = None
        del eng


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
