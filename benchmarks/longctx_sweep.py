"""128k long-context step-time probe (one variant per process — the lazy
allocator holds freed HBM, so chained variants OOM; CLAUDE.md bench note).

Usage: python benchmarks/longctx_sweep.py MLP_CHUNK CE_CHUNK {cpu|dev}
       [REMAT_POLICY] [SEQ] [GAS]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        LlamaConfig, init_params_and_specs, llama_loss_fn, materialize_params)
    from deepspeed_tpu.utils import groups

    mlp_chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    ce_chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    opt_arg = sys.argv[3] if len(sys.argv) > 3 else "cpu"
    if opt_arg not in ("cpu", "dev"):
        raise SystemExit(f"OFFLOAD_OPT must be 'cpu' or 'dev', got {opt_arg!r}")
    offload = opt_arg == "cpu"
    policy = sys.argv[4] if len(sys.argv) > 4 else "host_offload"
    seq_l = int(sys.argv[5]) if len(sys.argv) > 5 else 131072
    gas = int(sys.argv[6]) if len(sys.argv) > 6 else 1

    groups.reset_topology()
    lcfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                       intermediate_size=4096, num_hidden_layers=24,
                       num_attention_heads=8, num_key_value_heads=8,
                       max_position_embeddings=seq_l, remat=True,
                       remat_policy=policy, loss_chunk_size=ce_chunk,
                       mlp_chunk_size=mlp_chunk, dtype=jnp.bfloat16)
    lmodel, lparams = materialize_params(lcfg)
    _, lspecs = init_params_and_specs(lcfg)
    zero = {"stage": 3}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu"}
    lengine, *_ = deepspeed_tpu.initialize(
        model=lmodel, model_parameters=lparams,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": gas, "steps_per_print": 0,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}, "zero_optimization": zero},
        loss_fn=llama_loss_fn(lmodel), base_param_specs=lspecs)
    rng = np.random.default_rng(0)
    lb = {"input_ids": rng.integers(0, 32000, size=(gas, seq_l)).astype(np.int32)}
    float(lengine.train_batch(batch=lb))
    best = 1e9
    for _ in range(3):
        t0 = time.time()
        lloss = lengine.train_batch(batch=lb)
        jax.block_until_ready(lloss)
        best = min(best, time.time() - t0)
    from deepspeed_tpu.accelerator import get_accelerator
    peak = get_accelerator().peak_tflops("bfloat16")
    ltok = gas * seq_l / best
    lfpt = 6.0 * lengine.total_params + \
        6.0 * lcfg.num_hidden_layers * lcfg.hidden_size * seq_l
    print(json.dumps({
        "variant": f"mlp{mlp_chunk} ce{ce_chunk} "
                   f"{'cpu-opt' if offload else 'dev-opt'} {policy} s{seq_l} "
                   f"gas{gas}",
        "step_s": round(best, 2), "tokens_per_sec": round(ltok, 1),
        "mfu": round(ltok * lfpt / 1e12 / peak, 4)}))


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
