"""Autotuner (reference `autotuning/autotuner.py:42`).

Same strategy as the reference: estimate ZeRO model-state memory to prune
the space (`:278`), then launch short real runs over (zero stage,
micro-batch) candidates and keep the fastest (`tune:404`). The reference
schedules each experiment as a separate launcher job; on TPU each trial is
an in-process engine build + a few compiled steps (cheap, no process
spawning), which also means the tuner composes with any mesh.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger

TUNING_MICRO_BATCH_SIZES = [1, 2, 4, 8]
TUNING_ZERO_STAGES = [0, 1, 2, 3]


def estimate_zero_memory(num_params: int, stage: int, dp_size: int,
                         bf16: bool = True, gas: int = 2) -> int:
    """Per-device model-state bytes (reference memory estimation `:278` /
    `zero/model_states_mem_needs`): params + grads + Adam(m, v, master)."""
    bytes_per = 2 if bf16 else 4
    p = num_params * bytes_per          # model params
    # fp32 grad accumulation: sharded from stage >= 1 (partition.py
    # grad_accum_spec); fully ELIDED at GAS=1 — callers tuning GAS=1
    # workloads can pass gas=1 for the tighter bound
    g = 0 if gas == 1 else num_params * 4
    o = num_params * 12 if bf16 else num_params * 8  # master + m + v
    if stage >= 3:
        p //= dp_size
    if stage >= 1:
        g //= dp_size
        o //= dp_size
    return p + g + o


# Per-token-per-layer live activation bytes factor by remat policy, in units
# of `hidden` (H) and `intermediate` (I). Whole-block remat ('nothing')
# keeps only the residual stream at block boundaries; 'checkpoint_dots'
# additionally keeps every matmul output (q/k/v/o projections + gate/up/down
# inputs — the policy that OOMed at mbs4 and at 16k ctx on v5e, r2 ledger)
# and the flash kernel's output, the size of q (its logsumexp is 4 bytes a
# head and position); 'dots' keeps the matmul outputs alone; no remat keeps
# the full forward. Assumes flash attention (no S² logits).
_REMAT_FACTORS = {
    "nothing": lambda h, i: h,
    # host_offload stages the block-boundary residuals to pinned host
    # memory — their HBM share is ~0; the per-block working set (the
    # separate `working` term) still applies
    "host_offload": lambda h, i: 0,
    "checkpoint_dots": lambda h, i: 5 * h + 3 * i,
    "dots": lambda h, i: 4 * h + 3 * i,
    None: lambda h, i: 14 * h + 4 * i,  # no remat
}


def estimate_activation_memory(mbs: int, seq_len: int, hidden: int,
                               num_layers: int,
                               intermediate: Optional[int] = None,
                               vocab: Optional[int] = None,
                               remat_policy: Optional[str] = "nothing",
                               bytes_per: int = 2) -> int:
    """Per-device activation bytes for one micro-batch of a transformer —
    the term the r2 autotuner ignored (its pruning passed configs whose
    activations then OOMed at trial time; reference `autotuner.py:278`
    prunes on activation_mem too). Three parts: live checkpoints across all
    layers (policy-dependent), one block's recompute working set, and the
    fp32 logits+softmax buffers (elided when the model chunks its loss)."""
    i = intermediate or 4 * hidden
    if remat_policy not in _REMAT_FACTORS:
        raise ValueError(
            f"unknown remat_policy {remat_policy!r} — the estimator would "
            "have to guess its activation footprint (the model falls back "
            "to whole-block remat for unknown names; pass 'nothing' to "
            "estimate that)")
    factor = _REMAT_FACTORS[remat_policy](hidden, i)
    live = mbs * seq_len * num_layers * factor * bytes_per
    working = mbs * seq_len * (4 * hidden + 3 * i) * bytes_per
    logits = 2 * mbs * seq_len * vocab * 4 if vocab else 0
    return live + working + logits



def apply_candidate(base_config: Dict, cand: Dict[str, Any]) -> Dict:
    """Merge a winning candidate into a full engine config — ONE place for
    the mbs/zero-stage placement and the reserved-key exclusions (shared by
    Autotuner.tune and the experiment scheduler)."""
    out = dict(base_config)
    out["train_micro_batch_size_per_gpu"] = cand["micro_batch_size"]
    out.setdefault("zero_optimization", {})
    out["zero_optimization"] = {**out["zero_optimization"],
                                "stage": cand["zero_stage"]}
    for k, v in cand.items():
        if k not in ("zero_stage", "micro_batch_size", "samples_per_sec",
                     "exp_id"):
            out[k] = v
    return out


class Autotuner:
    """Search (zero_stage, micro_batch) by short measured runs.

    build_engine(config_dict) -> engine; batch_fn(mbs) -> global batch.
    """

    def __init__(self, build_engine: Callable[[Dict], Any],
                 batch_fn: Callable[[int], Dict],
                 base_config: Dict,
                 micro_batch_sizes: Optional[List[int]] = None,
                 zero_stages: Optional[List[int]] = None,
                 num_steps: int = 3, warmup: int = 1,
                 max_memory_bytes: Optional[int] = None,
                 num_params: Optional[int] = None,
                 dp_size: int = 1,
                 extra_dims: Optional[Dict[str, List[Any]]] = None,
                 model_info: Optional[Dict[str, int]] = None,
                 memory_safety: float = 0.92):
        """`max_memory_bytes=None` reads the per-device HBM budget from the
        accelerator (reference reads `autotuning.max_train_micro_batch_size`
        memory from the GPU); pass explicitly to override.

        `model_info` ({hidden_size, num_layers, seq_len, intermediate_size?,
        vocab_size?}) enables the ACTIVATION term in pruning — without it
        only model states are estimated and activation-bound configs (large
        mbs, long seq, heavy remat policies) reach trial time before
        failing."""
        self.build_engine = build_engine
        self.batch_fn = batch_fn
        self.base_config = base_config
        self.micro_batch_sizes = micro_batch_sizes or TUNING_MICRO_BATCH_SIZES
        self.zero_stages = zero_stages or TUNING_ZERO_STAGES
        self.num_steps = num_steps
        self.warmup = warmup
        if max_memory_bytes is None:
            from deepspeed_tpu.accelerator import get_accelerator
            total = get_accelerator().total_memory()
            max_memory_bytes = int(total * memory_safety) if total else None
        self.max_memory_bytes = max_memory_bytes
        self.num_params = num_params
        self.dp_size = dp_size
        self.model_info = model_info
        # Extra cross-product search dimensions, e.g.
        # {"remat_policy": ["nothing", "checkpoint_dots"]}: each key lands
        # at the top level of the trial config for build_engine to consume
        # (remat is how the v5e bench went 54% → 59% MFU — it belongs in
        # the search space, reference autotuner's `other flags` role).
        self.extra_dims = extra_dims or {}
        for k in ("zero_stage", "micro_batch_size"):
            if k in self.extra_dims:
                raise ValueError(
                    f"extra_dims[{k!r}] would silently override the swept "
                    "dimension of the same name — use zero_stages/"
                    "micro_batch_sizes instead")
        for k, v in self.extra_dims.items():
            if not v:
                raise ValueError(
                    f"extra_dims[{k!r}] is empty — an empty dimension would "
                    "silently collapse the whole cross-product")
        self.results: List[Dict] = []

    def _estimate(self, stage: int, mbs: int, extra: Dict[str, Any]) -> int:
        """Model-state + activation bytes for one candidate. GAS and remat
        policy are read from the candidate itself (falling back to
        base_config) so swept dimensions shape the estimate."""
        gas = int(extra.get("gradient_accumulation_steps",
                            self.base_config.get(
                                "gradient_accumulation_steps", 1)))
        need = estimate_zero_memory(self.num_params, stage, self.dp_size,
                                    gas=gas)
        if self.model_info:
            mi = self.model_info
            need += estimate_activation_memory(
                mbs, mi["seq_len"], mi["hidden_size"], mi["num_layers"],
                intermediate=mi.get("intermediate_size"),
                vocab=mi.get("vocab_size"),
                remat_policy=extra.get(
                    "remat_policy", self.base_config.get("remat_policy",
                                                         "nothing")))
        return need

    def _candidates(self) -> List[Dict[str, Any]]:
        import itertools
        extras = [dict(zip(self.extra_dims, vals)) for vals in
                  itertools.product(*self.extra_dims.values())] or [{}]
        out = []
        for stage in self.zero_stages:
            for mbs in self.micro_batch_sizes:
                for extra in extras:
                    if self.max_memory_bytes and self.num_params:
                        need = self._estimate(stage, mbs, extra)
                        if need > self.max_memory_bytes:
                            logger.info(
                                f"autotuner: prune stage={stage} mbs={mbs} "
                                f"{extra} (needs {need/1e9:.1f} GB)")
                            continue
                    out.append({"zero_stage": stage, "micro_batch_size": mbs,
                                **extra})
        return out

    def _run_trial(self, cand: Dict[str, Any]) -> Optional[float]:
        import jax
        stage, mbs = cand["zero_stage"], cand["micro_batch_size"]
        cfg = dict(self.base_config)
        cfg["train_micro_batch_size_per_gpu"] = mbs
        cfg.setdefault("zero_optimization", {})
        cfg["zero_optimization"] = {**cfg["zero_optimization"], "stage": stage}
        for k, v in cand.items():
            if k not in ("zero_stage", "micro_batch_size"):
                cfg[k] = v
        engine = None
        samples_s = None
        try:
            engine = self.build_engine(cfg)
            try:  # GAS-aware batch fns take (mbs, candidate_cfg)
                batch = self.batch_fn(mbs, cfg)
            except TypeError:
                batch = self.batch_fn(mbs)
            for _ in range(self.warmup):
                engine.train_batch(batch=batch)
            jax.block_until_ready(engine.state)
            t0 = time.perf_counter()
            for _ in range(self.num_steps):
                loss = engine.train_batch(batch=batch)
            jax.block_until_ready((engine.state, loss))
            dt = time.perf_counter() - t0
            samples_s = engine.train_batch_size() * self.num_steps / dt
        except Exception as e:
            logger.info(f"autotuner: trial {cand} failed: {e}")
        finally:
            # free the trial engine's device state before the next trial —
            # back-to-back HBM-sized optimizer trees otherwise overlap
            if engine is not None:
                engine.state = None
                getattr(engine, "_jit_cache", {}).clear()
            del engine
            gc.collect()
        if samples_s is None:
            # an OOM'd trial's HBM is returned lazily by some runtimes
            # (observed in r4: live_arrays() clean but the next trial
            # still ResourceExhausted) — settle AFTER the
            # cleanup above so the window actually covers freed buffers
            time.sleep(float(os.environ.get("DS_TPU_AUTOTUNE_COOLDOWN",
                                            "5")))
        return samples_s

    def tune(self) -> Dict:
        """Reference `tune:404` → best config dict (fastest samples/s)."""
        best = None
        for cand in self._candidates():
            tput = self._run_trial(cand)
            rec = {**cand, "samples_per_sec": tput}
            self.results.append(rec)
            logger.info(f"autotuner: {rec}")
            if tput is not None and (best is None or tput > best["samples_per_sec"]):
                best = rec
        if best is None:
            raise RuntimeError("autotuner: every trial failed")
        self.best = best
        return apply_candidate(self.base_config, best)
