"""deepspeed_tpu — a TPU-native distributed training & inference framework
with DeepSpeed's capability surface.

Top-level API mirrors the reference `deepspeed/__init__.py`:
- `initialize()`        (reference :69)  → (engine, optimizer, dataloader, lr_scheduler)
- `init_inference()`    (reference :291) → InferenceEngine
- `init_distributed()`  (reference :43)
plus `zero`, `comm`, `ops`, `moe`, `sequence`, `pipe` sub-packages.
"""

from __future__ import annotations

import os
import time as _time

_IMPORT_T0 = _time.perf_counter()   # the `import` span opens here

from typing import Any, Callable, Optional

__version__ = "0.1.0"

from deepspeed_tpu.accelerator import get_accelerator  # noqa: F401
from deepspeed_tpu import comm  # noqa: F401
from deepspeed_tpu.comm.comm import init_distributed  # noqa: F401
from deepspeed_tpu.runtime.config import DeepSpeedConfig  # noqa: F401
from deepspeed_tpu.runtime.engine import DeepSpeedEngine  # noqa: F401
from deepspeed_tpu.utils import groups  # noqa: F401
from deepspeed_tpu.utils.groups import MeshTopology  # noqa: F401
from deepspeed_tpu.utils.logging import logger  # noqa: F401
from deepspeed_tpu.telemetry import note_import as _note_import

_note_import(_IMPORT_T0)   # every import above, JAX's among them


def initialize(args=None,
               model: Any = None,
               optimizer=None,
               model_parameters: Any = None,
               training_data=None,
               lr_scheduler=None,
               distributed_port: int = 29500,
               mpu=None,
               mesh: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config: Any = None,
               config_params: Any = None,
               loss_fn: Optional[Callable] = None,
               base_param_specs: Any = None,
               expert_param_fn: Optional[Callable] = None,
               topology: Optional[MeshTopology] = None):
    """Build a training engine from (model, config).

    Counterpart of reference `deepspeed/__init__.py:initialize:69`. `model` is
    a flax module (or anything whose loss is computed by `loss_fn(params,
    batch, rng)`), `model_parameters` the parameter pytree (host or device).
    The DP×SP×TP×EP×PP mesh is built from the config's parallel sizes
    (reference builds the DP×SP mesh at `__init__.py:155-163`), or adopt a
    caller-provided `mesh`/`topology`.
    """
    if config is None:
        config = config_params
    if dist_init_required is None or dist_init_required:
        init_distributed()

    # ---- autotuning intercept (reference launcher runner.py:390 →
    # Autotuner.tune:404): `ds_tpu --autotuning {tune,run}` or an enabled
    # {"autotuning": {...}} config block turns THIS initialize() call into
    # the tuning driver — short real trials over the candidate space,
    # results persisted/resumable, then exit (tune) or continue building
    # the engine with the winning config (run).
    from deepspeed_tpu.autotuning.driver import (autotuning_requested,
                                                 run_autotuning)
    _raw_for_at = config
    if isinstance(_raw_for_at, str):
        # only pay the parse when the CLI/env explicitly asked for
        # autotuning — path-config error semantics (DeepSpeedConfig's own
        # validation) stay untouched on the normal path
        if os.environ.get("DS_TPU_AUTOTUNING", "").strip().lower() in (
                "tune", "run") and os.path.isfile(_raw_for_at):
            import json as _json
            with open(_raw_for_at) as _f:
                _raw_for_at = _json.load(_f)
        else:
            _raw_for_at = None
    _at_mode = autotuning_requested(_raw_for_at)
    if _at_mode is not None:
        best, model, loss_fn = run_autotuning(
            model=model, model_parameters=model_parameters,
            raw_cfg=_raw_for_at if isinstance(_raw_for_at, dict) else {},
            loss_fn=loss_fn, base_param_specs=base_param_specs,
            mode=_at_mode, initialize_fn=initialize)
        if _at_mode == "tune":
            logger.info("autotuning: mode=tune — exiting after the sweep "
                        "(rerun with the written best.json, or use "
                        "mode=run to continue training immediately)")
            raise SystemExit(0)
        config = best  # mode=run: train with the winner (model rebuilt
        #                with winning model-side knobs by the driver)

    from deepspeed_tpu.pipe.module import PipelineModule
    pipeline_module = model if isinstance(model, PipelineModule) else None

    ds_config = config if isinstance(config, DeepSpeedConfig) else None
    if ds_config is None:
        # Parallel sizes must be known before batch triangulation.
        if topology is None:
            import json as _json
            raw = config
            if isinstance(config, str):
                with open(config) as f:
                    raw = _json.load(f)
            raw = raw or {}
            tp = int((raw.get("tensor_parallel", {}) or {}).get("tp_size", 1)) or 1
            sp = int(raw.get("sequence_parallel_size", 1))
            ep = int(raw.get("expert_parallel_size", 1))
            pp = int((raw.get("pipeline", {}) or {}).get("pipeline_parallel_size", 1))
            zero_raw = raw.get("zero_optimization", {}) or {}
            mics = int(zero_raw.get("mics_shard_size", 0) or 0)
            if mics <= 0:  # hpZ secondary partition rides the same axis split
                mics = int(zero_raw.get("zero_hpz_partition_size", 0) or 0)
                mics = mics if mics > 1 else 0
            if pipeline_module is not None and pipeline_module.num_stages:
                pp = pipeline_module.num_stages
            topology = MeshTopology(pp=pp, ep=ep, sp=sp, tp=tp, mesh=mesh,
                                    mics_shard_size=max(mics, 0))
        ds_config = DeepSpeedConfig(config, mpu=mpu,
                                    world_size=topology.world_size)
    elif topology is None:
        topology = MeshTopology(
            pp=ds_config.pipeline.pipeline_parallel_size,
            ep=ds_config.expert_parallel_size,
            sp=ds_config.sequence_parallel_size,
            tp=ds_config.tensor_parallel.tp_size,
            mesh=mesh)

    groups.initialize(topology)
    if pipeline_module is not None:
        n_stages = topology.pp_size
        if pipeline_module.num_stages not in (None, n_stages):
            raise ValueError(
                f"PipelineModule(num_stages={pipeline_module.num_stages}) != "
                f"mesh pipe size {n_stages}")
        if loss_fn is None:
            loss_fn = pipeline_module.build_loss_fn(
                ds_config.gradient_accumulation_steps, n_stages)
        if base_param_specs is None:
            base_param_specs = pipeline_module.param_specs()
    engine = DeepSpeedEngine(
        model=model, loss_fn=loss_fn, config=ds_config,
        model_parameters=model_parameters, base_param_specs=base_param_specs,
        topology=topology, training_data=training_data, collate_fn=collate_fn,
        lr_scheduler=lr_scheduler, optimizer=optimizer,
        expert_param_fn=expert_param_fn)
    return engine, engine.opt, engine.training_dataloader, engine.lr_scheduler


def init_inference(model: Any = None, config: Any = None, **kwargs):
    """Build an inference engine (reference deepspeed/__init__.py:init_inference:291).

    `model` is a zoo flax module or a `(module, params)` tuple; params may
    also be passed via the `params=` kwarg.
    """
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    params = kwargs.pop("params", None)
    if not isinstance(config, DeepSpeedInferenceConfig):
        config = DeepSpeedInferenceConfig(**{**(config or {}), **kwargs})
    return InferenceEngine(model, config, params=params)


def add_config_arguments(parser):
    """Reference deepspeed/__init__.py:268 — CLI arg injection."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str)
    group.add_argument("--deepscale", default=False, action="store_true")
    group.add_argument("--local_rank", type=int, default=-1)
    return parser
