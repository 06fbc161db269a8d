"""The single JSON/dict config → typed config tree.

Counterpart of the reference's `deepspeed/runtime/config.py:706`
(`DeepSpeedConfig`): same user-facing key schema (a DeepSpeed JSON config
should parse unchanged), including the train_batch_size /
train_micro_batch_size_per_gpu / gradient_accumulation_steps triangulation
(`runtime/config.py:768-794`). "gpu" in key names is kept for schema
compatibility and means "chip" here.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from pydantic import Field

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.utils.logging import logger


class FP16Config(DeepSpeedConfigModel):
    """Reference: runtime/fp16 config block. loss_scale=0 → dynamic scaling."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0
    fp16_master_weights_and_grads: bool = False
    # TPU extension (not in the reference schema): when a GAS window contains
    # an overflowed micro-batch, still step from the finite micros (mean over
    # the good count) instead of skipping the whole window; the loss scale
    # drops either way. Default False = reference whole-window-skip semantics.
    per_micro_overflow_skip: bool = False


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    immediate_grad_update: bool = True


class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "Adam"
    params: Dict[str, Any] = Field(default_factory=dict)
    legacy_fusion: bool = False


class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = Field(default_factory=dict)


class CommsLoggerConfig(DeepSpeedConfigModel):
    """Reference: deepspeed/comm/config.py."""
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = Field(default_factory=list)


class FlopsProfilerConfig(DeepSpeedConfigModel):
    """Reference: profiling/config.py."""
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class TelemetryConfig(DeepSpeedConfigModel):
    """Unified telemetry hub (telemetry/hub.py): the in-step MetricsState is
    fetched WITH the loss and merged with timers / memory stats / comms
    volume / NVMe counters into JSONL (+ optional Prometheus text file).

    ``flush_every``: steps between host fetches of the deferred metrics
    (1 = one fetch per step, riding the loss transfer; 0 = manual
    ``hub.flush()``, which keeps a timed loop async).
    """
    enabled: bool = False
    jsonl_path: str = "telemetry.jsonl"
    prometheus_path: Optional[str] = None
    flush_every: int = 1
    trace_dir: Optional[str] = None


class MonitorSinkConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"
    # wandb/comet extras tolerated via extra="allow"
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference: runtime/activation_checkpointing config.

    TPU mapping: `partition_activations` → sequence-sharded remat residuals;
    `cpu_checkpointing` → jax host-offload of remat residuals.
    """
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class TensorParallelConfig(DeepSpeedConfigModel):
    autotp_size: int = 1
    tp_size: int = 1
    enabled: bool = True


class PipelineConfig(DeepSpeedConfigModel):
    stages: Any = "auto"
    pipeline_parallel_size: int = 1
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    use_reentrant: bool = True


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = Field(default_factory=dict)


class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True


class DeepSpeedConfigError(Exception):
    pass


class DeepSpeedConfig:
    """Parse + validate a DeepSpeed-schema config dict or JSON path."""

    def __init__(self, config: Any, mpu=None, mesh: Any = None,
                 world_size: Optional[int] = None):
        if isinstance(config, (str, os.PathLike)):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"config path does not exist: {config}")
            with open(config, "r") as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        elif config is None:
            self._param_dict = {}
        else:
            raise DeepSpeedConfigError(
                f"Expected a dict or json path, got {type(config)}")

        pd = self._param_dict
        self.raw = pd

        # Parallel sizes influencing DP world size for batch triangulation.
        self.sequence_parallel_size = int(pd.get(C.SEQUENCE_PARALLEL_SIZE, 1))
        tp_dict = pd.get(C.TENSOR_PARALLEL, {}) or {}
        self.tensor_parallel = TensorParallelConfig(**tp_dict) if isinstance(tp_dict, dict) \
            else TensorParallelConfig()
        self.pipeline = PipelineConfig(**(pd.get(C.PIPELINE, {}) or {}))

        self.zero_config = DeepSpeedZeroConfig(**(pd.get(C.ZERO_OPTIMIZATION, {}) or {}))
        self.fp16 = FP16Config(**(pd.get(C.FP16, {}) or {}))
        bf16_dict = pd.get(C.BFLOAT16, pd.get(C.BFLOAT16_OLD, {})) or {}
        self.bf16 = BF16Config(**bf16_dict)
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        if self.fp16.per_micro_overflow_skip and self.fp16.enabled \
                and self.fp16.loss_scale != 0.0:
            # With a static scale nothing ever reacts to the overflow: the
            # same micro would silently be dropped every window forever.
            raise DeepSpeedConfigError(
                "fp16.per_micro_overflow_skip requires dynamic loss scaling "
                "(loss_scale: 0)")

        opt = pd.get(C.OPTIMIZER)
        self.optimizer = OptimizerConfig(**opt) if isinstance(opt, dict) else None
        sched = pd.get(C.SCHEDULER)
        self.scheduler = SchedulerConfig(**sched) if isinstance(sched, dict) else None

        self.gradient_clipping = float(pd.get(C.GRADIENT_CLIPPING, 0.0))
        self.prescale_gradients = bool(pd.get(C.PRESCALE_GRADIENTS, False))
        self.gradient_predivide_factor = float(pd.get(C.GRADIENT_PREDIVIDE_FACTOR, 1.0))
        self.sparse_gradients_enabled = bool(pd.get(C.SPARSE_GRADIENTS, False))
        self.communication_data_type = pd.get(C.COMMUNICATION_DATA_TYPE, None)
        self.steps_per_print = pd.get(C.STEPS_PER_PRINT, 10)
        self.wall_clock_breakdown = bool(pd.get(C.WALL_CLOCK_BREAKDOWN, False))
        self.dump_state = bool(pd.get(C.DUMP_STATE, False))
        self.seed = int(pd.get(C.SEED, 1234))
        self.dataloader_drop_last = bool(pd.get(C.DATALOADER_DROP_LAST, False))

        self.comms_config = CommsLoggerConfig(**(pd.get(C.COMMS_LOGGER, {}) or {}))
        self.flops_profiler = FlopsProfilerConfig(**(pd.get(C.FLOPS_PROFILER, {}) or {}))
        self.tensorboard = MonitorSinkConfig(**(pd.get(C.MONITOR_TENSORBOARD, {}) or {}))
        self.csv_monitor = MonitorSinkConfig(**(pd.get(C.MONITOR_CSV, {}) or {}))
        self.wandb = MonitorSinkConfig(**(pd.get(C.MONITOR_WANDB, {}) or {}))
        self.comet = MonitorSinkConfig(**(pd.get(C.MONITOR_COMET, {}) or {}))
        self.jsonl_monitor = MonitorSinkConfig(**(pd.get(C.MONITOR_JSONL, {}) or {}))
        self.telemetry = TelemetryConfig(**(pd.get(C.TELEMETRY, {}) or {}))
        self.activation_checkpointing = ActivationCheckpointingConfig(
            **(pd.get(C.ACTIVATION_CHECKPOINTING, {}) or {}))
        self.checkpoint_config = CheckpointConfig(**(pd.get(C.CHECKPOINT, {}) or {}))
        self.data_types = DataTypesConfig(**(pd.get(C.GRADIENT_ACCUMULATION_DTYPE, {}) or {}))
        self.elasticity = ElasticityConfig(**(pd.get(C.ELASTICITY, {}) or {}))
        # Curriculum config: legacy top-level block, or the reference
        # data_efficiency nesting (data_efficiency.data_sampling.
        # curriculum_learning.curriculum_metrics.seqlen — reference
        # runtime/data_pipeline/config.py). Outer enabled flags gate inner.
        cl = dict(pd.get(C.CURRICULUM_LEARNING_LEGACY, {}) or {})
        enabled = bool(cl.get("enabled", False))
        if not cl:
            de = pd.get("data_efficiency", {}) or {}
            ds_blk = de.get("data_sampling", {}) or {}
            inner = dict(ds_blk.get("curriculum_learning", {}) or {})
            metrics = inner.get("curriculum_metrics", {}) or {}
            has_seqlen = "seqlen" in metrics  # presence, not truthiness: an
            # explicit empty block means "seqlen with default schedule"
            seqlen = metrics.get("seqlen", {}) or {}
            if seqlen:  # flatten the per-metric schema onto the scheduler's
                inner = {**inner, **seqlen}
                inner.pop("curriculum_metrics", None)
            cl = inner
            # reference defaults: outer enabled flags default FALSE; only the
            # seqlen metric is implemented — other metrics must not silently
            # activate a default seqlen schedule
            has_schedule = has_seqlen or not metrics
            enabled = (bool(de.get("enabled", False))
                       and bool(ds_blk.get("enabled", False))
                       and bool(inner.get("enabled", False))
                       and has_schedule)
            if inner.get("enabled", False) and metrics and not has_seqlen:
                logger.warning(
                    "curriculum_learning: only the 'seqlen' metric is "
                    f"supported; metrics {sorted(metrics)} ignored")
        self.curriculum_learning = cl
        self.curriculum_enabled = enabled
        self.load_universal_checkpoint = self.checkpoint_config.load_universal

        self.expert_parallel_size = int(pd.get(C.EXPERT_PARALLEL_SIZE, 1))

        self._resolve_batch_sizes(world_size)

    # ---- batch-size triangulation, reference runtime/config.py:768-794 ----
    def _resolve_batch_sizes(self, world_size: Optional[int]):
        pd = self._param_dict
        if world_size is None:
            try:
                import jax
                world_size = jax.device_count()
            except Exception:
                world_size = 1
        # DP size excludes model/pipe/sequence parallel degrees.
        denom = (self.tensor_parallel.tp_size * self.pipeline.pipeline_parallel_size
                 * self.sequence_parallel_size)
        self.world_size = world_size
        dp = max(1, world_size // max(1, denom))
        self.data_parallel_size = dp

        train_batch = pd.get(C.TRAIN_BATCH_SIZE)
        micro_batch = pd.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        grad_acc = pd.get(C.GRADIENT_ACCUMULATION_STEPS)
        train_batch = None if train_batch == "auto" else train_batch
        micro_batch = None if micro_batch == "auto" else micro_batch
        grad_acc = None if grad_acc == "auto" else grad_acc

        if train_batch is not None and micro_batch is not None and grad_acc is not None:
            if train_batch != micro_batch * grad_acc * dp:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({train_batch}) != micro_batch "
                    f"({micro_batch}) * gas ({grad_acc}) * dp ({dp})")
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // (micro_batch * dp)
            if grad_acc == 0 or train_batch % (micro_batch * dp) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train_batch} not divisible by micro_batch*dp "
                    f"{micro_batch * dp}")
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // (grad_acc * dp)
            if micro_batch == 0 or train_batch % (grad_acc * dp) != 0:
                raise DeepSpeedConfigError("cannot infer micro batch size")
        elif train_batch is not None:
            grad_acc = 1
            micro_batch = train_batch // dp
            if micro_batch == 0 or train_batch % dp != 0:
                raise DeepSpeedConfigError("cannot infer micro batch size")
        elif micro_batch is not None:
            grad_acc = grad_acc or 1
            train_batch = micro_batch * grad_acc * dp
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu "
                "must be provided")

        self.train_batch_size = int(train_batch)
        self.train_micro_batch_size_per_gpu = int(micro_batch)
        self.gradient_accumulation_steps = int(grad_acc)

    # ---- convenience ----
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def model_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def print_config(self):
        logger.info(json.dumps(self._param_dict, indent=2, default=str))
