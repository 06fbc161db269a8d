from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.real_accelerator import (
    get_accelerator,
    is_current_accelerator_supported,
    set_accelerator,
)
from deepspeed_tpu.accelerator.tpu_accelerator import on_tpu

__all__ = [
    "DeepSpeedAccelerator",
    "get_accelerator",
    "set_accelerator",
    "is_current_accelerator_supported",
    "on_tpu",
]
