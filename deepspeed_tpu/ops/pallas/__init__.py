"""Pallas TPU kernels."""

import os

from deepspeed_tpu.accelerator import on_tpu


def _interpret() -> bool:
    """Whether the kernels run in the Pallas interpreter: off the chip
    (the CPU golden tests) or when DS_TPU_PALLAS_INTERPRET asks."""
    return bool(os.environ.get("DS_TPU_PALLAS_INTERPRET")) or not on_tpu()
