"""TPU accelerator (the primary backend).

Fills the slot of the reference's `accelerator/cuda_accelerator.py`: device
enumeration, memory stats, and peak-FLOPs tables per TPU generation. The
communication backend name is `xla` — collectives ride ICI/DCN via XLA
(see `deepspeed_tpu/comm`), the counterpart of NCCL selection at
reference `accelerator/cuda_accelerator.py:communication_backend_name`.
"""

from __future__ import annotations

from typing import Any, List

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator

# Per-chip peaks by `device_kind` substring: (dense bf16 TFLOP/s, HBM GB/s,
# HBM bytes). Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v5e: 197 TFLOP/s bf16, 819 GB/s,
# 16 GB). A kind that is not here is an error, never a default: a
# utilization against the wrong chip's peak is worse than none.
_TPU_PEAKS = {
    "v2": (45.0, 700.0, 8 << 30),
    "v3": (123.0, 900.0, 16 << 30),
    "v4": (275.0, 1228.0, 32 << 30),
    "v5e": (197.0, 819.0, 16 << 30),
    "v5 lite": (197.0, 819.0, 16 << 30),
    "v5p": (459.0, 2765.0, 95 << 30),
    "v6e": (918.0, 1640.0, 32 << 30),
    "v6 lite": (918.0, 1640.0, 32 << 30),
}


def on_tpu() -> bool:
    """Is the default JAX backend a TPU? The one place the package asks.
    A backend that fails to initialise raises from here — it must never
    read as "not a TPU", which would quietly select interpret-mode
    kernels or the XLA fallback paths on a broken chip."""
    import jax
    return jax.devices()[0].platform == "tpu"


class TPU_Accelerator(DeepSpeedAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"

    def is_synchronized_device(self) -> bool:
        return False

    def devices(self) -> List[Any]:
        import jax
        return jax.devices() if on_tpu() else []

    def local_device_count(self) -> int:
        import jax
        return jax.local_device_count()

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    def device_kind(self) -> str:
        devs = self.devices()
        return devs[0].device_kind if devs else "unknown"

    def _peaks(self):
        kind = self.device_kind()
        for key, peaks in _TPU_PEAKS.items():
            if key in kind.lower():
                return peaks
        raise ValueError(
            f"no peak table entry for TPU device_kind {kind!r} "
            f"(known: {sorted(_TPU_PEAKS)}); add its spec-sheet numbers to "
            "accelerator/tpu_accelerator.py instead of borrowing a chip's")

    def peak_tflops(self, dtype: str = "bfloat16") -> float:
        tflops = self._peaks()[0]
        return tflops * 2 if dtype in ("int8", "fp8") else tflops

    def peak_hbm_gbps(self) -> float:
        return self._peaks()[1]

    def total_memory(self, device_index=None) -> int:
        reported = self.memory_stats(device_index).get("bytes_limit", 0)
        return reported or self._peaks()[2]

    def is_available(self) -> bool:
        return len(self.devices()) > 0


class CPU_Accelerator(DeepSpeedAccelerator):
    """CPU backend for tests and host-side work (reference: accelerator/cpu_accelerator.py)."""

    def __init__(self):
        super().__init__()
        self._name = "cpu"
        self._communication_backend_name = "gloo"

    def is_synchronized_device(self) -> bool:
        return True

    def devices(self) -> List[Any]:
        import jax
        return [d for d in jax.devices() if d.platform == "cpu"]

    def local_device_count(self) -> int:
        return len(self.devices())

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    def peak_tflops(self, dtype: str = "bfloat16") -> float:
        return 1.0

    def peak_hbm_gbps(self) -> float:
        return 50.0  # nominal DDR bandwidth; CPU rooflines are proxies

    def is_available(self) -> bool:
        return True
