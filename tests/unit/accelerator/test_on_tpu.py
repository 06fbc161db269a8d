"""The one on-chip predicate and the peak tables behind it.

What these pin: a backend that cannot initialise RAISES out of every
platform check (it used to read as "not a TPU" and quietly select
interpret-mode kernels or the XLA paths), a TPU the peak tables do not know
is an error and never answered as a v5e, and the measuring entry script
refuses to run off the chip.
"""

import os
import subprocess
import sys

import jax
import pytest

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("DS_TPU_FAULTS", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_on_tpu_is_false_on_the_cpu_mesh():
    assert on_tpu() is False


def _interpret():
    from deepspeed_tpu.ops.pallas import _interpret
    return _interpret()


def _use_pallas():
    from deepspeed_tpu.ops.attention import _use_pallas
    return _use_pallas()


def _flash_builder_compatible():
    from deepspeed_tpu.op_builder.builder import FlashAttentionBuilder
    return FlashAttentionBuilder().is_compatible()


def _v1_auto_layouts():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    eng = InferenceEngine.__new__(InferenceEngine)
    eng._config = DeepSpeedInferenceConfig()
    return eng._auto_layouts()


@pytest.mark.parametrize("check", [
    on_tpu, _interpret, _use_pallas, _flash_builder_compatible,
    _v1_auto_layouts, lambda: TPU_Accelerator().devices()],
    ids=["on_tpu", "pallas_interpret", "attention_use_pallas",
         "flash_op_builder", "v1_auto_layouts", "accelerator_devices"])
def test_backend_error_propagates(check, monkeypatch):
    def broken(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", broken)
    monkeypatch.delenv("DS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        check()


@pytest.mark.parametrize("kind,tflops,gbps,hbm", [
    ("TPU v5 lite", 197.0, 819.0, 16 << 30),
    ("TPU v5e", 197.0, 819.0, 16 << 30),
    ("TPU v4", 275.0, 1228.0, 32 << 30),
    ("TPU v6 lite", 918.0, 1640.0, 32 << 30),
])
def test_peak_tables_by_device_kind(kind, tflops, gbps, hbm, monkeypatch):
    acc = TPU_Accelerator()
    monkeypatch.setattr(acc, "device_kind", lambda: kind)
    monkeypatch.setattr(acc, "memory_stats", lambda device_index=None: {})
    assert acc.peak_tflops("bfloat16") == tflops
    assert acc.peak_tflops("int8") == 2 * tflops
    assert acc.peak_hbm_gbps() == gbps
    assert acc.total_memory() == hbm


@pytest.mark.parametrize("method", ["peak_tflops", "peak_hbm_gbps",
                                    "total_memory"])
def test_unknown_device_kind_raises(method, monkeypatch):
    acc = TPU_Accelerator()
    monkeypatch.setattr(acc, "device_kind", lambda: "TPU v9 mega")
    monkeypatch.setattr(acc, "memory_stats", lambda device_index=None: {})
    with pytest.raises(ValueError, match="TPU v9 mega"):
        getattr(acc, method)()


def test_total_memory_prefers_what_the_runtime_reports(monkeypatch):
    acc = TPU_Accelerator()
    monkeypatch.setattr(acc, "device_kind", lambda: "TPU v5 lite")
    monkeypatch.setattr(acc, "memory_stats",
                        lambda device_index=None: {"bytes_limit": 123})
    assert acc.total_memory() == 123


def test_import_initialises_no_backend():
    """A launcher parent imports the package and then starts the children
    that own the chip; an import that touched a backend would take it."""
    code = ("import deepspeed_tpu, deepspeed_tpu.launcher.runner, "
            "deepspeed_tpu.elasticity.elastic_agent\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=_cpu_env(), timeout=120)
