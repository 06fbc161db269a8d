"""Op builder registry (reference `op_builder/builder.py`: `OpBuilder:109`,
`jit_load:533`, `op_builder/all_ops.py`).

Two kinds of "ops" exist on TPU:
- **Pallas/XLA ops** (flash attention, fused optimizers, quantization):
  compiled by XLA at trace time — `load()` simply returns the python module
  exposing them (`is_compatible` reports where the fast path runs).
- **Native host ops** (async NVMe I/O): real C++ JIT-compiled with g++ into
  a shared library on first `load()` and cached under ~/.cache — the
  `jit_load` flow, with ctypes instead of pybind11.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib
import os
import subprocess
from typing import Any, Dict, Optional

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class OpBuilder:
    BUILD_VAR = "DS_BUILD_OPS"
    NAME = "op"

    def is_compatible(self, verbose: bool = False) -> bool:
        return True

    def load(self, verbose: bool = False):
        raise NotImplementedError

    # ---- native JIT machinery (reference jit_load:533) ----
    def jit_load_ctypes(self, sources, extra_flags=()) -> ctypes.CDLL:
        src_paths = [os.path.join(_REPO_ROOT, s) for s in sources]
        blob = b"".join(open(p, "rb").read() for p in src_paths)
        tag = hashlib.sha1(blob).hexdigest()[:12]
        cache = os.environ.get("DS_TPU_OP_CACHE",
                               os.path.expanduser("~/.cache/deepspeed_tpu/ops"))
        os.makedirs(cache, exist_ok=True)
        so_path = os.path.join(cache, f"{self.NAME}_{tag}.so")
        if not os.path.exists(so_path):
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
                   *extra_flags, *src_paths, "-o", so_path]
            logger.info(f"op_builder: compiling {self.NAME}: {' '.join(cmd)}")
            subprocess.run(cmd, check=True, capture_output=True)
        return ctypes.CDLL(so_path)


class _PythonOpBuilder(OpBuilder):
    """Pallas/XLA-backed op: load() returns the implementing module."""
    MODULE = ""

    def load(self, verbose: bool = False):
        return importlib.import_module(self.MODULE)


class FusedAdamBuilder(_PythonOpBuilder):
    NAME = "fused_adam"
    MODULE = "deepspeed_tpu.ops.optimizers"


class FusedLambBuilder(_PythonOpBuilder):
    NAME = "fused_lamb"
    MODULE = "deepspeed_tpu.ops.optimizers"


class CPUAdamBuilder(_PythonOpBuilder):
    # host-compute Adam (compute_on('device_host')) — engine wires it
    NAME = "cpu_adam"
    MODULE = "deepspeed_tpu.ops.optimizers"


class FlashAttentionBuilder(_PythonOpBuilder):
    NAME = "flash_attn"
    MODULE = "deepspeed_tpu.ops.pallas.flash_attention"

    def is_compatible(self, verbose: bool = False) -> bool:
        from deepspeed_tpu.accelerator import on_tpu
        return on_tpu()


class QuantizerBuilder(_PythonOpBuilder):
    NAME = "quantizer"
    MODULE = "deepspeed_tpu.ops.quantization"


class TransformerBuilder(_PythonOpBuilder):
    NAME = "transformer"
    MODULE = "deepspeed_tpu.ops.attention"


class InferenceCoreBuilder(_PythonOpBuilder):
    NAME = "inference_core_ops"
    MODULE = "deepspeed_tpu.inference.kv_cache"


class AsyncIOBuilder(OpBuilder):
    """Native async file I/O (reference op_builder/async_io.py + csrc/aio)."""
    NAME = "async_io"
    SOURCES = ["csrc/aio/ds_aio.cpp"]

    def is_compatible(self, verbose: bool = False) -> bool:
        from shutil import which
        return which("g++") is not None

    def load(self, verbose: bool = False):
        lib = self.jit_load_ctypes(self.SOURCES)
        lib.ds_aio_create.restype = ctypes.c_void_p
        lib.ds_aio_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.ds_aio_destroy.argtypes = [ctypes.c_void_p]
        lib.ds_aio_open.restype = ctypes.c_int
        lib.ds_aio_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.ds_aio_close.argtypes = [ctypes.c_int]
        for fn in (lib.ds_aio_pread, lib.ds_aio_pwrite):
            fn.restype = ctypes.c_longlong
            fn.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
                           ctypes.c_longlong, ctypes.c_longlong]
        lib.ds_aio_wait.restype = ctypes.c_longlong
        lib.ds_aio_wait.argtypes = [ctypes.c_void_p]
        lib.ds_aio_create_ex.restype = ctypes.c_void_p
        lib.ds_aio_create_ex.argtypes = [ctypes.c_int, ctypes.c_int,
                                         ctypes.c_longlong]
        lib.ds_aio_using_uring.restype = ctypes.c_int
        lib.ds_aio_using_uring.argtypes = [ctypes.c_void_p]
        return lib


ALL_OPS: Dict[str, Any] = {
    b.NAME: b for b in (FusedAdamBuilder, FusedLambBuilder, CPUAdamBuilder,
                        FlashAttentionBuilder, QuantizerBuilder,
                        TransformerBuilder, InferenceCoreBuilder,
                        AsyncIOBuilder)
}


def get_op_builder(name: str) -> OpBuilder:
    """Reference accelerator `get_op_builder` surface."""
    return ALL_OPS[name]()
