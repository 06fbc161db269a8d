"""Environment report (reference `deepspeed/env_report.py` — `ds_report`).

Prints the TPU-relevant compatibility matrix: jax/jaxlib/flax versions, the
backend and device inventory, Pallas availability, and which framework
features are usable in this environment (the op-builder compatibility table
analog — there is no JIT C++ build to check on TPU; "ops" are Pallas kernels
compiled by XLA at trace time).
"""

from __future__ import annotations

import sys


GREEN_OK = "[OKAY]"
RED_NO = "[NO]"


def _try_version(mod_name: str) -> str:
    try:
        import importlib
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return "not installed"


def report(out=sys.stdout) -> dict:
    import jax

    from deepspeed_tpu.accelerator import on_tpu

    lines = []
    info: dict = {}

    def add(k, v, ok=True):
        info[k] = v
        lines.append(f"{k:.<40} {v} {GREEN_OK if ok else RED_NO}")

    add("jax version", _try_version("jax"))
    add("jaxlib version", _try_version("jaxlib"))
    add("flax version", _try_version("flax"))
    add("optax version", _try_version("optax"))
    add("orbax-checkpoint version", _try_version("orbax.checkpoint"))
    try:
        devs = jax.devices()
        add("backend", jax.default_backend())
        add("device count", str(len(devs)))
        add("device kind", devs[0].device_kind if devs else "none")
        add("pallas kernels (flash attention)",
            "native" if on_tpu() else "interpret-mode", True)
        add("host offload (pinned_host)",
            "native" if on_tpu() else "staged", True)
    except Exception as e:  # no backend at all
        add("backend", f"unavailable ({e})", ok=False)
    add("multi-host (jax.distributed)",
        f"{jax.process_count()} process(es)")

    print("-" * 60, file=out)
    print("DeepSpeed-TPU environment report (ds_report analog)", file=out)
    print("-" * 60, file=out)
    for ln in lines:
        print(ln, file=out)
    return info


def cli_main() -> int:
    report()
    return 0


if __name__ == "__main__":
    sys.exit(cli_main())
