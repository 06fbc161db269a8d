"""In-step training metrics.

The reference stack observes training by reading host state the torch
engine mutates as it goes (grad norms inside ``stage3.step``, the overflow
flag, router counters). Here every capability is a property of the compiled
step — per the architecture invariant "never host-side mutation mid-step" —
so the metrics are too: ``MetricsState`` is a small pytree COMPUTED INSIDE
the jitted train step and returned next to the loss. One extra program
output, zero extra dispatches; the host fetches it together with the loss
in a single transfer (each fetch syncs the host with the device, so
per-metric fetches would stall the dispatch pipeline once per metric).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import numpy as np


class MetricsState(NamedTuple):
    """Per-step metrics produced inside the compiled train step.

    Scalars unless noted. ``aux`` carries whatever the model's loss fn
    reported (lm_loss, moe_aux_loss, and — for MoE families — per-layer
    ``router_load`` (L, E) / ``router_drop`` (L,) arrays), averaged over
    the GAS window's micro-batches.
    """
    global_step: Any      # i32, AFTER this step (skipped steps don't count)
    grad_norm: Any        # f32 pre-clip global L2 of the unscaled grads
    param_norm: Any       # f32 global L2 of the params entering the step
    loss_scale: Any       # f32 scale the window ran at
    overflow: Any         # bool, this window's optimizer step was skipped
    skipped_steps: Any    # i32 cumulative skipped steps
    good_micros: Any      # i32 finite micros in the window just closed
    lr: Any               # f32 learning rate applied
    aux: Dict[str, Any]   # model-side metrics (see class docstring)


# Aux arrays at or under this many elements are inlined verbatim into the
# JSONL event; larger ones are summarized to min/mean/max. Keeps router-load
# tables readable without letting a 64-expert 80-layer model bloat every line.
_INLINE_ELEMENTS = 64


def host_metrics(m: MetricsState) -> Dict[str, Any]:
    """Flatten an (already fetched) MetricsState to plain JSON-able values.

    Field names are part of the JSONL schema (docs/telemetry.md) — keep
    them stable across rounds, like the bench metric name.
    """
    if m is None:
        return {}
    out = {
        "global_step": int(m.global_step),
        "grad_norm": float(m.grad_norm),
        "param_norm": float(m.param_norm),
        "loss_scale": float(m.loss_scale),
        "overflow": bool(m.overflow),
        "skipped_steps": int(m.skipped_steps),
        "good_micros": int(m.good_micros),
        "lr": float(m.lr),
    }
    for name, val in (m.aux or {}).items():
        arr = np.asarray(val)
        if arr.ndim == 0:
            out[name] = float(arr)
        elif arr.size <= _INLINE_ELEMENTS:
            out[name] = np.asarray(arr, np.float64).round(6).tolist()
            out[f"{name}_mean"] = float(arr.mean())
        else:
            out[f"{name}_min"] = float(arr.min())
            out[f"{name}_mean"] = float(arr.mean())
            out[f"{name}_max"] = float(arr.max())
    return out
