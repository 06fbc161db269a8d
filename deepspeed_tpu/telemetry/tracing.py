"""Trace capture hooks, and where set-up time goes.

``trace_capture`` wraps ``jax.profiler.start_trace``/``stop_trace`` so a
perfetto trace of any step range is one context manager (``engine.trace``
is its user-facing form). ``annotate`` is the named-phase
marker (``jax.profiler.TraceAnnotation``) the engines place around
fwd/bwd/step/fetch dispatches — annotations cost nothing when no trace is
being captured, so the hot paths keep them unconditionally.

The serving loop's own spans reach such a trace through the
``RequestTracer`` (telemetry/spans.py): while it is active, every
``span()`` enters ``TraceAnnotation("ds:<name>")``. To turn it on for a
profile without a JSONL sink, set ``engine.tracer.force = True`` before the
``with trace_capture(dir):`` block; with ``DS_TPU_TELEMETRY_JSONL`` set (or
a telemetry config block) it is on already.

Set-up: one ``jax.monitoring`` listener (installed when this package is
imported) adds every backend compile, or load from the persistent cache, to
the hub counters ``compile_seconds_total`` / ``compiles_total`` and keeps
the last of them as records; ``compile_span`` marks the first dispatch of a
named program, so that what a program cost to compile is one record with
its name on it.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Any, Dict, Iterator, List

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES: collections.deque = collections.deque(maxlen=4096)
_TOTALS = [0, 0.0]            # count and seconds of every backend compile
_PROGRAM: List[str] = []      # the compile_span(s) open now, innermost last
_installed = False


@contextlib.contextmanager
def trace_capture(logdir: str,
                  create_perfetto_link: bool = False) -> Iterator[str]:
    """Capture a profiler trace of the enclosed block into ``logdir``
    (open the result with perfetto / tensorboard's profile plugin)."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named phase marker visible in the captured trace timeline."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # profiler unavailable: annotations are cosmetic
        yield
        return
    with TraceAnnotation(name):
        yield


# ------------------------------------------------------------------ set-up
def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    from deepspeed_tpu.telemetry.hub import get_hub
    hub = get_hub()
    hub.counter("compile_seconds_total", duration)
    hub.counter("compiles_total")
    _TOTALS[0] += 1
    _TOTALS[1] += duration
    _COMPILES.append({"t": time.perf_counter(), "seconds": float(duration),
                      "fun_name": kw.get("fun_name"),
                      "program": _PROGRAM[-1] if _PROGRAM else None})


def install_compile_listener() -> None:
    """Idempotent; touches no backend."""
    global _installed
    if _installed:
        return
    _installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_records() -> List[Dict[str, Any]]:
    """The last backend compiles (or loads from the persistent cache), oldest
    first: `t` (`perf_counter` at its end), `seconds`, `fun_name` (the jitted
    function, as JAX names it), `program` (the `compile_span` it fell in)."""
    return list(_COMPILES)


def compile_totals():
    """(count, seconds) of every backend compile this process has seen."""
    return _TOTALS[0], _TOTALS[1]


@contextlib.contextmanager
def compile_span(program: str, engine: str, phase: str = "first_dispatch",
                 under=(None, None)) -> Iterator[None]:
    """Around the first dispatch of a named program (or the ahead-of-time
    compile that pins v2's layouts): one span named `compile` in the span
    store, whatever the tracer's state, with the backend compiles that fell
    inside it; a `compile` event on an enabled hub. `under` is the (id,
    round) of the span it happens in (`RequestTracer.current()`)."""
    from deepspeed_tpu.telemetry.hub import get_hub
    from deepspeed_tpu.telemetry.spans import (ANNOTATION_PREFIX, _IDS,
                                               get_span_store)
    n0, s0 = compile_totals()
    _PROGRAM.append(program)
    t0 = time.perf_counter()
    try:
        with annotate(ANNOTATION_PREFIX + "compile"):
            yield
    finally:
        t1 = time.perf_counter()
        _PROGRAM.pop()
        n1, s1 = compile_totals()
        fields = {"program": program, "phase": phase,
                  "backend_compiles": n1 - n0,
                  "backend_compile_s": round(s1 - s0, 6)}
        get_span_store().add({
            "name": "compile", "t0": t0, "t1": t1, "id": next(_IDS),
            "parent": under[0], "round": under[1], "uids": None,
            "engine": engine, "fields": fields})
        hub = get_hub()
        if hub.enabled:
            hub.emit("compile", engine=engine,
                     dur_ms=round((t1 - t0) * 1e3, 3), **fields)
