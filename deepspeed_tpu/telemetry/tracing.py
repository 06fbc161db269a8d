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

Set-up: one pair of ``jax.monitoring`` listeners (installed when this
package is imported) hears the whole of a compile. Every tracing of a jitted
function, every lowering to an MLIR module and every backend compile (or
load from the persistent cache) becomes a record with its ``kind``
(``trace``, ``lower``, ``backend_compile``), its end on ``perf_counter``
and its seconds; a backend compile also says what the persistent cache did
(``cache``: ``hit``, ``miss`` or ``uncached``) and is added to the hub
counters ``compile_seconds_total`` / ``compiles_total``. A tracing inside
another (an inner ``jit``) is a record of its own with its own interval:
readers take unions, never sums. ``compile_span`` marks the first dispatch
of a named program, so that what a program cost to trace, lower and compile
is one span with its name on it; ``init_span`` / ``init_phase`` mark an
engine's construction and its parts; ``note_import`` the package's import.
All of it is on ``perf_counter``, the span store's clock: set-up ends before
any profiler session starts.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence

_COMPILE = "/jax/core/compile/"
_CACHE = "/jax/compilation_cache/"
COMPILE_EVENT = _COMPILE + "backend_compile_duration"
KINDS = {_COMPILE + "jaxpr_trace_duration": "trace",
         _COMPILE + "jaxpr_to_mlir_module_duration": "lower",
         COMPILE_EVENT: "backend_compile"}
_RECORD_CAP = 65536           # of each kind: the last ones are kept
_RECORDS: Dict[str, collections.deque] = {
    kind: collections.deque(maxlen=_RECORD_CAP) for kind in KINDS.values()}
# what the persistent cache has said since the last backend compile ended:
# JAX reports a hit, a miss and the retrieval's seconds INSIDE the compile
# they belong to, before its duration, so that compile's record takes them
_CACHE_SAID: Dict[str, Any] = {}
_PROGRAM: List[str] = []      # the compile_span(s) open now, innermost last
_INIT: List[Dict[str, Any]] = []   # the init_span(s) open now, innermost last
_installed = False


@contextlib.contextmanager
def trace_capture(logdir: str,
                  create_perfetto_link: bool = False) -> Iterator[str]:
    """Capture a profiler trace of the enclosed block into ``logdir``
    (open the result with perfetto / tensorboard's profile plugin). As it
    closes it writes ``program_map.json`` beside the trace: the table of
    every compiled program's instructions by scope
    (``telemetry.program_map``), which ``python -m deepspeed_tpu.telemetry
    --by-scope <logdir>`` joins to the device ops. That is the one place
    the map is built unasked: after the trace has stopped, in a run whose
    operator asked for a trace."""
    import jax
    from deepspeed_tpu.telemetry.program_map import write_program_map
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
        write_program_map(logdir)


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
    kind = KINDS.get(event)
    if kind is None:
        if event == _CACHE + "cache_retrieval_time_sec":
            _CACHE_SAID["retrieval_s"] = float(duration)
        elif event == _CACHE + "compile_time_saved_sec":
            _CACHE_SAID["saved_s"] = float(duration)
        return
    rec = {"kind": kind, "t": time.perf_counter(), "seconds": float(duration),
           "fun_name": kw.get("fun_name"),
           "program": _PROGRAM[-1] if _PROGRAM else None}
    if kind == "backend_compile":
        from deepspeed_tpu.telemetry.hub import get_hub
        hub = get_hub()
        hub.counter("compile_seconds_total", duration)
        hub.counter("compiles_total")
        rec["cache"] = _CACHE_SAID.pop("cache", "uncached")
        rec.update(_CACHE_SAID)
        _CACHE_SAID.clear()
    _RECORDS[kind].append(rec)


def _on_event(event: str, **kw) -> None:
    if event == _CACHE + "cache_hits":
        _CACHE_SAID["cache"] = "hit"
    elif event == _CACHE + "cache_misses":
        _CACHE_SAID["cache"] = "miss"


def install_compile_listener() -> None:
    """Idempotent; touches no backend."""
    global _installed
    if _installed:
        return
    _installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_records(kinds: Sequence[str] = ("backend_compile",)
                    ) -> List[Dict[str, Any]]:
    """The last records of `kinds`, oldest first; called bare, the backend
    compiles (or loads from the persistent cache) alone. Each: `kind`
    (`trace`: a jitted function traced to a jaxpr; `lower`: a jaxpr lowered
    to an MLIR module; `backend_compile`), `t` (`perf_counter` at its end),
    `seconds` (its own interval is [t - seconds, t]; one `trace` may lie
    inside another), `fun_name` (the jitted function, as JAX names it),
    `program` (the `compile_span` it fell in). A `backend_compile` also
    carries `cache`: `hit` (loaded from the persistent cache; then also
    `retrieval_s`, and `saved_s`, the compile's seconds when it was
    written less the retrieval's), `miss` (compiled, and written to it) or
    `uncached` (the cache is off, JAX did not consult it, or the compile
    was too quick to keep)."""
    recs = [r for kind in kinds for r in _RECORDS[kind]]
    return recs if len(kinds) == 1 else sorted(recs, key=lambda r: r["t"])


def compile_totals():
    """(count, seconds) of the backend compiles the hub has counted."""
    from deepspeed_tpu.telemetry.hub import get_hub
    counters = get_hub().counters
    return (int(counters.get("compiles_total", 0)),
            float(counters.get("compile_seconds_total", 0.0)))


def union_seconds(records: Iterable[Dict[str, Any]],
                  since: float = float("-inf")) -> float:
    """Length of the union of the records' own intervals, each cut at
    `since`: nested and overlapping ones count once."""
    total, end = 0.0, since
    for a, b in sorted((r["t"] - r["seconds"], r["t"]) for r in records):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def _since(kind: str, t0: float) -> List[Dict[str, Any]]:
    """The records of `kind` that ended at or after `t0`."""
    out = []
    for r in reversed(_RECORDS[kind]):
        if r["t"] < t0:
            break
        out.append(r)
    return out


def _worst_cache(backend: Sequence[Dict[str, Any]]) -> str:
    """`miss`, `uncached` or `hit`, whichever comes first among the
    backend compiles of one span; `uncached` where there was none."""
    said = {r["cache"] for r in backend}
    return next((w for w in ("miss", "uncached", "hit") if w in said),
                "uncached")


@contextlib.contextmanager
def compile_span(program: str, engine: str, phase: str = "first_dispatch",
                 under=(None, None)) -> Iterator[Dict[str, Any]]:
    """Around the first dispatch of a named program (or the ahead-of-time
    compile that pins v2's layouts): one span named `compile` in the span
    store, whatever the tracer's state, with what the listener heard inside
    it (tracing and lowering as unions, backend compiles and what the cache
    said of them as sums); a `compile` event on an enabled hub. The span
    less those is the rest of a first dispatch: argument checks, the feeds'
    `device_put`, the dispatch and, where the caller fetches, the run.
    It yields a dict: what the caller puts there of the program it traced
    (the train engine's `tp_exchange_sites` and `dw_exchange_sites`) joins the
    span's fields. A
    program kept for the map (`program_map.keep`, by the caller, inside
    this span) is told what the persistent cache said of this compile.
    `under` is the (id, round) of the span it happens in
    (`RequestTracer.current()`)."""
    from deepspeed_tpu.telemetry.hub import get_hub
    from deepspeed_tpu.telemetry.spans import (ANNOTATION_PREFIX, _IDS,
                                               get_span_store)
    _PROGRAM.append(program)
    t0 = time.perf_counter()
    found: Dict[str, Any] = {}   # what the caller read off the program
    try:
        with annotate(ANNOTATION_PREFIX + "compile"):
            yield found
    finally:
        t1 = time.perf_counter()
        _PROGRAM.pop()
        backend = _since("backend_compile", t0)
        fields = {"program": program, "phase": phase,
                  "backend_compiles": len(backend),
                  "backend_compile_s": round(
                      sum(r["seconds"] for r in backend), 6),
                  "trace_s": round(union_seconds(_since("trace", t0), t0), 6),
                  "lower_s": round(union_seconds(_since("lower", t0), t0), 6),
                  "cache_hits": sum(r["cache"] == "hit" for r in backend),
                  "cache_misses": sum(r["cache"] == "miss" for r in backend),
                  "cache_retrieval_s": round(
                      sum(r.get("retrieval_s", 0.0) for r in backend), 6),
                  **found}
        if phase == "first_dispatch":
            from deepspeed_tpu.telemetry.program_map import \
                note_first_dispatch
            note_first_dispatch(program, _worst_cache(backend))
        get_span_store().add({
            "name": "compile", "t0": t0, "t1": t1, "id": next(_IDS),
            "parent": under[0], "round": under[1], "uids": None,
            "engine": engine, "fields": fields}, setup=True)
        hub = get_hub()
        if hub.enabled:
            hub.emit("compile", engine=engine,
                     dur_ms=round((t1 - t0) * 1e3, 3), **fields)


# ------------------------------------------------------ engine construction
def _open(name: str, engine: Optional[str], t0: float,
          fields: Dict[str, Any], outer: Optional[Dict[str, Any]]
          ) -> Dict[str, Any]:
    """A span of engine construction, opened under `outer`, with the `ds:`
    annotation entered."""
    from jax.profiler import TraceAnnotation
    from deepspeed_tpu.telemetry.spans import ANNOTATION_PREFIX, _IDS
    note = TraceAnnotation(ANNOTATION_PREFIX + name)
    note.__enter__()
    return {"name": name, "t0": t0, "id": next(_IDS), "engine": engine,
            "parent": None if outer is None else outer["id"],
            "depth": 0 if outer is None else outer["depth"] + 1,
            "round": None, "uids": None, "fields": fields,
            "note": note, "child": None}


def _close(rec: Dict[str, Any], t1: float) -> None:
    from deepspeed_tpu.telemetry.spans import get_span_store
    rec.pop("note").__exit__(None, None, None)
    rec.pop("child")
    rec["t1"] = t1
    get_span_store().add(rec, setup=True)


@contextlib.contextmanager
def init_span(engine: str) -> Iterator[None]:
    """Around an engine's construction (as a decorator of its `__init__`):
    one span named `init` in the span store, whatever the tracer's state
    (it runs once), whose sequential children `init_phase` opens. Host time
    only: nothing here waits for the device."""
    outer = _INIT[-1] if _INIT else None    # an engine built by an engine
    if outer is not None and outer["child"] is not None:
        outer = outer["child"]
    rec = _open("init", engine, time.perf_counter(), {}, outer)
    _INIT.append(rec)
    try:
        yield
    finally:
        now = time.perf_counter()   # one reading: the children tile it
        _INIT.pop()
        if rec["child"] is not None:
            _close(rec["child"], now)
        _close(rec, now)


def init_phase(name: str, **fields) -> Dict[str, Any]:
    """Open the next part of the engine construction under way, and close
    the part before it: `plan` (mesh, partition plan, configuration),
    `place_params` (casts, `device_put`, re-layout), `alloc_cache`,
    `init_optimizer`, `build_programs` (jit objects made, nothing traced).
    The first part starts where its `init` does and the last ends with it.
    Returns the part's mutable fields; a part that leaves work running on
    the device sets `async` (`device_busy`), and the wait then shows under
    whichever later span blocks. Outside any `init_span` (a state built or
    re-placed later) nothing is recorded."""
    if not _INIT:
        return fields
    rec = _INIT[-1]
    if rec["child"] is None:
        now = rec["t0"]
    else:
        now = time.perf_counter()
        _close(rec["child"], now)
    rec["child"] = _open(name, rec["engine"], now, fields, rec)
    return fields


def device_busy(tree) -> bool:
    """Whether any array of `tree` is still being computed or copied: a
    poll (`jax.Array.is_ready`), never a wait."""
    import jax
    return any(not leaf.is_ready() for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "is_ready"))


def note_import(t0: float) -> None:
    """The package's import, begun at `t0` on `perf_counter` and over now:
    one span named `import` in the span store."""
    from deepspeed_tpu.telemetry.spans import _IDS, get_span_store
    get_span_store().add({
        "name": "import", "t0": t0, "t1": time.perf_counter(),
        "id": next(_IDS), "parent": None, "depth": 0, "round": None,
        "uids": None, "engine": None, "fields": {}}, setup=True)
