"""Readers of where SET-UP went (`program_counter`, `program_span`): what the
program records of the seconds between the process's start and the judged
window's opening, `[ctx.t_start, ctx.t_start + setup_s]` on `perf_counter`.

The program keeps three kinds of record there, all process-global in
`deepspeed_tpu.telemetry` and all on that clock: the listener's `trace`,
`lower` and `backend_compile` records (`compile_records(kinds)`; each its own
interval `[t - seconds, t]`, a `trace` possibly inside another), the `compile`
span of each named program's first dispatch, and the `import` and `init`
spans. Four metrics beside `setup_compile_s.*` (every backend compile's
seconds, read by `program.py` as before) take disjoint parts of the window:

    setup_trace_lower_s     the union of the trace and lower intervals, less
                            any backend compile that fell inside one
    setup_cache_miss_s      of `setup_compile_s.*`, the compiles the
                            persistent cache did not serve (`cache` not `hit`)
    setup_engine_init_s     the `import` and `init` spans and v2's
                            `pin_layouts`, less every record inside them
    setup_unattributed_s    the window less the union of every record and
                            every span above: the harness and the device where
                            the program records nothing

so trace_lower + compile + engine_init + unattributed is at most `setup_s`,
and what the sum lacks is the rest of the first dispatches (a `compile` span
less the records inside it), which `<cell>.setup.json` lists by program. JAX
compiles on the calling thread, one program at a time; only backend compiles
that overlapped EACH OTHER could carry the sum past `setup_s`.

A program that lacks a record a reader needs (any commit before they were
added: `compile_records` takes no argument there, and no span is named
`init`) gives None, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench import trace as tm
from perfbench.readers.program import _telemetry, stored_spans

KINDS = ("trace", "lower", "backend_compile")
INIT_SPANS = ("import", "init")


# ------------------------------------------------------------ the program


def _records() -> Optional[List[Dict[str, Any]]]:
    """Every record of the three kinds, or None where the program keeps
    only its backend compiles (`compile_records` takes no argument)."""
    fn = _telemetry("compile_records")
    if fn is None:
        return None
    try:
        return fn(KINDS)
    except TypeError:
        return None


def _is_init(span: Dict[str, Any]) -> bool:
    """Engine construction: the `import` and `init` spans, and the one part
    of it that cannot happen before the first program is known, v2's layout
    pin, which the program records as the `compile` span of that phase."""
    return span["name"] in INIT_SPANS or (
        span["name"] == "compile"
        and span["fields"].get("phase") == "pin_layouts")


class Setup:
    """The set-up window of one run, as intervals (unions, clipped to it)."""

    def __init__(self, window: tm.Interval, records: Sequence[Dict[str, Any]],
                 spans: Sequence[Dict[str, Any]]):
        self.window = window
        self.records = [r for r in records if r["t"] - r["seconds"] < window[1]]
        self.spans = [s for s in spans if s["t0"] < window[1]
                      and (_is_init(s) or s["name"] == "compile")]
        self.backend = self._of("backend_compile")
        self.trace_lower = tm.subtract(
            tm.union(self._of("trace") + self._of("lower")), self.backend)
        recorded = tm.union(self.trace_lower + self.backend)
        self.init = tm.subtract(self._named(_is_init), recorded)
        self.covered = tm.union(
            recorded + self._named(lambda s: True))

    def _of(self, kind: str) -> List[tm.Interval]:
        return tm.union(tm.clip(
            [(r["t"] - r["seconds"], r["t"]) for r in self.records
             if r["kind"] == kind], self.window))

    def _named(self, want) -> List[tm.Interval]:
        return tm.union(tm.clip(
            [(s["t0"], s["t1"]) for s in self.spans if want(s)], self.window))

    def unattributed(self) -> float:
        return (self.window[1] - self.window[0]) - tm.length(self.covered)


def setup_of(ctx) -> Optional[Setup]:
    if "setup_s" not in ctx.counters:
        return None
    records, spans = _records(), stored_spans()
    if records is None or spans is None:
        return None
    if not any(s["name"] in INIT_SPANS for s in spans):
        return None     # a program that records no construction
    setup = Setup((ctx.t_start, ctx.t_start + ctx.counters["setup_s"]),
                  records, spans)
    _dump(ctx, setup)
    return setup


# ---------------------------------------------------------------- metrics


def trace_lower_seconds(ctx):
    """Seconds of set-up in which JAX traced a jitted function or lowered a
    jaxpr: the union of those records' intervals, so a trace inside a trace
    counts once."""
    s = setup_of(ctx)
    return None if s is None else tm.length(s.trace_lower)


def cache_miss_seconds(ctx):
    """Seconds of the backend compiles that ended before the window opened
    and that the persistent cache did not serve: on a warm run, what the
    cache did NOT save. Whole records by their end, as `setup_compile_s.*`
    selects them, of which this is a part."""
    s = setup_of(ctx)
    if s is None:
        return None
    return sum(r["seconds"] for r in s.records
               if r["kind"] == "backend_compile" and r["t"] <= s.window[1]
               and r.get("cache") != "hit")


def engine_init_seconds(ctx):
    """Self time of engine construction: the `import` and `init` spans (and
    v2's layout pin) less the trace, lower and backend-compile records that
    fell inside them."""
    s = setup_of(ctx)
    return None if s is None else tm.length(s.init)


def unattributed_seconds(ctx):
    """`setup_s` less everything the program recorded: where the harness
    and the device spend set-up (weights from the seed, the float32
    reference, warm-up programs executing outside a first dispatch, the
    ramp)."""
    s = setup_of(ctx)
    return None if s is None else s.unattributed()


# ------------------------------------------------------------ to read by hand


def _bare(fun_name: Optional[str]) -> str:
    """`jit(f)` and `f` are one program's lowering and tracing."""
    name = fun_name or "?"
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def program_rows(setup: Setup) -> List[Dict[str, Any]]:
    """One row a program: a named program's `compile` span(s) and the
    records that fell inside; else, by the jitted function's name, the
    records outside any such span. A trace inside another trace belongs to
    the outer one's row."""
    rows: Dict[str, Dict[str, Any]] = {}

    def row(key: str) -> Dict[str, Any]:
        return rows.setdefault(key, {
            "program": key, "trace": [], "lower": [], "backend_compile_s": 0.0,
            "backend_compiles": 0, "cache": set(), "first_dispatch_s": None})

    outer: Optional[Tuple[float, float, str]] = None   # the trace open around
    for r in sorted(setup.records, key=lambda r: (r["t"] - r["seconds"], -r["t"])):
        a, b = r["t"] - r["seconds"], r["t"]
        key = r.get("program") or _bare(r.get("fun_name"))
        if r["kind"] == "trace":
            if outer is not None and b <= outer[1]:
                continue
            outer = (a, b, key)
        cur = row(key)
        if r["kind"] == "backend_compile":
            cur["backend_compile_s"] += r["seconds"]
            cur["backend_compiles"] += 1
            cur["cache"].add(r.get("cache", "uncached"))
        else:
            cur[r["kind"]].append((a, b))
    for s in setup.spans:
        if s["name"] == "compile":
            cur = row(s["fields"]["program"])
            cur["first_dispatch_s"] = (cur["first_dispatch_s"] or 0.0) + \
                s["t1"] - s["t0"]
    out = []
    for cur in rows.values():
        traced = tm.union(cur.pop("trace"))
        cur["trace_s"] = tm.length(traced)
        # a lowering traces the inner functions it meets: its self time
        cur["lower_s"] = tm.length(tm.subtract(tm.union(cur.pop("lower")),
                                               traced))
        cur["cache"] = ",".join(sorted(cur["cache"])) or None
        cur["seconds"] = max(cur["first_dispatch_s"] or 0.0, cur["trace_s"]
                             + cur["lower_s"] + cur["backend_compile_s"])
        out.append(cur)
    return sorted(out, key=lambda r: -r["seconds"])


def longest_gaps(setup: Setup, n: int = 8) -> List[Dict[str, Any]]:
    """The longest stretches of the window that nothing recorded covers,
    each with what ended last before it and what began first after it (of
    several, the longest)."""
    marks = [(r["t"] - r["seconds"], r["t"],
              f"{r['kind']} {r.get('program') or _bare(r.get('fun_name'))}")
             for r in setup.records]
    marks += [(s["t0"], s["t1"], " ".join(
        str(x) for x in (s["name"], s["fields"].get("program") or s["engine"])
        if x)) for s in setup.spans]
    w0, w1 = setup.window
    out = []
    for a, b in sorted(tm.subtract([setup.window], setup.covered),
                       key=lambda g: g[0] - g[1])[:n]:
        before = max((m for m in marks if m[1] <= a + 1e-6),
                     key=lambda m: (m[1], -m[0]),
                     default=(w0, w0, "the process's start"))
        after = min((m for m in marks if m[0] >= b - 1e-6),
                    key=lambda m: (m[0], -m[1]),
                    default=(w1, w1, "the window's opening"))
        out.append({"from_s": a - w0, "seconds": b - a,
                    "follows": before[2], "precedes": after[2]})
    return out


def _dump(ctx, setup: Setup) -> None:
    """With PERFBENCH_DUMP set (as for the harness's own dumps): set-up by
    part, engine construction by child, the longest stretches that nothing
    recorded covers, and one row a program. Never read back."""
    dump = os.environ.get("PERFBENCH_DUMP")
    if not dump or getattr(ctx, "_setup_dumped", False):
        return
    ctx._setup_dumped = True
    os.makedirs(dump, exist_ok=True)
    spans = stored_spans() or []
    inits = [{"engine": s["engine"], "seconds": s["t1"] - s["t0"],
              "children": [{"name": c["name"], "seconds": c["t1"] - c["t0"],
                            **c["fields"]}
                           for c in spans if c.get("parent") == s["id"]]}
             for s in spans if s["name"] == "init"]
    backend = [r for r in setup.records if r["kind"] == "backend_compile"]
    doc = {"setup_s": setup.window[1] - setup.window[0],
           "import_s": sum(s["t1"] - s["t0"] for s in spans
                           if s["name"] == "import"),
           "init": inits,
           "trace_lower_s": tm.length(setup.trace_lower),
           "backend_compile_s": sum(r["seconds"] for r in backend),
           "cache": {c: [sum(1 for r in backend if r.get("cache") == c),
                         sum(r["seconds"] for r in backend
                             if r.get("cache") == c)]
                     for c in ("hit", "miss", "uncached")},
           "engine_init_s": tm.length(setup.init),
           "unattributed_s": setup.unattributed(),
           "records": {k: sum(1 for r in setup.records if r["kind"] == k)
                       for k in KINDS},
           "gaps": longest_gaps(setup),
           "programs": program_rows(setup)}
    name = os.path.join(dump, ctx.workload["name"] + ".setup.json")
    with open(name, "w") as f:
        json.dump(doc, f, indent=1, default=str)
