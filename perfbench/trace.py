"""From a profiler trace to numbers: the reduction every PR is measured by.

Two halves. `read_xplane` turns the `.xplane.pb` the JAX profiler writes into
a small neutral form (plain lists, nanoseconds), and needs JAX. Everything
after it is arithmetic on that form and needs nothing, so it is checked on the
CPU against a small recorded trace (`tests/perfbench/data/`).

Neutral form:

    {"devices": {"<id>": {"ops": [[name, start_ns, dur_ns], ...],
                          "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}      # the harness's annotations

Device op events of one line may nest (a `while` holds its body's ops), so
busy time is a UNION of intervals and time by name is SELF time.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

HOST_PREFIX = "pb:"          # the harness's own TraceAnnotation names
# collective ops as XLA names them on the device's op line, sync or async
COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|async-collective|send|recv)")
_SUFFIX_RE = re.compile(r"(\.\d+)+$")


def op_name(raw: str) -> str:
    """`%fusion.123 = bf16[...] fusion(...)` -> `fusion`: the device line
    carries the whole HLO instruction; the name before ` = `, less its
    number, is one name per kind of op, so that totals survive a recompile
    that renumbers them."""
    return _SUFFIX_RE.sub("", raw.split(" = ", 1)[0].strip().lstrip("%"))


# -------------------------------------------------------------- extraction


def newest_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, inventory: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """The neutral form of one `.xplane.pb`. With `inventory` (a dict to
    fill): every plane and line with its event count, to read by hand."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in data.planes:
        dev = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            events = list(line.events)
            if inventory is not None:
                inventory.setdefault(plane.name, {})[line.name] = len(events)
            if dev and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                out["devices"].setdefault(dev.group(1), {
                    "ops": [], "modules": []})[key] += [
                    [e.name.split(" = ", 1)[0], float(e.start_ns),
                     float(e.duration_ns)] for e in events]
            elif plane.name.startswith("/host:"):
                out["host"] += [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in events if e.name.startswith(HOST_PREFIX)]
    out["host"].sort(key=lambda e: e[1])
    return out


# --------------------------------------------------------------- intervals


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if min(b, w1) > max(a, w0)]


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of `intervals` (a union) that no hole (a union) covers."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def spans_of(events: Sequence[Sequence], window: Optional[Interval] = None
             ) -> List[Interval]:
    iv = [(e[1], e[1] + e[2]) for e in events]
    return clip(iv, window) if window else iv


# ------------------------------------------------------------- reductions


def trace_window(trace: Dict[str, Any]) -> Interval:
    """The traced window as the harness marked it: its `pb:traced`
    annotation; else the span of all device ops."""
    marks = [e for e in trace["host"] if e[0] == HOST_PREFIX + "traced"]
    if marks:
        return (marks[0][1], marks[0][1] + marks[0][2])
    ops = [e for d in trace["devices"].values() for e in d["ops"]]
    if not ops:
        raise ValueError("no device operation in the trace")
    return (min(e[1] for e in ops), max(e[1] + e[2] for e in ops))


def busy_seconds(trace: Dict[str, Any], window: Interval) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    devices that ran any."""
    per = [length(union(spans_of(d["ops"], window))) / 1e9
           for d in trace["devices"].values() if d["ops"]]
    if not per:
        raise ValueError("no device operation in the trace")
    return sum(per) / len(per)


def idle_gaps(ops: Sequence[Sequence], window: Interval) -> List[Interval]:
    return subtract([window], union(spans_of(ops, window)))


def attribute_gaps(gaps: Sequence[Interval], host: Sequence[Sequence]
                   ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap (sorted, disjoint)
    is split among the harness annotations it overlaps; what none covers is
    `(unannotated)`. `pb:traced` spans everything and is only the fallback."""
    out: Dict[str, float] = {}
    ends = [b for _, b in gaps]
    covered: List[Interval] = []
    for name, start, dur in host:
        if name == HOST_PREFIX + "traced":
            continue
        i = bisect.bisect_right(ends, start)
        got = 0.0
        while i < len(gaps) and gaps[i][0] < start + dur:
            got += min(gaps[i][1], start + dur) - max(gaps[i][0], start)
            i += 1
        if got > 0:
            out[name] = out.get(name, 0.0) + got / 1e9
            covered.append((start, start + dur))
    rest = length(subtract(list(gaps), union(covered))) / 1e9
    if rest > 0:
        out["(unannotated)"] = rest
    return out


def self_times(ops: Sequence[Sequence], window: Optional[Interval] = None
               ) -> Dict[str, float]:
    """Seconds by op name, each op's time less that of the ops nested in it
    (events of one line are properly nested or disjoint)."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []   # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for raw, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        a, b = start, start + dur
        if window:
            a, b = max(a, window[0]), min(b, window[1])
            if b <= a:
                continue
        close(a)
        if stack:
            stack[-1][2] -= (min(b, stack[-1][1]) - a)
        stack.append([op_name(raw), b, b - a])
    close(float("inf"))
    return out


def seconds_matching(ops: Sequence[Sequence], pattern: str,
                     window: Optional[Interval] = None) -> float:
    """Self seconds of the ops whose name matches `pattern` (a regex,
    searched in the suffix-free name)."""
    rx = re.compile(pattern)
    return sum(s for n, s in self_times(ops, window).items() if rx.search(n))


def exposed_collective_seconds(ops: Sequence[Sequence], window: Interval
                               ) -> float:
    """Seconds inside collective ops during which no compute op ran on this
    device. Container ops (an op with others nested in it) are neither."""
    leaf = _leaves(ops)
    coll = [e for e in leaf if COLLECTIVE_RE.match(op_name(e[0]))]
    comp = [e for e in leaf if not COLLECTIVE_RE.match(op_name(e[0]))]
    return length(subtract(union(spans_of(coll, window)),
                           union(spans_of(comp, window)))) / 1e9


def _leaves(ops: Sequence[Sequence]) -> List[Sequence]:
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        nested = nxt is not None and nxt[1] < e[1] + e[2] and \
            nxt[1] + nxt[2] <= e[1] + e[2]
        if not nested:
            out.append(e)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def first_device(trace: Dict[str, Any]) -> Dict[str, Any]:
    return trace["devices"][sorted(trace["devices"], key=int)[0]]


def breakdown(trace: Dict[str, Any], window: Interval) -> Dict[str, Any]:
    """What the driver copies into the ledger: the device ops that took
    most (self) time and the idle gaps by host annotation, first device."""
    dev = first_device(trace)
    return {"device_ops": top(self_times(dev["ops"], window)),
            "idle_gaps": top(attribute_gaps(idle_gaps(dev["ops"], window),
                                            trace["host"]))}


def excerpt(trace: Dict[str, Any], ops: int = 1500) -> Dict[str, Any]:
    """A small piece of a trace to keep with the tests: each device's first
    `ops` op events inside the marked window, and the modules and host spans
    that overlap them."""
    w0 = trace_window(trace)[0]
    out: Dict[str, Any] = {"devices": {}, "host": []}
    end = w0
    for key, dev in trace["devices"].items():
        kept = sorted((e for e in dev["ops"] if e[1] >= w0),
                      key=lambda e: e[1])[:ops]
        if kept:
            end = max(end, max(e[1] + e[2] for e in kept))
        out["devices"][key] = {"ops": kept, "modules": []}
    for key, dev in trace["devices"].items():
        out["devices"][key]["modules"] = [
            e for e in dev["modules"] if e[1] < end and e[1] + e[2] > w0]
    out["host"] = [e for e in trace["host"] if e[1] < end and e[1] + e[2] > w0]
    return out
