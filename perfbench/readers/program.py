"""Readers of what the PROGRAM records about itself (`program_span`,
`program_counter`): the spans of a v2 `put` round, the token slots a round
computed against the tokens it was fed, and the compiles of set-up.

The runners hand no engine to a reader, so these reach the program through
the process-globals of `deepspeed_tpu.telemetry` (its span store and its
compile records), as `get_hub()` is one. A program that has neither (any
commit before they were added) gives None, and the metric is left out.

Two clocks meet here. The program's spans are on `perf_counter`; the judged
window opens at `ctx.t_start + setup_s` on that clock, so phase metrics
select their rounds by time. The device trace is on the profiler's clock
(nanoseconds from the session's start), and its reduction keeps only the
harness's `pb:` annotations; each `pb:round` encloses exactly one `put`, so
the rounds both sides saw, paired from the last backwards, give the offset
between the clocks (`align`). Every aligned span is then clipped to its own
`pb:round`: a bad alignment leaves idle time uncovered and shows as
`idle_unattributed_share.serve`, not as a wrong attribution.
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench import trace as tm

ROUND = tm.HOST_PREFIX + "round"
# what the host does before the device can start a round, and after it
PREPARE = ("schedule", "feeds", "sync", "dispatch")
COLLECT = ("fetch", "commit", "flush")
BETWEEN = "between_rounds"   # made here: end of one put to the start of the next


# ------------------------------------------------------------ the program


def _telemetry(attr: str):
    try:
        from deepspeed_tpu import telemetry
    except ImportError:
        return None
    return getattr(telemetry, attr, None)


def stored_spans() -> Optional[List[Dict[str, Any]]]:
    store = _telemetry("get_span_store")
    return None if store is None else store().spans()


def rounds_of(spans: Sequence[Dict[str, Any]]) -> List[List[Dict[str, Any]]]:
    """The spans of each `put` round, rounds in order."""
    by: Dict[int, List[Dict[str, Any]]] = {}
    for s in spans:
        if s.get("round") is not None:
            by.setdefault(s["round"], []).append(s)
    return [by[k] for k in sorted(by)]


def window_rounds(ctx) -> Optional[List[List[Dict[str, Any]]]]:
    """The rounds that lie wholly inside the judged window."""
    spans = stored_spans()
    if not spans or "setup_s" not in ctx.counters:
        return None
    w0 = ctx.t_start + ctx.counters["setup_s"]
    w1 = w0 + ctx.seconds
    inside = [r for r in rounds_of(spans)
              if min(s["t0"] for s in r) >= w0 and max(s["t1"] for s in r) <= w1]
    return inside or None


# ------------------------------------------------------------ phase metrics


def phase_ms(ctx, names):
    """Median, over the judged window's rounds, of the milliseconds a round
    spent in the spans called `names`."""
    rounds = window_rounds(ctx)
    if rounds is None:
        return None
    return statistics.median(
        1e3 * sum(s["t1"] - s["t0"] for s in r if s["name"] in names)
        for r in rounds)


def slot_fill(ctx):
    """100 x tokens fed / token slots computed, over the window's rounds."""
    rounds = window_rounds(ctx)
    if rounds is None:
        return None
    fed = slots = 0
    for r in rounds:
        for s in r:
            fed += s["fields"].get("tokens_fed", 0)
            slots += s["fields"].get("token_slots", 0)
    return 100.0 * fed / slots if slots else None


# ------------------------------------------------------------------ set-up


def _setup_compiles(ctx) -> Optional[List[Dict[str, Any]]]:
    records = _telemetry("compile_records")
    if records is None or "setup_s" not in ctx.counters:
        return None
    _dump(ctx)
    t_open = ctx.t_start + ctx.counters["setup_s"]
    return [r for r in records() if r["t"] <= t_open]


def setup_compile_seconds(ctx):
    """Backend-compile seconds (loads from the persistent cache included)
    that ended before the window opened."""
    recs = _setup_compiles(ctx)
    return None if recs is None else sum(r["seconds"] for r in recs)


def setup_compile_count(ctx):
    recs = _setup_compiles(ctx)
    return None if recs is None else len(recs)


# --------------------------------------------------- idle time by host phase


def align(host: Sequence[Sequence], rounds: Sequence[Sequence[Dict[str, Any]]]
          ) -> Optional[Tuple[float, List[Tuple[Sequence, List[Dict[str, Any]]]]]]:
    """(offset_ns, pairs): the trace's `pb:round` annotations paired with the
    program's rounds from the last backwards, and the median over the pairs
    of (program start - annotation start): program ns minus `offset_ns` is
    on the trace's clock."""
    marks = sorted((e for e in host if e[0] == ROUND), key=lambda e: e[1])
    pairs = list(zip(reversed(marks), reversed(rounds)))[::-1]
    if not pairs:
        return None
    offset = statistics.median(
        min(s["t0"] for s in r) * 1e9 - m[1] for m, r in pairs)
    return offset, pairs


def host_intervals(ctx) -> Optional[Dict[str, List[tm.Interval]]]:
    """The program's spans of the traced segment on the trace's clock, by
    name, each clipped to its own `pb:round`; plus `between_rounds`."""
    spans = stored_spans()
    if not spans:
        return None
    got = align(ctx.trace["host"], rounds_of(spans))
    if got is None:
        return None
    offset, pairs = got
    out: Dict[str, List[tm.Interval]] = {}
    edges = []   # each round's first start and last end, on the trace clock
    for mark, spans_k in pairs:
        inside = (mark[1], mark[1] + mark[2])
        clipped = [(s["name"], tm.clip([(s["t0"] * 1e9 - offset,
                                         s["t1"] * 1e9 - offset)], inside))
                   for s in spans_k]
        for name, iv in clipped:
            out.setdefault(name, []).extend(iv)
        flat = [i for _, iv in clipped for i in iv]
        edges.append((min(a for a, _ in flat), max(b for _, b in flat))
                     if flat else inside)
    out[BETWEEN] = [(edges[k][1], edges[k + 1][0])
                    for k in range(len(edges) - 1)
                    if edges[k + 1][0] > edges[k][1]]
    # spans outside any put (`flush`), on the same clock, inside the segment
    lo, hi = edges[0][0], edges[-1][1]
    for s in spans:
        if s.get("round") is None and s["name"] in COLLECT:
            out.setdefault(s["name"], []).extend(tm.clip(
                [(s["t0"] * 1e9 - offset, s["t1"] * 1e9 - offset)], (lo, hi)))
    return out


def idle_split(ctx) -> Optional[Dict[str, float]]:
    """Device idle seconds of the traced window (first device) under the
    host's `prepare` spans, under its `collect` spans, and under neither."""
    if ctx.trace is None or not ctx.trace["devices"] or not ctx.trace_window:
        return None
    by_name = host_intervals(ctx)
    if by_name is None:
        return None
    w = ctx.trace_window
    gaps = tm.idle_gaps(tm.first_device(ctx.trace)["ops"], w)
    prepare = tm.union(i for n in PREPARE for i in by_name.get(n, []))
    collect = tm.subtract(tm.union(i for n in COLLECT + (BETWEEN,)
                                   for i in by_name.get(n, [])), prepare)

    def under(cover):   # gaps and cover are both sorted and disjoint
        return tm.length(gaps) - tm.length(tm.subtract(gaps, cover))

    idle = {"prepare": under(prepare) / 1e9, "collect": under(collect) / 1e9}
    idle["unattributed"] = tm.length(gaps) / 1e9 - sum(idle.values())
    idle["window"] = (w[1] - w[0]) / 1e9
    return idle


def idle_share(ctx, under):
    """Device idle time under the host's `under` spans ("prepare", "collect"
    or "unattributed"), as a share of the traced window."""
    idle = idle_split(ctx)
    return None if idle is None else 100.0 * idle[under] / idle["window"]


# ------------------------------------------------------------ to read by hand


def _dump(ctx) -> None:
    """With PERFBENCH_DUMP set (as for the harness's own dumps): the compile
    spans (set-up by program) and every compile record. Never read back."""
    dump = os.environ.get("PERFBENCH_DUMP")
    if not dump or getattr(ctx, "_program_dumped", False):
        return
    ctx._program_dumped = True
    os.makedirs(dump, exist_ok=True)
    spans = stored_spans() or []
    doc = {"compile_spans": [s for s in spans if s["name"] == "compile"],
           "compile_records": _telemetry("compile_records")(),
           "spans_stored": len(spans)}
    name = os.path.join(dump, ctx.workload["name"] + ".program.json")
    with open(name, "w") as f:
        json.dump(doc, f, indent=1, default=str)
