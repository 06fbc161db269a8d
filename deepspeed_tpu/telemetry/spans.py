"""Request-level span tracing for the serving engines.

The hub's `serving` event is an engine-lifetime counter snapshot — it can
say "this generate() served 48 queries at 2500 tok/s" but not "where did
request #4812's 900 ms go". The `RequestTracer` answers that: the serving
loop opens named spans (admit, prefill, chunk, decode_wave, spec_round,
mixed_round, flush, degrade) around its host-side phases, and every
finished request's wall time is decomposed over them into a `request_span`
summary event whose `unattributed` residual is a tested invariant (<1% on
the CPU mesh).

Design constraints (the r6 hub discipline, CLAUDE.md):
- ZERO new device fetches: every timestamp is a host `perf_counter` taken
  at the engine's EXISTING materialization points (wave fetch, put round,
  flush). Tracing on vs off is bit-identical output and zero extra
  dispatches — the pin tests hold the RecompileDetector at zero misses
  with tracing enabled.
- Free when disabled: `span()` is a no-op context manager (one attribute
  read + one dict already allocated by the kwargs) unless the hub is
  enabled or `force` is set.
- Spans nest (put()'s prefill/chunk/decode inside _generate's
  mixed_round): only depth-0 intervals enter the wall-time decomposition
  so nothing double-counts; nested intervals still export to the Chrome
  trace. Every record carries its own `id`, the id of the span that was
  open around it (`parent`) and the `put` round it ran in (`round`); a
  layer's self time is its span less its children. `phase()` opens the
  sequential children of a round's span (feeds, sync, dispatch, fetch,
  commit): each closes the one before it.
- Closed spans outlive their requests in ONE bounded process-global
  `SpanStore` (`get_span_store()`), on the tracer's clock itself
  (`perf_counter`), so a reader can select a window by time after the run.
  The tracer's own intervals are still pruned with the last open request.
- An active `span()` also enters `jax.profiler.TraceAnnotation("ds:<name>")`,
  so a profile taken around the serving loop shows the program's spans
  over the device ops. Nothing is entered when the tracer is off.

Attribution rule: a depth-0 interval overlapping a request's [admit, done]
window is clipped to the window and credited to its span name when the
request is in the interval's `uids` (or the span is engine-wide,
uids=None), else to `<name>_other` — time the engine verifiably spent
serving OTHER requests while this one waited. `queue_s` (admit − submit)
names the pre-admission wait; the gap left over is `unattributed`.

Timeline: span t0/t1 are seconds-since-tracer-epoch on `perf_counter` (so
monotonicity is guaranteed within a trace); the epoch's unix time is
emitted once as a `trace_epoch` event so fault/retry/watchdog instants —
which only carry the hub's wall-clock `ts` — land on the same Chrome-trace
timeline in `export_chrome_trace`.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import itertools
import math
import time
import weakref
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence

# Kinds the tracer mirrors from the hub stream as in-memory instants (and
# the exporter renders as Chrome-trace instant events): the resilience
# vocabulary — every failure-matrix row's telemetry lands here.
INSTANT_KINDS = ("fault", "retry", "watchdog", "serve_mode_degraded",
                 "recompile", "memory_watermark")

_INSTANT_CAP = 4096      # bound the in-memory instant mirror
_INTERVAL_CAP = 65536    # hard bound on retained intervals (safety valve)
_STORE_CAP = 32768       # closed spans kept after their requests: ~4000 rounds
_SETUP_CAP = 4096        # set-up's spans, kept apart from the rounds'
ANNOTATION_PREFIX = "ds:"   # the program's names in a jax.profiler trace
_IDS = itertools.count(1)   # span ids: unique in the process, across tracers


# ------------------------------------------------------------- span store
class SpanStore:
    """The closed spans of the last rounds, process-global and bounded.

    One record per closed span, as the tracer keeps it (`name`, `id`,
    `parent`, `round`, `depth`, `uids`, `slots`, `fields`) plus `engine`,
    with `t0`/`t1` in seconds on the clock the tracer runs on
    (`perf_counter`), NOT from the tracer's epoch. The oldest fall out.
    Set-up's spans (`compile`, `init` and its parts, `import`) are few and
    are kept apart, so that a long run's rounds do not push them out."""

    def __init__(self, cap: int = _STORE_CAP):
        self._spans: collections.deque = collections.deque(maxlen=cap)
        self._setup: collections.deque = collections.deque(maxlen=_SETUP_CAP)

    def add(self, rec: Dict[str, Any], setup: bool = False) -> None:
        (self._setup if setup else self._spans).append(rec)

    def spans(self, t0: Optional[float] = None, t1: Optional[float] = None
              ) -> List[Dict[str, Any]]:
        """The stored spans that lie wholly inside [t0, t1], oldest first
        (by their end)."""
        return [r for r in heapq.merge(self._setup, self._spans,
                                       key=lambda r: r["t1"])
                if (t0 is None or r["t0"] >= t0)
                and (t1 is None or r["t1"] <= t1)]

    def clear(self) -> None:
        self._spans.clear()
        self._setup.clear()

    def __len__(self) -> int:
        return len(self._spans) + len(self._setup)


_STORE = SpanStore()


def get_span_store() -> SpanStore:
    return _STORE


# --------------------------------------------------------------- histogram
# Fixed log-spaced bucket bounds: 8 per decade from 100 µs to 1000 s.
# FIXED by contract (like the bench metric name): streaming percentiles
# from two runs merge bucket-wise only if the bounds never move.
HIST_BOUNDS_S = tuple(10.0 ** (i / 8.0) for i in range(-32, 25))


class Histogram:
    """Streaming log-bucket histogram (fixed bounds — see HIST_BOUNDS_S).

    `observe` is two int adds and a bisect: cheap enough to run
    unconditionally, like the hub's counters. Percentiles interpolate
    log-linearly inside the landing bucket — error is bounded by the
    bucket width (~33% relative at 8/decade), which is the right trade
    for streaming SLA percentiles (the bench row computes exact ones from
    raw stamps where they matter)."""

    def __init__(self, bounds: Sequence[float] = HIST_BOUNDS_S):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.n = 0
        self.total = 0.0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None

    def observe(self, value) -> None:
        try:
            v = float(value)
        except (TypeError, ValueError):
            return
        if not math.isfinite(v):
            return
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.n += 1
        self.total += v
        self.vmin = v if self.vmin is None else min(self.vmin, v)
        self.vmax = v if self.vmax is None else max(self.vmax, v)

    def percentile(self, q: float) -> Optional[float]:
        if not self.n:
            return None
        rank = max(1, math.ceil(q * self.n))
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= rank:
                lo = self.bounds[i - 1] if i > 0 else (
                    self.vmin if self.vmin is not None else 0.0)
                hi = self.bounds[i] if i < len(self.bounds) else (
                    self.vmax if self.vmax is not None else lo)
                lo = max(min(lo, hi), 1e-12)
                hi = max(hi, lo)
                # log-linear interpolation by in-bucket rank fraction
                frac = (rank - (acc - c)) / max(c, 1)
                return float(lo * (hi / lo) ** min(max(frac, 0.0), 1.0))
        return self.vmax

    def summary(self) -> Dict[str, Any]:
        """Stable field set for the `histogram` event / --percentiles."""
        r6 = lambda v: None if v is None else round(v, 6)
        return {"count": self.n,
                "mean": r6(self.total / self.n) if self.n else None,
                "p50": r6(self.percentile(0.50)),
                "p95": r6(self.percentile(0.95)),
                "p99": r6(self.percentile(0.99)),
                "min": r6(self.vmin), "max": r6(self.vmax),
                "buckets": {f"{self.bounds[i - 1] if i else 0:.6g}": c
                            for i, c in enumerate(self.counts) if c}}


# ----------------------------------------------------------------- tracer
class RequestTracer:
    """Per-request span records for one serving engine.

    Host-side only; single-threaded by construction (the serving loops
    are). `span()` nests via a depth counter; `begin_request` is
    IDEMPOTENT (keeps the earliest admit) so request traces survive a
    degrade-ladder engine rebuild and the generate() retry that follows;
    `end_request` computes the wall-time decomposition and emits the
    `request_span` summary.
    """

    def __init__(self, engine: str = "v2", clock=time.perf_counter,
                 force: bool = False):
        self.engine = engine
        self.force = force   # trace without an enabled hub (in-memory)
        self._clock = clock
        self.epoch_unix = time.time()
        self._t0 = clock()
        self._stack: List[Dict[str, Any]] = []   # the spans open now
        self.round: Optional[int] = None   # the `put` round, set by put
        self._intervals: List[Dict[str, Any]] = []
        self._open: Dict[Any, Dict[str, Any]] = {}
        self.last_requests: Dict[Any, Dict[str, Any]] = {}
        self.instants: List[Dict[str, Any]] = []
        self.spans_recorded = 0
        self.requests_finished = 0
        self._epoch_emitted = False
        self._listening = False

    # ------------------------------------------------------------ plumbing
    @property
    def active(self) -> bool:
        if self.force:
            return True
        from deepspeed_tpu.telemetry.hub import get_hub
        return get_hub().enabled

    def now(self) -> float:
        """Seconds since the tracer epoch (perf_counter-precise)."""
        return self._clock() - self._t0

    def _hub(self):
        from deepspeed_tpu.telemetry.hub import get_hub
        return get_hub()

    def _maybe_emit_epoch(self, hub) -> None:
        if not self._epoch_emitted and hub.enabled:
            self._epoch_emitted = True
            hub.emit("trace_epoch", engine=self.engine,
                     epoch_unix=round(self.epoch_unix, 6))

    def _register_listener(self) -> None:
        """Mirror resilience events (fault/retry/watchdog/degrade/
        recompile) off the hub stream as in-memory instants — the tracer
        holds only a weak self-reference so discarded engines don't pile
        up in the hub's listener list."""
        if self._listening:
            return
        self._listening = True
        from deepspeed_tpu.telemetry import hub as hub_mod
        wm = weakref.WeakMethod(self._on_hub_event)

        def cb(rec, wm=wm):
            m = wm()
            if m is None:
                hub_mod.remove_listener(cb)
            else:
                m(rec)
        hub_mod.add_listener(cb)

    def attach(self) -> None:
        """Start mirroring resilience events now (idempotent). The serving
        loops attach lazily at the first `begin_request`; a replay harness
        calls this up front so faults fired BEFORE the first admission
        (placement, compile) still land in `instants` for 1:1 matching."""
        if self.active:
            self._register_listener()

    def _on_hub_event(self, rec: Dict[str, Any]) -> None:
        if rec.get("kind") not in INSTANT_KINDS:
            return
        if len(self.instants) >= _INSTANT_CAP:
            return
        inst = {"kind": rec["kind"], "t_s": round(self.now(), 6)}
        for f in ("point", "action", "label", "what", "watchdog",
                  "from_mode", "to_mode", "program", "hit"):
            if rec.get(f) is not None:
                inst[f] = rec[f]
        self.instants.append(inst)

    # --------------------------------------------------------------- spans
    @contextmanager
    def span(self, name: str, uids: Optional[Sequence] = None,
             slots: Optional[Sequence[int]] = None, **fields):
        """Record one named interval. Yields the span's mutable `fields`
        dict so stats known only after the body (spec acceptance, stall
        deltas) can be attached before emission. `uids` may be a mutable
        list filled during the body (the admit span does this)."""
        if not self.active:
            yield fields
            return
        rec = self._begin(name, uids, slots, fields)
        try:
            yield fields
        finally:
            self._end(rec)

    def phase(self, name: str) -> Dict[str, Any]:
        """Open the next sequential child of the innermost open span, and
        close the child before it: `feeds`, `sync`, `dispatch`, `fetch`,
        `commit` inside a round's `prefill` / `chunk` / `decode`. The
        parent's exit closes the last one. Returns the child's mutable
        fields. Only for a caller that has read `active` as true."""
        now = self.now()   # one reading: the children tile their parent
        if self._stack[-1]["phase"]:
            self._end(self._stack[-1], now)
        parent = self._stack[-1]
        return self._begin(name, parent["uids"], parent["slots"], {},
                           phase=True, now=now)["fields"]

    def current(self):
        """(id, round) of the innermost open span; (None, None) if none."""
        if not self._stack:
            return None, None
        return self._stack[-1]["id"], self._stack[-1]["round"]

    def _begin(self, name, uids, slots, fields, phase=False, now=None):
        from jax.profiler import TraceAnnotation
        note = TraceAnnotation(ANNOTATION_PREFIX + name)
        note.__enter__()
        rec = {"name": name, "depth": len(self._stack), "uids": uids,
               "slots": slots, "fields": fields, "id": next(_IDS),
               "parent": self._stack[-1]["id"] if self._stack else None,
               "round": self.round, "phase": phase, "note": note,
               "t0": self.now() if now is None else now}
        self._stack.append(rec)
        return rec

    def _end(self, rec, now=None) -> None:
        now = self.now() if now is None else now
        while self._stack[-1] is not rec:   # an open phase, or an exception
            self._end(self._stack[-1], now)  # that skipped an inner exit
        rec["t1"] = now
        self._stack.pop()
        rec["note"].__exit__(None, None, None)
        self._record(rec)

    def _record(self, rec) -> None:
        if len(self._intervals) >= _INTERVAL_CAP:
            self._prune()
        name, t0, t1, depth = rec["name"], rec["t0"], rec["t1"], rec["depth"]
        uids = None if rec["uids"] is None else tuple(rec["uids"])
        slots = None if rec["slots"] is None else tuple(rec["slots"])
        fields = dict(rec["fields"])
        iv = {"name": name, "t0": t0, "t1": t1, "depth": depth, "uids": uids,
              "slots": slots, "fields": fields, "id": rec["id"],
              "parent": rec["parent"], "round": rec["round"]}
        self._intervals.append(iv)
        _STORE.add({**iv, "t0": self._t0 + t0, "t1": self._t0 + t1,
                    "engine": self.engine})
        self.spans_recorded += 1
        hub = self._hub()
        if hub.enabled:
            self._maybe_emit_epoch(hub)
            hub.emit("span", name=name, engine=self.engine,
                     t0_s=round(t0, 6), t1_s=round(t1, 6),
                     dur_ms=round((t1 - t0) * 1e3, 3), depth=depth,
                     id=rec["id"], parent=rec["parent"], round=rec["round"],
                     uids=None if uids is None else list(uids),
                     slots=None if slots is None else list(slots),
                     fields=fields or None)
            # the span's own JSONL write (json.dumps + file flush, ~100 µs
            # on the 1-core box) happened AFTER t1 — stretch the RETAINED
            # interval over it so tracing overhead attributes to the span
            # it traced instead of leaking into `unattributed`. The emitted
            # event keeps the pre-write t1 (its dur is the phase's own).
            if depth == 0:
                iv["t1"] = self.now()

    # ------------------------------------------------------ request records
    def begin_request(self, uid, prompt_tokens: int = 0,
                      slot: Optional[int] = None,
                      submit_s: Optional[float] = None, **fields) -> None:
        """Open a request record. IDEMPOTENT: re-begun uids (the degrade
        retry re-admitting its in-flight work) keep their original admit
        and submit stamps, so a request's trace spans the engine rebuild."""
        if not self.active:
            return
        self._register_listener()
        rec = self._open.get(uid)
        if rec is not None:
            rec["fields"].update(fields)
            if slot is not None:
                rec["slot"] = slot
            return
        now = self.now()
        self._open[uid] = {
            "admit": now,
            "submit": now if submit_s is None else float(submit_s),
            "prompt_tokens": int(prompt_tokens), "slot": slot,
            "first": None, "fields": dict(fields)}

    def note(self, uid, **fields) -> None:
        rec = self._open.get(uid)
        if rec is not None:
            rec["fields"].update(fields)

    def bump(self, uid, field: str, n: int = 1) -> None:
        rec = self._open.get(uid)
        if rec is not None:
            rec["fields"][field] = rec["fields"].get(field, 0) + n

    def first_token(self, uid) -> None:
        rec = self._open.get(uid)
        if rec is not None and rec["first"] is None:
            rec["first"] = self.now()

    def open_uids(self) -> List[Any]:
        return list(self._open)

    def end_request(self, uid, new_tokens: Optional[int] = None,
                    total_tokens: Optional[int] = None,
                    serve_mode: Optional[str] = None,
                    status: str = "finished") -> Optional[Dict[str, Any]]:
        """Close a request: decompose its wall time over the recorded
        depth-0 intervals, emit the `request_span` summary, feed the hub's
        ttft/tpot/e2e histograms. Idempotent (unknown/closed uids no-op)."""
        rec = self._open.pop(uid, None)
        if rec is None:
            return None
        done = self.now()
        if new_tokens is None:
            new_tokens = max(0, int(total_tokens or 0)
                             - rec["prompt_tokens"])
        first = rec["first"]
        if first is None and new_tokens > 0:
            # a request retiring in the wave that produced its first token:
            # the token materialized at this wave's fetch — done IS first
            first = done
        t_admit = rec["admit"]
        spans: Dict[str, float] = {}
        for iv in self._intervals:
            if iv["depth"] != 0:
                continue
            a, b = max(iv["t0"], t_admit), min(iv["t1"], done)
            if b <= a:
                continue
            name = iv["name"]
            if iv["uids"] is not None and uid not in iv["uids"]:
                name += "_other"
            spans[name] = spans.get(name, 0.0) + (b - a)
        attributed = sum(spans.values())
        unattributed = max(0.0, (done - t_admit) - attributed)
        e2e = done - rec["submit"]
        queue = max(0.0, t_admit - rec["submit"])
        ttft = None if first is None else max(0.0, first - rec["submit"])
        tpot = ((done - first) / (new_tokens - 1)
                if first is not None and new_tokens > 1 else None)
        summary = {
            "uid": uid, "engine": self.engine, "slot": rec["slot"],
            "serve_mode": serve_mode, "status": status,
            "prompt_tokens": rec["prompt_tokens"],
            "new_tokens": int(new_tokens),
            "admit_s": round(t_admit, 6), "done_s": round(done, 6),
            "queue_s": round(queue, 6), "e2e_s": round(e2e, 6),
            "ttft_s": None if ttft is None else round(ttft, 6),
            "tpot_s": None if tpot is None else round(tpot, 6),
            "spans": {k: round(v, 6) for k, v in sorted(spans.items())},
            "unattributed_s": round(unattributed, 6),
            "unattributed_frac": round(
                unattributed / e2e if e2e > 0 else 0.0, 6),
            "fields": dict(rec["fields"]) or None}
        self.last_requests[uid] = summary
        self.requests_finished += 1
        hub = self._hub()
        # histograms stream even without a JSONL sink (counter semantics)
        hub.observe_hist("ttft_s", ttft)
        hub.observe_hist("tpot_s", tpot)
        hub.observe_hist("e2e_s", e2e)
        if hub.enabled:
            self._maybe_emit_epoch(hub)
            hub.emit("request_span", **summary)
        self._prune()
        return summary

    def _prune(self) -> None:
        """Drop intervals no open request can still attribute — bounds
        memory across a long-lived engine without touching live windows."""
        if not self._open:
            self._intervals.clear()
            return
        horizon = min(r["admit"] for r in self._open.values())
        self._intervals = [iv for iv in self._intervals
                           if iv["t1"] >= horizon]


# -------------------------------------------------------- chrome trace I/O
def _trace_epoch(events: Sequence[Dict[str, Any]]) -> float:
    """Unix time of the tracer epoch: the emitted `trace_epoch` event, or
    (older files) the median of span events' (wall ts − t1_s)."""
    for e in events:
        if e.get("kind") == "trace_epoch" and e.get("epoch_unix"):
            return float(e["epoch_unix"])
    offs = sorted(float(e["ts"]) - float(e["t1_s"]) for e in events
                  if e.get("kind") == "span"
                  and e.get("ts") is not None and e.get("t1_s") is not None)
    return offs[len(offs) // 2] if offs else 0.0


def export_chrome_trace(events: Sequence[Dict[str, Any]],
                        path: Optional[str] = None) -> Dict[str, Any]:
    """Telemetry JSONL events → Chrome trace_event JSON (chrome://tracing
    / Perfetto). One track (tid) per request SLOT — `request_span`
    summaries draw the request's [admit, done] envelope on its slot,
    `span` events draw the engine phases (slot-attributed spans on their
    slots, engine-wide ones on tid 0), and fault/retry/watchdog/degrade/
    recompile events land as instants. Timestamps are µs on the tracer's
    perf_counter timeline — monotonic by construction."""
    epoch = _trace_epoch(events)
    us = lambda s: round(float(s) * 1e6, 3)
    out: List[Dict[str, Any]] = [
        {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
         "args": {"name": "engine"}}]
    named_slots = set()

    def slot_meta(slot):
        if slot in named_slots:
            return
        named_slots.add(slot)
        out.append({"ph": "M", "pid": 1, "tid": 1 + int(slot),
                    "name": "thread_name",
                    "args": {"name": f"slot {int(slot)}"}})

    for e in events:
        kind = e.get("kind")
        if kind == "span":
            t0, t1 = float(e.get("t0_s", 0.0)), float(e.get("t1_s", 0.0))
            slots = e.get("slots") or []
            args = dict(e.get("fields") or {})
            if e.get("uids") is not None:
                args["uids"] = e["uids"]
            base = {"ph": "X", "pid": 1, "name": e.get("name", "span"),
                    "ts": us(t0), "dur": us(max(t1 - t0, 0.0)),
                    "args": args}
            if slots:
                for s in slots:
                    slot_meta(s)
                    out.append(dict(base, tid=1 + int(s)))
            else:
                out.append(dict(base, tid=0))
        elif kind == "request_span":
            if e.get("slot") is None:
                continue
            slot_meta(e["slot"])
            out.append({
                "ph": "X", "pid": 1, "tid": 1 + int(e["slot"]),
                "name": f"request {e.get('uid')}",
                "ts": us(e.get("admit_s", 0.0)),
                "dur": us(max(float(e.get("done_s", 0.0))
                              - float(e.get("admit_s", 0.0)), 0.0)),
                "args": {k: e.get(k) for k in
                         ("uid", "serve_mode", "prompt_tokens",
                          "new_tokens", "ttft_s", "tpot_s",
                          "unattributed_frac", "spans")
                         if e.get(k) is not None}})
        elif kind == "memory_snapshot":
            # per-tier counter tracks ("C" events) — Perfetto draws each
            # tier's registered bytes as a stacked area over the timeline
            ts = e.get("ts")
            tiers = (e.get("residency") or {}).get("tiers") or {}
            if ts is None or not tiers:
                continue
            rel = max(0.0, float(ts) - epoch) if epoch else 0.0
            for tier, b in sorted(tiers.items()):
                out.append({"ph": "C", "pid": 1, "name": f"memory:{tier}",
                            "ts": us(rel), "args": {"bytes": int(b)}})
        elif kind in INSTANT_KINDS:
            ts = e.get("ts")
            if ts is None:
                continue
            rel = max(0.0, float(ts) - epoch) if epoch else 0.0
            label = e.get("point") or e.get("watchdog") or \
                e.get("to_mode") or e.get("program") or e.get("tier") or kind
            out.append({"ph": "i", "pid": 1, "tid": 0, "s": "g",
                        "name": f"{kind}:{label}", "ts": us(rel),
                        "args": {k: v for k, v in e.items()
                                 if k not in ("ts", "step") and
                                 v is not None}})
    trace = {"traceEvents": out, "displayTimeUnit": "ms"}
    if path:
        import json
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f)
    return trace
