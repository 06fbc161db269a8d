"""Telemetry CLI.

    python -m deepspeed_tpu.telemetry --summarize run.jsonl
    python -m deepspeed_tpu.telemetry --summarize run.jsonl --percentiles
    python -m deepspeed_tpu.telemetry --summarize run.jsonl \
        --export-trace trace.json
    python -m deepspeed_tpu.telemetry --by-scope /tmp/ds_tpu_trace

``--summarize`` prints a step-time / memory table from a telemetry
JSONL file (schema: docs/telemetry.md). ``--percentiles`` adds the
streaming SLA histograms (`histogram` events: TTFT/TPOT/e2e p50/p95/p99)
and a per-serve-mode request table aggregated from `request_span` events.
``--memory`` adds the residency section (peak registered bytes per tier,
the last snapshot's per-component breakdown, reconcile drift rows).
``--export-trace OUT`` converts the file's span/request/instant events to
Chrome trace_event JSON (chrome://tracing or ui.perfetto.dev; one track
per request slot; `memory_snapshot` events become per-tier counter
tracks). Pure-stdlib parsing — works on any box that can read the file.
``--by-scope LOGDIR`` reads a directory ``engine.trace`` /
``trace_capture`` wrote (the newest ``.xplane.pb`` there and the
``program_map.json`` beside it) and prints the device's seconds by scope,
by what an instruction is or holds, and by phase; it needs JAX's profile
reader and touches no backend.
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List, Optional


def _pct(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def _fmt(v, unit: str = "", nd: int = 4) -> str:
    if v is None:
        return "-"
    return f"{v:.{nd}g}{unit}"


def load_events(path: str) -> List[Dict[str, Any]]:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line of a live run
    return events


def summarize(path: str) -> str:
    events = load_events(path)
    by_kind: Dict[str, List[Dict[str, Any]]] = {}
    for e in events:
        by_kind.setdefault(e.get("kind", "?"), []).append(e)

    def field_vals(name, kinds=None):
        out = []
        for e in events:
            if kinds and e.get("kind") not in kinds:
                continue
            v = e.get(name)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out.append(float(v))
        return out

    lines = [f"telemetry summary — {path}",
             "events: " + ", ".join(f"{k}×{len(v)}"
                                    for k, v in sorted(by_kind.items()))]

    steps = by_kind.get("train_step", [])
    times = sorted(field_vals("step_time_s"))
    losses = field_vals("loss", kinds=("train_step",))
    peaks = field_vals("peak_hbm_gb") + [
        b / (1 << 30) for b in field_vals("peak_bytes_in_use")]
    norms = field_vals("grad_norm", kinds=("train_step",))
    skipped = [e.get("skipped_steps") for e in steps
               if isinstance(e.get("skipped_steps"), int)]

    lines.append(f"train      steps {len(steps)}"
                 + (f"   loss {losses[0]:.4g} → {losses[-1]:.4g}"
                    if losses else ""))
    lines.append(f"step time  mean {_fmt(sum(times) / len(times) if times else None, ' s')}"
                 f"   p50 {_fmt(_pct(times, 0.5), ' s')}"
                 f"   p95 {_fmt(_pct(times, 0.95), ' s')}")
    lines.append(f"peak HBM   {_fmt(max(peaks) if peaks else None, ' GB', 5)}")
    if norms:
        lines.append(f"grad norm  last {_fmt(norms[-1])}"
                     f"   skipped steps {skipped[-1] if skipped else 0}")

    srv = by_kind.get("serving", [])
    if srv:
        s = srv[-1]
        lines.append(f"serving    queries {s.get('queries', '-')}"
                     f"   ttft p50 {_fmt(s.get('ttft_p50_s'), ' s')}"
                     f"   decode {_fmt(s.get('decode_tok_s'), ' tok/s', 6)}"
                     f"   kv util peak {_fmt(s.get('kv_util_peak'))}")
    rec = by_kind.get("recompile", [])
    if rec:
        pinned = sum(1 for e in rec if e.get("pinned"))
        lines.append(f"recompiles {len(rec)} (pinned {pinned})")
    nvme = by_kind.get("nvme", [])
    if nvme:
        n = nvme[-1]
        lines.append(f"nvme       backend {n.get('backend', '-')}"
                     f"   reads {n.get('reads', '-')}"
                     f" ({_fmt((n.get('read_bytes') or 0) / 1e9, ' GB', 4)})"
                     f"   writes {n.get('writes', '-')}")
    return "\n".join(lines)


def percentiles(path: str) -> str:
    """The SLA section: last `histogram` snapshot per metric name, and a
    per-serve-mode request table from `request_span` events (count, TTFT
    p50/p99, mean TPOT, generated tokens). Exact percentiles from the raw
    request records where the file has them; the histogram rows are the
    streaming (bucketed) view the hub maintains in-process."""
    events = load_events(path)
    lines = [f"telemetry percentiles — {path}"]

    hists: Dict[str, Dict[str, Any]] = {}
    for e in events:
        if e.get("kind") == "histogram" and e.get("name"):
            hists[e["name"]] = e  # last snapshot wins
    if hists:
        lines.append("histograms (streaming, fixed log buckets):")
        lines.append(f"  {'name':<10} {'count':>6} {'mean':>9} {'p50':>9}"
                     f" {'p95':>9} {'p99':>9} {'max':>9}")
        for name in sorted(hists):
            h = hists[name]
            lines.append(
                f"  {name:<10} {h.get('count', 0):>6}"
                f" {_fmt(h.get('mean'), '', 3):>9}"
                f" {_fmt(h.get('p50'), '', 3):>9}"
                f" {_fmt(h.get('p95'), '', 3):>9}"
                f" {_fmt(h.get('p99'), '', 3):>9}"
                f" {_fmt(h.get('max'), '', 3):>9}")
    else:
        lines.append("no histogram events in file")

    by_mode: Dict[str, List[Dict[str, Any]]] = {}
    for e in events:
        if e.get("kind") == "request_span":
            by_mode.setdefault(str(e.get("serve_mode")), []).append(e)
    if by_mode:
        lines.append("requests by serve mode (exact, from request_span):")
        lines.append(f"  {'serve_mode':<12} {'count':>6} {'ttft_p50':>9}"
                     f" {'ttft_p99':>9} {'tpot_mean':>10} {'tokens':>8}"
                     f" {'unattr_max':>10}")
        for mode in sorted(by_mode):
            rs = by_mode[mode]
            ttfts = sorted(r["ttft_s"] for r in rs
                           if isinstance(r.get("ttft_s"), (int, float)))
            tpots = [r["tpot_s"] for r in rs
                     if isinstance(r.get("tpot_s"), (int, float))]
            toks = sum(int(r.get("new_tokens") or 0) for r in rs)
            unat = [r.get("unattributed_frac") for r in rs
                    if isinstance(r.get("unattributed_frac"),
                                  (int, float))]
            lines.append(
                f"  {mode:<12} {len(rs):>6}"
                f" {_fmt(_pct(ttfts, 0.5), '', 3):>9}"
                f" {_fmt(_pct(ttfts, 0.99), '', 3):>9}"
                f" {_fmt(sum(tpots) / len(tpots) if tpots else None, '', 3):>10}"
                f" {toks:>8}"
                f" {_fmt(max(unat) if unat else None, '', 3):>10}")
    else:
        lines.append("no request_span events in file")
    return "\n".join(lines)


def memory_report(path: str) -> str:
    """The residency section: peak registered bytes per tier (from
    `memory_watermark` events plus the last snapshot's running
    watermarks), the last `memory_snapshot`'s per-tier × per-component
    breakdown, and every `residency_reconcile` drift row."""
    events = load_events(path)
    lines = [f"memory residency — {path}"]

    peaks: Dict[str, float] = {}
    last_snap: Optional[Dict[str, Any]] = None
    for e in events:
        kind = e.get("kind")
        if kind == "memory_watermark":
            t = str(e.get("tier"))
            b = e.get("peak_bytes")
            if isinstance(b, (int, float)):
                peaks[t] = max(peaks.get(t, 0), float(b))
        elif kind == "memory_snapshot":
            last_snap = e
            for t, b in ((e.get("residency") or {}).get("watermarks")
                         or {}).items():
                if isinstance(b, (int, float)):
                    peaks[str(t)] = max(peaks.get(str(t), 0), float(b))
    if peaks:
        lines.append("peak registered bytes per tier:")
        for t in sorted(peaks):
            lines.append(f"  {t:<12} {peaks[t] / (1 << 30):>9.4f} GiB")
    else:
        lines.append("no memory_watermark/memory_snapshot events in file")

    if last_snap is not None:
        res = last_snap.get("residency") or {}
        comps = res.get("components") or {}
        lines.append(f"last snapshot ({last_snap.get('reason', '-')}):")
        for tier in sorted(comps):
            for comp, b in sorted(comps[tier].items()):
                lines.append(f"  {tier:<12} {comp:<10}"
                             f" {float(b) / (1 << 20):>10.2f} MiB")
        logical = res.get("logical") or {}
        for name, b in sorted(logical.items()):
            lines.append(f"  (logical)    {name}"
                         f" {float(b) / (1 << 20):>10.2f} MiB")

    recs = [e for e in events if e.get("kind") == "residency_reconcile"]
    if recs:
        lines.append("reconciliations (registered vs formula):")
        lines.append(f"  {'check':<28} {'tier':<8} {'registered':>12}"
                     f" {'predicted':>12} {'drift':>8} ok")
        for e in recs:
            lines.append(
                f"  {str(e.get('check')):<28} {str(e.get('tier')):<8}"
                f" {e.get('registered_bytes', 0):>12}"
                f" {e.get('predicted_bytes', 0):>12}"
                f" {_fmt(e.get('drift'), '', 3):>8}"
                f" {'yes' if e.get('ok') else 'NO'}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.telemetry",
        description="Summarize a telemetry JSONL file, or a trace by scope")
    ap.add_argument("--summarize", metavar="JSONL",
                    help="path to a telemetry JSONL file")
    ap.add_argument("--by-scope", metavar="LOGDIR",
                    help="a directory trace_capture wrote: print the device "
                         "ops' seconds by scope, from its newest trace and "
                         "the program_map.json beside it")
    ap.add_argument("--percentiles", action="store_true",
                    help="with --summarize: print the SLA histogram section "
                         "and the per-serve-mode request table")
    ap.add_argument("--memory", action="store_true",
                    help="with --summarize: print the residency section "
                         "(peak per tier, per-component breakdown, "
                         "reconcile drift)")
    ap.add_argument("--export-trace", metavar="OUT",
                    help="with --summarize: write the file's span/request/"
                         "instant events as Chrome trace_event JSON to OUT")
    args = ap.parse_args(argv)
    if args.by_scope:
        from deepspeed_tpu.telemetry.program_map import report
        print(report(args.by_scope))
        if not args.summarize:
            return 0
    elif not args.summarize:
        ap.error("one of --summarize and --by-scope is required")
    print(summarize(args.summarize))
    if args.percentiles:
        print(percentiles(args.summarize))
    if args.memory:
        print(memory_report(args.summarize))
    if args.export_trace:
        from deepspeed_tpu.telemetry.spans import export_chrome_trace
        trace = export_chrome_trace(load_events(args.summarize),
                                    path=args.export_trace)
        print(f"trace: {len(trace['traceEvents'])} events → "
              f"{args.export_trace}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
