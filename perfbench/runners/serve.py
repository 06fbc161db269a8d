"""Serving cells: open-loop traffic against the v2 engine through `put`.

The benchmark's OWN serving loop (the one copy since PR 30 deleted
`benchmarks/traffic_replay.py`, whose `replay_put` it was taken from): the
harness is the scheduler's caller, one `put` round at a time,
`argmax_only=True`, one process, one thread. What differs from the
original: arrivals are timed from their DUE time, a request is admitted only
when a slot AND its KV blocks are free (first come, first served), the
generator's lateness and the backlog are recorded, requests not done by the
drain bound are `failed`, and a ramp of the same traffic runs before the
window so that no window measures an empty engine filling up.
"""

from __future__ import annotations

import collections
import contextlib
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from perfbench import traffic as tg
from perfbench.runners_common import (MARGIN_SAFE, REDRAWS, TIE_TOL, compared,
                                      judged, reference_pass, tie_gap)


class Served:
    """What one pass of the loop recorded; times are seconds from `t0`."""

    def __init__(self):
        self.req: Dict[int, Dict[str, Any]] = {}
        self.rounds: List[List[float]] = []     # [t_end, seconds, live]
        self.backlog_mid: Optional[int] = None
        self.backlog_end: Optional[int] = None


def serve(ctx, engine, reqs, t0: float, seconds: float, drain_s: float,
          blocks_total: int, block_size: int, trace_t0: float = 0.0,
          mark: Optional[Callable] = None) -> Served:
    """Offer `reqs` (sorted by `due`, seconds from `t0`; a ramp's are
    negative) and serve them until all are done or `seconds + drain_s` has
    passed. A request with `after` falls due once that uid has its first
    token (the warm-up's way to overlap two). `mark()` is a context entered
    when the window opens and left when it closes."""
    clock, tr = ctx.clock, engine.tracer
    pending = collections.deque(reqs)
    arrived: collections.deque = collections.deque()   # due, no slot yet
    live: Dict[int, Dict[str, Any]] = {}
    out = Served()
    blocks_free = blocks_total
    deadline = seconds + drain_s
    marked = contextlib.ExitStack()
    opened = closed = mark is None

    def blocks_for(r):   # the request's whole life, reserved at admission
        return math.ceil((len(r["prompt"]) + r["out"]) / block_size)

    def due(r, now):
        if r.get("after") is not None:
            return r["after"] in out.req or \
                live.get(r["after"], {}).get("first") is not None
        return r["due"] <= now

    while pending or arrived or live:
        now = clock() - t0
        if now > deadline:
            break
        if not opened and now >= 0:
            marked.enter_context(mark())
            opened = True
        if opened and not closed and now >= seconds:
            marked.close()
            closed = True
        while pending and due(pending[0], now):
            r = pending.popleft()
            r["picked"] = now
            arrived.append(r)
        if out.backlog_mid is None and now >= seconds / 2:
            out.backlog_mid = len(arrived)
        if out.backlog_end is None and now >= seconds:
            out.backlog_end = len(arrived)
        feeds_u, feeds_t = [], []
        while arrived and len(live) < engine.max_batch and \
                blocks_for(arrived[0]) <= blocks_free:
            r = arrived.popleft()
            r.update(admitted=now, produced=0, feed=None, first=None,
                     last=None, tokens=[], blocks=blocks_for(r))
            blocks_free -= r["blocks"]
            tr.begin_request(r["uid"], prompt_tokens=len(r["prompt"]),
                             submit_s=trace_t0 + r.get("due", now))
            feeds_u.append(r["uid"])
            feeds_t.append(r["prompt"])
            live[r["uid"]] = r
        for uid, r in live.items():
            if r["feed"] is not None:
                feeds_u.append(uid)
                feeds_t.append(np.asarray([r["feed"]], np.int32))
                r["feed"] = None
        if not live:
            # idle: nap until the next arrival, briefly, so that a pickup is
            # late by a fraction of a round and no more
            if pending and pending[0].get("after") is None:
                time.sleep(min(0.002, max(0.0, pending[0]["due"] - now)))
            continue
        t_put = clock()
        with ctx.annotate("round"):
            got = engine.put(feeds_u, feeds_t, argmax_only=True)
        t_end = clock()
        out.rounds.append([t_end - t0, t_end - t_put, len(live)])
        done = []
        for uid, tok in got.items():
            r = live[uid]
            tok = int(np.asarray(tok).reshape(-1)[-1])
            if r["produced"] == 0:
                r["first"] = t_end - t0
                tr.first_token(uid)
            r["produced"] += 1
            r["last"] = t_end - t0
            if len(r["tokens"]) < 4:
                r["tokens"].append(tok)
            if r["produced"] >= r["out"]:
                done.append(uid)
            else:
                r["feed"] = tok
        if done:
            with ctx.annotate("flush"):
                engine._flush_batch(done)
            for uid in done:
                r = live.pop(uid)
                blocks_free += r["blocks"]
                out.req[uid] = r
    marked.close()
    if out.backlog_mid is None:
        out.backlog_mid = len(arrived)
    if out.backlog_end is None:
        out.backlog_end = len(arrived)
    if live:   # cut off by the bound: released, so the engine is clean again
        engine._flush_batch(list(live))
    for r in list(live.values()) + list(arrived) + list(pending):
        r["failed"] = True
        out.req[r["uid"]] = r
    return out


# ------------------------------------------------------------------ phases


def check_requests(ctx, vocab: int):
    """The warm-up's requests, which are also the correctness sample, and
    `draw(n)`: a further prompt of `n` tokens from their stream. Each of
    `warm_prefill_lengths` goes alone (one single-shot prefill bucket each;
    none where every prompt is longer than a chunk). Of `check_prompt_lengths`
    the first goes alone (chunked prefill with no decode rows) and the rest
    join together once it decodes (chunks fused with decode rows). Between
    them they compile every program the window can dispatch."""
    rng = np.random.default_rng([ctx.seed, 99])

    def draw(n):
        return rng.integers(1, vocab, n).astype(np.int32)

    eng = ctx.traffic["engine"]
    lone, joined = eng["warm_prefill_lengths"], eng["check_prompt_lengths"]
    reqs = [{"uid": -1 - i, "due": 0.0, "out": 3 if i < len(lone) else 6,
             "prompt": draw(n)}
            for i, n in enumerate(list(lone) + list(joined))]
    for r in reqs[len(lone) + 1:]:
        r["after"] = reqs[len(lone)]["uid"]
    reqs[len(lone)]["out"] = 16
    return reqs, draw


def anchor_checks(ctx, params, checks, draw) -> Dict[str, Any]:
    """The reference's logits at the last position of each check prompt,
    from the RAW tree. Where the family routes, a prompt whose routing margin
    there is under `MARGIN_SAFE` is replaced, in `checks`, by a further draw
    of the SAME length (so no length, order or program changes), at most
    `REDRAWS` times a slot; past that the run ends without a result, never
    with a weaker check."""
    ref = reference_pass(ctx.reference, ctx.sizes)
    width = max(len(r["prompt"]) for r in checks)
    last = np.asarray([len(r["prompt"]) - 1 for r in checks], np.int32)

    def look():
        ids = np.zeros((len(checks), width), np.int32)
        for i, r in enumerate(checks):
            ids[i, :len(r["prompt"])] = r["prompt"]
        return ref(params, ids, last)

    anchor, margins = look()
    redrawn: List[float] = []          # the margins that were passed over
    for _ in range(REDRAWS):
        unsafe = [i for i, m in enumerate(margins or []) if not judged(m)]
        if not unsafe:
            break
        for i in unsafe:
            redrawn.append(margins[i])
            checks[i]["prompt"] = draw(len(checks[i]["prompt"]))
        again, their = look()
        for i in unsafe:
            anchor[i], margins[i] = again[i], their[i]
    for i, m in enumerate(margins or []):
        if not judged(m):
            raise SystemExit(
                f"perfbench: check slot {i} ({len(checks[i]['prompt'])} "
                f"tokens): no prompt of {REDRAWS + 1} draws has a routing "
                f"margin of {MARGIN_SAFE} in the float32 reference (the last "
                f"read {m:.5f}); nothing was served and nothing is judged")
    return {"anchor": anchor, "margins": margins, "margins_redrawn": redrawn}


def build(ctx, devices) -> Dict[str, Any]:
    """Weights from the seed on the device; the reference's logits for the
    check prompts from the RAW tree (before any engine placed, cast or
    re-laid it: PERF.md PR 21 finding 14); then the engine and its KV pool."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups

    eng = ctx.traffic["engine"]
    cfg = ctx.adapter.model_config(ctx.sizes, remat=False, dtype=jnp.bfloat16)
    groups.reset_topology()
    model, params = ctx.adapter.materialize(cfg, ctx.seed, jnp.bfloat16)

    checks, draw = check_requests(ctx, cfg.vocab_size)
    ref = anchor_checks(ctx, params, checks, draw)

    engine = InferenceEngineV2(
        model, params=params, max_batch=eng["max_batch"],
        max_seq_len=eng["max_seq_len"], kv_layout="paged",
        cache_block_size=eng["cache_block_size"],
        split_fuse_chunk=eng["split_fuse_chunk"],
        num_cache_blocks=eng["num_cache_blocks"],
        prefix_sharing=bool(eng.get("prefix_sharing", False)))
    del params
    engine.tracer.force = ctx.traced   # request spans in memory, traced run only
    return {"engine": engine, "vocab": cfg.vocab_size, "checks": checks,
            **ref, "blocks": eng["num_cache_blocks"],
            "block": eng["cache_block_size"]}


def warm(ctx, st) -> Dict[str, Any]:
    """Serve the check requests and judge each first token against the
    reference: every one at `TIE_TOL`, and every one at a routing margin of
    `MARGIN_SAFE` or more (`anchor_checks` saw to that)."""
    engine, checks = st["engine"], st["checks"]
    n_lone = len(ctx.traffic["engine"]["warm_prefill_lengths"])
    got: Dict[int, Dict[str, Any]] = {}
    for group in [[r] for r in checks[:n_lone]] + [checks[n_lone:]]:
        got.update(serve(ctx, engine, group, ctx.clock(), 0.0, 900.0,
                         st["blocks"], st["block"]).req)
    gaps = [tie_gap(st["anchor"][i], got[r["uid"]]["tokens"][0])
            if got[r["uid"]].get("tokens") else 1e9
            for i, r in enumerate(checks)]
    margins = st["margins"]
    return {"ok": all(g <= TIE_TOL for g in gaps), "tie_tolerance": TIE_TOL,
            "first_token_gaps": [round(g, 5) for g in gaps],
            "argmax_exact": sum(g == 0.0 for g in gaps), "checked": len(gaps),
            "margins": margins and [round(m, 5) for m in margins],
            "redrawn": len(st["margins_redrawn"]),
            "margins_redrawn": [round(m, 5) for m in st["margins_redrawn"]],
            "margin_safe": MARGIN_SAFE}


def measure(ctx, st, seconds: float, stream: int, traced: bool = False
            ) -> Dict[str, Any]:
    """Ramp, window, drain. The window's requests are the `N = round(r*T)`
    due in `[0, seconds)`; the ramp offers the same mix for `ramp_seconds`
    before it opens. A `traced` window runs under the profiler, is marked
    `pb:traced`, and is cut off when it closes (nothing of it is judged, so
    nothing is drained; stopping the profiler stalls the loop for seconds)."""
    engine, tf = st["engine"], ctx.traffic
    ramp_s = float(tf["ramp_seconds"])
    uid0 = 1_000_000 * (stream + 1)
    ramp = tg.make_requests(tf, ramp_s, ctx.seed, st["vocab"], start=-ramp_s,
                            uid0=uid0, stream=stream + 100)
    window = tg.make_requests(tf, seconds, ctx.seed, st["vocab"],
                              uid0=uid0 + 500_000, stream=stream)
    reqs = sorted(ramp + window, key=lambda r: r["due"])
    before = ctx.compiles
    t0 = ctx.clock() + ramp_s
    with ctx.counting_compiles():
        served = serve(ctx, engine, reqs, t0, seconds,
                       0.0 if traced else float(tf["drain_seconds"]),
                       st["blocks"], st["block"],
                       trace_t0=engine.tracer.now() + ramp_s,
                       mark=(lambda: ctx.annotate("traced")) if traced else None)
    return {"served": served, "window": window, "t_open": t0,
            "compiles": ctx.compiles - before}


def collect(ctx, rec: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """The window's samples and counts, for the readers."""
    served, window = rec["served"], rec["window"]
    slo = ctx.traffic.get("slo", {})
    s = {k: [] for k in ("ttft_ms", "tpot_ms", "gen_late_ms", "admit_wait_ms")}
    done = met = out_tokens = 0
    t_last = 0.0
    for w in window:
        r = served.req.get(w["uid"], w)
        if "picked" in r:
            s["gen_late_ms"].append((r["picked"] - r["due"]) * 1e3)
        if "admitted" in r:
            s["admit_wait_ms"].append((r["admitted"] - r["due"]) * 1e3)
        if r.get("failed") or r.get("first") is None:
            continue
        done += 1
        out_tokens += r["produced"]
        t_last = max(t_last, r["last"])
        ttft = (r["first"] - r["due"]) * 1e3
        tpot = (r["last"] - r["first"]) / max(1, r["out"] - 1) * 1e3
        s["ttft_ms"].append(ttft)
        s["tpot_ms"].append(tpot)
        if slo and ttft <= slo["ttft_ms"] and tpot <= slo["tpot_ms"]:
            met += 1
    inside = [r for r in served.rounds if 0 <= r[0] <= seconds]
    s["round_ms"] = [r[1] * 1e3 for r in inside]
    s["occupancy"] = [100.0 * r[2] / ctx.traffic["engine"]["max_batch"]
                      for r in inside]
    n = len(window)
    counts = {"attempted": n, "failed": n - done, "missing": n - done,
              "slo_met": met, "completed_share": done / n if n else 0.0,
              "backlog_mid": served.backlog_mid, "backlog_end": served.backlog_end,
              "rounds": len(inside), "out_tokens": out_tokens,
              "out_tok_s": out_tokens / t_last if t_last > 0 else 0.0,
              "drain_s": max(0.0, t_last - seconds),
              "compiles_in_window": rec["compiles"], **tg.token_totals(window)}
    # for a reader of untraced lines (`noise.py`): the first tokens' mean and
    # the percentiles beside the judged one; the metrics' readers read `samples`
    if s["ttft_ms"]:
        counts.update(ttft_mean_ms=sum(s["ttft_ms"]) / len(s["ttft_ms"]),
                      tpot_mean_ms=sum(s["tpot_ms"]) / len(s["tpot_ms"]),
                      **{f"ttft_p{q}_ms": tg.percentile(s["ttft_ms"], q, n - done)
                         for q in (50, 90)})
    return {"samples": s, "counts": counts}


def run(ctx, devices) -> Dict[str, Any]:
    st = build(ctx, devices)
    check = warm(ctx, st)
    engine = st["engine"]
    misses0 = engine.recompiles.pinned_misses
    rec = measure(ctx, st, ctx.seconds, stream=0)
    got = collect(ctx, rec, ctx.seconds)
    ctx.samples.update(got["samples"])
    ctx.counters.update(got["counts"])
    ctx.counters["setup_s"] = rec["t_open"] - ctx.t_start
    ctx.counters["recompiles_in_window"] = rec["compiles"] + (
        engine.recompiles.pinned_misses - misses0)
    n = got["counts"]["attempted"]
    tail = float(ctx.traffic["tail_percentile"])
    if not ctx.rehearsal and not tg.percentile_supported(n, tail):
        raise SystemExit(f"perfbench: {n} requests in the window cannot carry "
                         f"a {tail:g}th percentile (ten samples beyond it)")
    if ctx.traced:
        # a further, short window of the same traffic under the profiler,
        # after the judged one has drained: that one ran undisturbed
        trace_s = float(ctx.traffic["trace_seconds"])
        with ctx.profile():
            trec = measure(ctx, st, trace_s, stream=1, traced=True)
        ctx.counters["traced_rounds"] = len(
            [r for r in trec["served"].rounds if 0 <= r[0] <= trace_s])
        ctx.spans["requests"] = [
            engine.tracer.last_requests[w["uid"]] for w in rec["window"]
            if w["uid"] in engine.tracer.last_requests]
    return {"correct": check["ok"], "attempted": n,
            "failed": got["counts"]["failed"],
            "notes": {"check": check, **got["counts"]},
            "compared": compared(check["first_token_gaps"], check["margins"])}
