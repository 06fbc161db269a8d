"""Fault-composable traffic replay against the v2 serving engine.

Drives `InferenceEngineV2` with an open-loop request stream — Poisson
arrivals, weighted prompt/output-length mixes, a shared-prefix pool — and
asserts the request-span tracing contract end to end:

  - ZERO dropped requests: every submitted uid finishes with a
    `request_span` summary (faults retried at the put() boundary — the
    engine's `generate_dispatch` fault point fires BEFORE any admission
    mutation, so a retry sees clean state);
  - stall accounting: per-request `unattributed_frac` stays under
    `--max-unattributed` (default 1%) — in put mode the harness wraps each
    scheduling round in a depth-0 `round` span, so fault stalls and retry
    backoff inside the round attribute instead of leaking;
  - resilience instants 1:1: every fault/retry/watchdog/degrade event the
    hub saw during the replay is mirrored in the tracer's `instants`;
  - the Chrome-trace export parses and is monotonic (ts/dur >= 0).

Runnable with a fault schedule mid-flight:

  DS_TPU_FAULTS="generate_dispatch/v2_put:raise@3,7" \\
      python benchmarks/traffic_replay.py --n-requests 8

Two drive modes: `--api put` (default; the harness IS the serving loop —
continuous batching via put(argmax_only=True), per-arrival admission) and
`--api generate` (one engine.generate() call over the whole stream; the
engine's own loop provides the admit/decode_wave/mixed_round
decomposition and the OOM degrade ladder — compose with
DS_TPU_FAULTS="program_compile/<mode>:oom@1" and `--floor` to assert a
degraded-mode throughput floor).

Prints ONE JSON summary line; exit code 1 when any assertion failed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_mix(spec: str):
    """'12:2,24:1' → ([12, 24], [2/3, 1/3])."""
    lens, weights = [], []
    for part in spec.split(","):
        n, _, w = part.partition(":")
        lens.append(int(n))
        weights.append(float(w) if w else 1.0)
    total = sum(weights)
    return lens, [w / total for w in weights]


def build_workload(args, vocab: int, rng: np.random.Generator):
    """The replay script: (uid, arrival_s, prompt ndarray, out_target)."""
    plens, pw = _parse_mix(args.prompt_mix)
    olens, ow = _parse_mix(args.out_mix)
    # shared-prefix pool: block-aligned length so paged prefix matching can
    # commit full blocks (partial tails never register)
    pool = [rng.integers(0, vocab, args.prefix_len).astype(np.int32)
            for _ in range(max(1, args.prefix_pool))]
    t, reqs = 0.0, []
    for i in range(args.n_requests):
        t += float(rng.exponential(1.0 / args.rate))
        plen = int(rng.choice(plens, p=pw))
        out = int(rng.choice(olens, p=ow))
        tail = rng.integers(0, vocab, plen).astype(np.int32)
        if args.prefix_share > 0 and rng.random() < args.prefix_share:
            pre = pool[int(rng.integers(0, len(pool)))]
            prompt = np.concatenate([pre, tail])
        else:
            prompt = tail
        reqs.append((i, t, prompt, out))
    return reqs


def replay_put(engine, reqs, args):
    """Open-loop continuous batching through put(argmax_only=True). The
    harness is the serving loop, so it owns the depth-0 `round` span (put's
    prefill/chunk/decode spans nest inside it and still export to the
    Chrome trace) and the first-token stamps."""
    from deepspeed_tpu.resilience.retry import retry_call

    tr = engine.tracer
    pending = list(reqs)           # arrival-ordered
    live = {}                      # uid -> [produced, target, last_token]
    draining = set()               # admitted, prefill not finished
    produced_total = 0
    t0 = time.perf_counter()
    trace_t0 = tr.now()            # arrival_s → tracer timeline offset
    t_first = None

    while pending or live or draining:
        now = time.perf_counter() - t0
        feeds_u, feeds_t = [], []
        # admit due arrivals while slots are free
        while pending and pending[0][1] <= now and \
                len(live) + len(draining) < engine.max_batch:
            uid, arr, prompt, out = pending.pop(0)
            tr.begin_request(uid, prompt_tokens=len(prompt),
                             submit_s=trace_t0 + arr)
            feeds_u.append(uid)
            feeds_t.append(prompt)
            draining.add(uid)
            live[uid] = [0, out, None]
        for uid, st in live.items():
            if st[2] is not None:          # has a token to feed back
                feeds_u.append(uid)
                feeds_t.append(np.asarray([st[2]], np.int32))
                st[2] = None
        if not feeds_u and not draining:
            # idle: no live work, next arrival in the future
            if pending:
                time.sleep(max(0.0, pending[0][1]
                               - (time.perf_counter() - t0)))
            continue
        with tr.span("round", uids=tuple(live)):
            out = retry_call(
                lambda: engine.put(feeds_u, feeds_t, argmax_only=True),
                what="traffic_replay_put", retries=args.retries,
                base_delay=0.01)
            if t_first is None:
                t_first = time.perf_counter()
            for uid, tok in out.items():
                st = live.get(uid)
                if st is None:
                    continue
                tok = int(np.asarray(tok).reshape(-1)[-1])
                if st[0] == 0:
                    tr.first_token(uid)
                draining.discard(uid)
                st[0] += 1
                produced_total += 1
                st[2] = tok
        done = [uid for uid, st in live.items() if st[0] >= st[1]]
        if done:
            engine._flush_batch(done)      # ends the request traces
            for uid in done:
                del live[uid]
    dt = (time.perf_counter() - (t_first or t0))
    return produced_total, dt


def replay_generate(engine, reqs, args):
    """One generate() call over the stream — the engine's own continuous-
    batching loop provides the span decomposition and the degrade ladder."""
    prompts = [list(map(int, p)) for _, _, p, _ in reqs]
    max_new = max(out for _, _, _, out in reqs)
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=max_new)
    dt = time.perf_counter() - t0
    return sum(max(0, len(o) - len(p)) for o, p in zip(outs, prompts)), dt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--prompt-mix", default="12:2,24:1",
                    help="len:weight[,len:weight...]")
    ap.add_argument("--out-mix", default="4:2,8:1")
    ap.add_argument("--prefix-share", type=float, default=0.5,
                    help="fraction of prompts drawing a pooled prefix")
    ap.add_argument("--prefix-pool", type=int, default=2)
    ap.add_argument("--prefix-len", type=int, default=16)
    ap.add_argument("--api", choices=("put", "generate"), default="put")
    ap.add_argument("--serve-mode", default=None,
                    help="dequant | layer_scan | capacity (streamed modes "
                         "quantize the tree and force the slot KV layout)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--retries", type=int, default=4,
                    help="put-round retry budget (absorbs raise faults)")
    ap.add_argument("--max-unattributed", type=float, default=0.01)
    ap.add_argument("--floor", type=float, default=None,
                    help="assert decode throughput >= FLOOR tok/s "
                         "(degraded-mode acceptance)")
    ap.add_argument("--jsonl", default="traffic_replay.jsonl")
    ap.add_argument("--export-trace", metavar="OUT", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.accelerator import on_tpu as _on_tpu
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, materialize_params
    from deepspeed_tpu.resilience.faults import faults_active
    from deepspeed_tpu.telemetry import hub as hub_mod
    from deepspeed_tpu.telemetry.spans import INSTANT_KINDS, \
        export_chrome_trace
    from deepspeed_tpu.utils import groups

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=4096, num_hidden_layers=24,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=2048, remat=False,
                          dtype=jnp.bfloat16)
        mb, msl = 16, 1024
    else:
        cfg = LlamaConfig(vocab_size=1024, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, remat=False,
                          dtype=jnp.float32)
        mb, msl = 4, 128

    try:
        os.remove(args.jsonl)
    except OSError:
        pass
    hub = hub_mod.TelemetryHub(enabled=True, jsonl_path=args.jsonl)
    hub_mod.set_hub(hub)
    # count resilience instants independently of the tracer's mirror — the
    # 1:1 assertion compares the two tallies over the same event stream
    fired = {}

    def _count(rec):
        if rec.get("kind") in INSTANT_KINDS:
            fired[rec["kind"]] = fired.get(rec["kind"], 0) + 1
    hub_mod.add_listener(_count)

    rng = np.random.default_rng(args.seed)
    groups.reset_topology()
    model, params = materialize_params(cfg)
    kw = dict(max_batch=mb, max_seq_len=msl, split_fuse_chunk=16,
              cache_block_size=args.prefix_len)
    if args.serve_mode not in (None, "dequant"):
        kw.update(quant={"enabled": True})
    if args.serve_mode is not None:
        kw.update(serve_mode=args.serve_mode)
    engine = InferenceEngineV2(model, params=params, **kw)
    engine.tracer.attach()

    reqs = build_workload(args, cfg.vocab_size, rng)
    if args.api == "put":
        produced, dt = replay_put(engine, reqs, args)
    else:
        produced, dt = replay_generate(engine, reqs, args)
    for hname in ("ttft_s", "tpot_s", "e2e_s"):
        hub.histogram_event(hname)

    tr = engine.tracer
    failures = []
    finished = {s["uid"]: s for s in tr.last_requests.values()}
    dropped = [uid for uid, _, _, _ in reqs if uid not in finished]
    if dropped:
        failures.append(f"dropped requests: {dropped}")
    worst_unattr = max((s["unattributed_frac"]
                        for s in finished.values()), default=0.0)
    if worst_unattr > args.max_unattributed:
        worst = max(finished.values(),
                    key=lambda s: s["unattributed_frac"])
        failures.append(
            f"unattributed_frac {worst_unattr:.4f} > "
            f"{args.max_unattributed} (uid {worst['uid']}, "
            f"spans {worst['spans']})")
    mirrored = {}
    for inst in tr.instants:
        mirrored[inst["kind"]] = mirrored.get(inst["kind"], 0) + 1
    if mirrored != fired:
        failures.append(f"instant mirror mismatch: hub saw {fired}, "
                        f"tracer mirrored {mirrored}")
    tok_s = produced / dt if dt > 0 else 0.0
    if args.floor is not None and tok_s < args.floor:
        failures.append(f"throughput {tok_s:.1f} tok/s under floor "
                        f"{args.floor}")
    if args.export_trace:
        from deepspeed_tpu.telemetry.__main__ import load_events
        trace = export_chrome_trace(load_events(args.jsonl),
                                    path=args.export_trace)
        bad = [e for e in trace["traceEvents"]
               if e.get("ts", 0) < 0 or e.get("dur", 0) < 0]
        if bad:
            failures.append(f"non-monotonic trace events: {bad[:3]}")
        json.loads(open(args.export_trace).read())  # parses back

    ttfts = sorted(s["ttft_s"] for s in finished.values()
                   if s.get("ttft_s") is not None)
    pct = lambda a, q: a[min(len(a) - 1, int(q * len(a)))] if a else None
    print(json.dumps({
        "harness": "traffic_replay", "api": args.api,
        "serve_mode": engine.serve_mode, "requests": len(reqs),
        "finished": len(finished), "dropped": len(dropped),
        "decode_tok_s": round(tok_s, 1),
        "ttft_p50_ms": round(pct(ttfts, 0.5) * 1e3, 1) if ttfts else None,
        "ttft_p99_ms": round(pct(ttfts, 0.99) * 1e3, 1) if ttfts else None,
        "unattributed_frac_max": round(worst_unattr, 4),
        "faults_active": faults_active(), "instants": fired,
        "spans_recorded": tr.spans_recorded,
        "ok": not failures, "failures": failures}))
    hub_mod.remove_listener(_count)
    return 1 if failures else 0


if __name__ == "__main__":
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
