"""The knee sweep of a serving cell: run ONCE, by a benchmark PR, on the chip.

    python3 perfbench/sweep.py --workload qwen2.5-3b.serve-chat \
        --rates 2,3,4,6,8,10,12,16 --seconds 45 --out chiprun_out/sweep.json

One process builds the engine once and offers the cell's traffic at each rate
in turn (one window per rate, ramp and drain as in a run), stopping two rates
after the first that fails `traffic.rate_sustained`. Then one more window at
half the knee gives the medians the `slo` limits are set from. The table it
writes is kept beside the traffic file (`traffic/<name>.sweep.json`), so a
later benchmark PR can see when the cell's rate has been overtaken. The
driver never runs this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, traffic as tg  # noqa: E402


def row_of(ctx, runner, st, rate: float, seconds: float, stream: int):
    ctx.traffic["arrivals"]["rate"] = rate
    rec = runner.measure(ctx, st, seconds, stream=stream)
    got = runner.collect(ctx, rec, seconds)
    s, c = got["samples"], got["counts"]
    pct = lambda series, q: tg.percentile(s[series], q, c["missing"]
                                          if series != "gen_late_ms" else 0)
    row = {"rate": rate, "requests": c["attempted"],
           "completed_share": c["completed_share"],
           "backlog_mid": c["backlog_mid"], "backlog_end": c["backlog_end"],
           "ttft_p50_ms": pct("ttft_ms", 50), "ttft_p90_ms": pct("ttft_ms", 90),
           "tpot_p50_ms": pct("tpot_ms", 50), "tpot_p90_ms": pct("tpot_ms", 90),
           "gen_late_p90_ms": pct("gen_late_ms", 90),
           "occupancy_mean": sum(s["occupancy"]) / max(1, len(s["occupancy"])),
           "out_tok_s": c["out_tok_s"], "compiles_in_window": c["compiles_in_window"]}
    # a tail that fell on a failed request has no value: the rate failed
    row["sustained"] = all(row[k] is not None for k in (
        "tpot_p50_ms", "gen_late_p90_ms")) and tg.rate_sustained(row)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="2,3,4,6,8,10,12,16")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--share", type=float, default=0.75)
    ap.add_argument("--out", default="chiprun_out/sweep.json")
    ap.add_argument("--commit", default="(working tree)")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--set", action="append", metavar="KEY=JSON",
                    help="one traffic parameter, as run.py takes it")
    args = ap.parse_args(argv)

    ctx, devices = harness.prepare(args, T_START, traced=False)
    runner = ctx.manifest.module("runners", ctx.traffic["kind"])
    st = runner.build(ctx, devices)
    check = runner.warm(ctx, st)

    table, fails = [], 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = row_of(ctx, runner, st, rate, args.seconds, stream=10 + i)
        table.append(row)
        print(json.dumps(row), flush=True)
        fails += 0 if row["sustained"] and not fails else 1
        if fails > tg.KNEE_STOP_AFTER_FAILS:
            break
    knee = tg.find_knee(table)
    doc = {"workload": args.workload, "commit": args.commit,
           "device": ctx.device, "seconds": args.seconds, "seed": args.seed,
           "rule": {"completed_share_min": tg.KNEE_MIN_COMPLETED,
                    "backlog": "end <= middle",
                    "gen_late_p90_ms_max": f"{tg.KNEE_LATE_ROUNDS} x tpot_p50_ms"},
           "engine": ctx.traffic["engine"],
           "correct": check["ok"], "table": table, "knee": knee}
    if knee:
        half = row_of(ctx, runner, st, round(knee / 2, 1), args.seconds,
                      stream=50)
        print(json.dumps(half), flush=True)
        doc["half_knee"] = half
        doc["rate"] = tg.cell_rate(knee, args.share)
        doc["share_of_knee"] = args.share
        doc["slo"] = {"ttft_ms": round(2 * half["ttft_p50_ms"], -1),
                      "tpot_ms": round(2 * half["tpot_p50_ms"]),
                      "rule": "twice the medians at half the knee, rounded"}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({k: doc.get(k) for k in ("knee", "rate", "slo")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
