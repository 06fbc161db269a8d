"""perfbench: how far a cell's judged metrics spread over seeds, as a table.

    python3 perfbench/noise.py --lines runs.jsonl --label D --into perfbench/traffic/<mix>.noise.json

Reads JSON objects, one a line: result lines of `run.py --trace 0` (what the
driver runs), or rows that already carry the judged metrics by name. Other
lines are passed over. The rows are cut, in the order read, into sets of six;
each set gives every judged metric's readings, median and spread. The spread
is the driver's: the range of a set's readings, less the one farthest from
the median where that narrows it, over the median. With `--limits` the table
says whether every set is whole and under them (`steady`).

The table goes into `--into` under `tables`, replacing one of the same
`--label`; whatever else that file holds (the rule, the verdict) is kept.
Serves nothing and touches no JAX. The driver never runs this.
"""

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import traffic as tg  # noqa: E402

SET = 6


def trimmed_spread(values) -> float:
    """The range of the readings, less the one farthest from the median
    where that narrows it, over the median."""
    v, mid = sorted(values), statistics.median(values)
    rest = v[1:] if mid - v[0] > v[-1] - mid else v[:-1]
    return (rest[-1] - rest[0]) / mid if len(v) > 2 else (v[-1] - v[0]) / mid


def driver_spread(values) -> float:
    """The check's own measure of a set (BENCHMARK_REFUSED.md, PR 40): the
    distance between the first and third quartiles (`statistics.quantiles`,
    n=4) of the readings less the one farthest from their median, over the
    median of all of them."""
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    q = statistics.quantiles(rest, n=4)
    return (q[2] - q[0]) / mid


def row_of(line):
    """A result line of `run.py --trace 0` as a flat row; a row as it is."""
    if "metrics" not in line:
        return line
    n = line["notes"]
    row = {"seed": line["seed"], "requests": line["attempted"],
           "failed": line["failed"], "correct": line["correct"],
           "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
    row.update({k: n[k] for k in ("completed_share", "backlog_mid",
                                  "backlog_end", "compiles_in_window",
                                  "out_tok_s")})
    row.update({k: n[k] for k in ("ttft_mean_ms", "tpot_mean_ms",
                                  "ttft_p50_ms", "ttft_p90_ms") if k in n})
    row.update({k: v["value"] for k, v in line["metrics"].items()})
    return row


def whole(row) -> bool:
    """Nothing failed or compiled in the window and the queue did not grow.
    A sweep's row brings `sustained` by the knee's full rule; a line of
    `run.py --trace 0` keeps no lateness series, so its rule (c) is not
    judged here."""
    return (row["failed"] == 0 and row["compiles_in_window"] == 0
            and row["completed_share"] >= tg.KNEE_MIN_COMPLETED
            and row["backlog_end"] <= row["backlog_mid"]
            and row.get("correct", True) and row.get("sustained", True))


def sets_of(rows, names):
    out = []
    for k in range(len(rows) // SET):
        part = rows[k * SET:(k + 1) * SET]
        one = {"seeds": [r["seed"] for r in part],
               "whole": all(whole(r) for r in part)}
        for name in names:
            vals = [r[name] for r in part]
            one[name] = {"readings": vals, "median": statistics.median(vals),
                         "trimmed_spread": trimmed_spread(vals),
                         "driver_spread": driver_spread(vals)}
        out.append(one)
    return out


def steady(sets, limits) -> bool:
    """Every set whole, every limited metric's spread at or under its limit."""
    return bool(sets) and all(s["whole"] and all(
        s[name]["trimmed_spread"] <= lim for name, lim in limits.items())
        for s in sets)


def table_of(lines, label, names, limits=None, where=None, about=None):
    rows = [row_of(x) for x in lines]
    rows = [r for r in rows if all(n in r for n in names)
            and all(r.get(k) == v for k, v in (where or {}).items())]
    table = {"label": label, **(about or {}), "rows": rows,
             "sets": sets_of(rows, names)}
    if limits:
        table.update(limits=limits, steady=steady(table["sets"], limits))
    return table


def pairs(text):
    return {k: json.loads(v) for k, _, v in
            (x.partition("=") for x in text.split(",") if x)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lines", required=True)
    ap.add_argument("--label", required=True)
    ap.add_argument("--into", required=True)
    ap.add_argument("--metrics", default="ttft_p80_ms,tpot_p80_ms")
    ap.add_argument("--limits", default="", metavar="METRIC=SPREAD,...")
    ap.add_argument("--where", default="", metavar="KEY=JSON,...",
                    help="keep the rows that read so, e.g. rate=4.5")
    ap.add_argument("--about", default="{}", metavar="JSON",
                    help="what the table is of: share, rate, mix, origin")
    args = ap.parse_args(argv)

    with open(args.lines) as f:
        lines = [json.loads(x) for x in f if x.lstrip().startswith("{")]
    table = table_of(lines, args.label, args.metrics.split(","),
                     pairs(args.limits), pairs(args.where),
                     json.loads(args.about))
    doc = {}
    if os.path.exists(args.into):
        with open(args.into) as f:
            doc = json.load(f)
    doc["tables"] = [t for t in doc.get("tables", [])
                     if t["label"] != args.label] + [table]
    with open(args.into, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"label": args.label, "sets": [
        {n: s[n]["trimmed_spread"] for n in args.metrics.split(",")}
        for s in table["sets"]], "steady": table.get("steady")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
