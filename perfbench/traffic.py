"""The one general traffic generator, and the arithmetic on what a run records.

A traffic mix is a data file (`perfbench/traffic/<name>.json`); everything a
mix can ask for is read here, so a later cell is a new file and no new code.

Seed-to-seed variance is taken out of the GENERATOR and not out of the
traffic: for a window of `T` seconds at rate `r` every seed offers exactly
`N = round(r*T)` requests; arrivals are a Poisson (or gamma) process
conditioned on that count; the lengths are the distribution's inverse CDF at
the MIDPOINTS of its N equal-probability strata, `(i + 1/2)/N`, shuffled. So
every seed offers the same set of sizes in another order, at other moments,
with the process's local burstiness kept. (Drawing inside each stratum, at
`(i + u_i)/N`, moved `ttft_p80_ms` by the width of the 61st stratum: 4.3%
between seeds against 0.1% between two runs of one seed; my chip run, PR 23.)

That still leaves the seed WHICH prompt stands behind which, and a tail of
first-token times is made of just that: six seeds spread `ttft_p80_ms` by
12-20%, and by 2-3.5% once they serve one schedule (PERF.md, PR 40). A mix
that names
`arrivals.cycle` (`seed`, `seconds`) takes that out as well: ONE schedule of
`round(rate * cycle.seconds)` requests, drawn as above from the cycle's own
seed, is laid on a circle, and `--seed` turns the circle: every seed offers
the same requests at the same gaps behind the same neighbours, beginning at
another of them, and the ramp is the stretch of the circle before the
window's first. The token ids stay the seed's.

Nothing here touches JAX.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_NORMAL = NormalDist()


# ------------------------------------------------------------ distributions


def quantile(dist: Dict[str, Any], u: np.ndarray) -> np.ndarray:
    """Inverse CDF of `dist` at probabilities `u` in (0, 1), as whole
    numbers clipped to the distribution's `min`/`max`."""
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(u.shape, float(dist["value"]))
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified(dist: Dict[str, Any], n: int, rng: np.random.Generator
               ) -> np.ndarray:
    """`n` values, the inverse CDF at the midpoint of each of `n`
    equal-probability strata, shuffled: the same set for every seed. A
    `cycle` distribution is its values in order, repeated."""
    if dist["dist"] == "cycle":
        vals = list(dist["values"])
        return np.asarray([vals[i % len(vals)] for i in range(n)], np.int64)
    x = quantile(dist, (np.arange(n) + 0.5) / n)
    rng.shuffle(x)
    return x


def arrival_times(arrivals: Dict[str, Any], n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """`n` sorted arrival times in [0, seconds). `poisson`: sorted i.i.d.
    uniforms (a Poisson process given its count). `gamma`: inter-arrival
    gaps of coefficient of variation `cv`, scaled so that the n-th falls
    inside the window (bursts kept, count fixed)."""
    proc = arrivals.get("process", "poisson")
    if n == 0:
        return np.zeros((0,))
    if proc == "poisson":
        return np.sort(rng.random(n)) * seconds
    if proc == "gamma":
        cv = float(arrivals["cv"])
        gaps = rng.gamma(1.0 / cv ** 2, cv ** 2, size=n + 1)
        t = np.cumsum(gaps)
        return t[:n] / t[n] * seconds
    if proc == "back_to_back":
        return np.zeros((n,))
    raise ValueError(f"unknown arrival process {proc!r}")


# ------------------------------------------------------------------ requests


def request_count(traffic: Dict[str, Any], seconds: float) -> int:
    return int(round(float(traffic["arrivals"]["rate"]) * seconds))


CYCLE_STREAM = 0x43594331      # the draw that turns the circle, apart from any stream's


def cycle_slice(traffic: Dict[str, Any], seconds: float, seed: int,
                start: float = 0.0):
    """The stretch `[start, start + seconds)` of the mix's one schedule as
    seed `seed` sees it: `due`, prompt lengths, output lengths, in order of
    `due`. The schedule is `arrivals.cycle`: `N = round(rate * cycle.seconds)`
    arrivals and sizes from `cycle.seed`, repeating every `cycle.seconds`.
    The seed gives the moment of the circle that is the window's time 0 (one
    draw, whatever the stream, so that a ramp runs into its window). A
    stretch as long as the cycle holds each of the N requests once."""
    cyc = traffic["arrivals"]["cycle"]
    period = float(cyc["seconds"])
    own = np.random.default_rng([int(cyc["seed"])])
    n = request_count(traffic, period)
    at = arrival_times(traffic["arrivals"], n, period, own)
    plen = stratified(traffic["prompt"], n, own)
    olen = stratified(traffic["output"], n, own)
    turn = np.random.default_rng([int(seed), CYCLE_STREAM]).random() * period
    first = np.mod(at - (turn + start), period)   # each one's wait from `start`
    first[first >= period] = 0.0                  # a rounding of -0.0
    laps = max(1, math.ceil(seconds / period))
    wait = (first[None, :] + period * np.arange(laps)[:, None]).ravel()
    which = np.tile(np.arange(n), laps)
    keep = wait < seconds
    order = np.argsort(wait[keep], kind="stable")
    which = which[keep][order]
    return start + wait[keep][order], plen[which], olen[which]


def make_requests(traffic: Dict[str, Any], seconds: float, seed: int,
                  vocab: int, start: float = 0.0, uid0: int = 0,
                  stream: int = 0) -> List[Dict[str, Any]]:
    """The requests due in `[start, start + seconds)`: dicts with `uid`,
    `due` (seconds from the window's opening), `prompt` (int32 token ids),
    `out` (tokens to generate, forced). `stream` separates the ramp's
    draws from the window's under one seed."""
    rng = np.random.default_rng([int(seed), int(stream)])
    if traffic["arrivals"].get("cycle"):
        due, plen, olen = cycle_slice(traffic, seconds, seed, start)
        n = len(due)
    else:
        n = request_count(traffic, seconds)
        due = start + arrival_times(traffic["arrivals"], n, seconds, rng)
        plen = stratified(traffic["prompt"], n, rng)
        olen = stratified(traffic["output"], n, rng)
    pre = traffic.get("prefix")
    pool = []
    if pre:
        pool = [rng.integers(1, vocab, int(pre["length"])).astype(np.int32)
                for _ in range(int(pre.get("pool", 1)))]
    reqs = []
    for i in range(n):
        body = rng.integers(1, vocab, int(plen[i])).astype(np.int32)
        if pool and rng.random() < float(pre["share"]):
            # `prompt` in the file is the length of the unshared tail
            body = np.concatenate([pool[int(rng.integers(len(pool)))], body])
        reqs.append({"uid": uid0 + i, "due": float(due[i]), "prompt": body,
                     "out": int(olen[i])})
    return reqs


def token_totals(reqs: Sequence[Dict[str, Any]]) -> Dict[str, int]:
    return {"requests": len(reqs),
            "prompt_tokens": int(sum(len(r["prompt"]) for r in reqs)),
            "output_tokens": int(sum(r["out"] for r in reqs))}


# --------------------------------------------------------------- percentiles


def percentile(values: Sequence[float], q: float,
               missing: int = 0) -> Optional[float]:
    """Nearest-rank `q`-th percentile (0 < q <= 100) of `values` plus
    `missing` samples that are worse than any value (failed requests miss
    every limit). None where the rank falls on a missing sample or there
    are no samples: the caller reports no value, never a flattering one."""
    n = len(values) + missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))      # 1-based
    if rank > len(values):
        return None
    return float(sorted(values)[rank - 1])


def samples_beyond(n: int, q: float) -> int:
    """How many of `n` samples lie beyond the nearest-rank `q`-th one."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def percentile_supported(n: int, q: float, beyond: int = 10) -> bool:
    """The choosing-metrics rule: report the highest percentile that has at
    least ten samples beyond it. A p90 needs 100 requests, a p95 200."""
    return samples_beyond(n, q) >= beyond



# ---------------------------------------------------------------- knee rule

KNEE_MIN_COMPLETED = 0.97      # of the requests due in the window
KNEE_LATE_ROUNDS = 4.0         # gen_late_p90 under this many median rounds
KNEE_STOP_AFTER_FAILS = 2      # rates swept past the first that fails


def rate_sustained(row: Dict[str, Any]) -> bool:
    """One row of a sweep table: did the system sustain this rate?
    (a) at least 97% of the requests due in the window completed by the end
    of the drain; (b) the backlog (due, not admitted) at the window's end is
    no larger than at its middle; (c) the generator's lateness stays under
    four median rounds (the loop picks arrivals up only between rounds, so a
    round or two is inherent)."""
    return (row["completed_share"] >= KNEE_MIN_COMPLETED
            and row["backlog_end"] <= row["backlog_mid"]
            and row["gen_late_p90_ms"] < KNEE_LATE_ROUNDS * row["tpot_p50_ms"])


def find_knee(table: Sequence[Dict[str, Any]]) -> Optional[float]:
    """The highest swept rate that was sustained, with every lower swept
    rate sustained too (a rate that passes above one that fails is luck)."""
    knee = None
    for row in sorted(table, key=lambda r: r["rate"]):
        if not rate_sustained(row):
            break
        knee = float(row["rate"])
    return knee


def cell_rate(knee: float, share: float = 0.75) -> float:
    """The fixed rate of a cell below the knee, rounded to 0.1 req/s."""
    return round(knee * share, 1)
