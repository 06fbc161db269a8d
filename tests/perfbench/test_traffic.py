"""The generator's promises and the arithmetic on a run's record: no JAX."""

import json
import os

import numpy as np
import pytest

from perfbench import traffic as tg

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = json.load(open(os.path.join(HERE, "..", "..", "perfbench", "traffic",
                                   "serve-chat.json")))
CHAT_POISSON = {**CHAT, "arrivals": {k: v for k, v in CHAT["arrivals"].items()
                                     if k != "cycle"}}
SEEDS = [0, 1, 7, 2147483659, 3000000019]
VOCAB = 151936   # Qwen2.5's; a name, not a context length


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("rate,seconds", [(6.0, 45), (7.3, 45), (2.0, 10)])
def test_exact_count_sorted_inside_window(seed, rate, seconds):
    tf = {**CHAT, "arrivals": {"process": "poisson", "rate": rate}}
    reqs = tg.make_requests(tf, seconds, seed, vocab=VOCAB)
    assert len(reqs) == round(rate * seconds)
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
    assert all(32 <= len(r["prompt"]) <= 4096 and 8 <= r["out"] <= 512
               for r in reqs)
    assert all(r["prompt"].min() >= 1 and r["prompt"].max() < VOCAB
               for r in reqs)


def test_same_seed_same_requests_other_seed_other_order():
    a = tg.make_requests(CHAT_POISSON, 45, 11, VOCAB)
    b = tg.make_requests(CHAT_POISSON, 45, 11, VOCAB)
    c = tg.make_requests(CHAT_POISSON, 45, 12, VOCAB)
    assert all((x["prompt"] == y["prompt"]).all() and x["due"] == y["due"]
               for x, y in zip(a, b))
    assert [len(x["prompt"]) for x in a] != [len(x["prompt"]) for x in c]


def test_every_seed_offers_the_same_sizes_in_another_order():
    """The lengths are the strata's midpoints: every seed has the same set
    and so the same token totals, where independent draws from this
    heavy-tailed mix differ by several percent. The order and the arrival
    times are the seed's."""
    runs = [tg.make_requests(CHAT_POISSON, 51, s, VOCAB) for s in SEEDS]
    assert len({json.dumps(tg.token_totals(r)) for r in runs}) == 1
    for key in ("prompt", "out"):
        size = lambda r: len(r[key]) if key == "prompt" else r[key]
        sets = [sorted(size(r) for r in run) for run in runs]
        assert all(s == sets[0] for s in sets)
        assert [size(r) for r in runs[0]] != [size(r) for r in runs[1]]
    assert [r["due"] for r in runs[0]] != [r["due"] for r in runs[1]]
    # the 80th percentile of the prompt lengths is one number, whatever the seed
    assert len({sorted(len(r["prompt"]) for r in run)[60] for run in runs}) == 1
    rng = np.random.default_rng(0)
    loose = [np.clip(512 * np.exp(rng.normal(size=76)), 32, 4096).sum()
             for _ in range(5)]
    assert (max(loose) - min(loose)) / np.mean(loose) > 0.02


def test_ramp_and_window_streams_differ_under_one_seed():
    ramp = tg.make_requests(CHAT_POISSON, 6, 3, VOCAB, start=-6.0, stream=100)
    win = tg.make_requests(CHAT_POISSON, 6, 3, VOCAB, stream=0)
    assert all(-6 <= r["due"] < 0 for r in ramp)
    assert [len(r["prompt"]) for r in ramp] != [len(r["prompt"]) for r in win]


# ------------------------------------------------ one schedule, turned by the seed

CYCLED = {**CHAT, "arrivals": {"process": "poisson", "rate": 1.5,
                               "cycle": {"seed": 40, "seconds": 51}}}


def _turned_to(run, head):
    """`run` (sizes and gaps in order of `due`) begun at its first `head`."""
    k = next(i for i, x in enumerate(run) if x[:2] == head)
    return run[k:] + run[:k]


@pytest.mark.parametrize("seed", SEEDS)
def test_a_cycle_offers_every_seed_the_same_requests_behind_the_same_neighbours(seed):
    """The same sizes AND the same gaps in the same cyclic order: only the
    request the window begins at is the seed's."""
    def ring(s):
        reqs = tg.make_requests(CYCLED, 51, s, VOCAB)
        due = [r["due"] for r in reqs]
        assert len(reqs) == 76 and due == sorted(due) and 0 <= due[0] and due[-1] < 51
        gaps = np.diff(due + [due[0] + 51.0])       # to the next one round the circle
        return [(len(r["prompt"]), r["out"], g) for r, g in zip(reqs, gaps)]
    base, mine = ring(11), ring(seed)
    assert [x[:2] for x in mine] != [x[:2] for x in base]      # begun elsewhere
    again = _turned_to(mine, base[0][:2])
    assert [x[:2] for x in again] == [x[:2] for x in base]
    assert np.allclose([x[2] for x in again], [x[2] for x in base], atol=1e-9)
    assert tg.token_totals(tg.make_requests(CYCLED, 51, seed, VOCAB)) == \
        tg.token_totals(tg.make_requests(CHAT_POISSON, 51, seed, VOCAB))


@pytest.mark.parametrize("seed", SEEDS)
def test_a_cycle_s_ramp_is_the_stretch_before_its_window(seed):
    ramp = tg.make_requests(CYCLED, 10, seed, VOCAB, start=-10.0, stream=100)
    win = tg.make_requests(CYCLED, 51, seed, VOCAB, stream=0)
    assert ramp and all(-10 <= r["due"] < 0 for r in ramp)
    tail = win[-len(ramp):]                          # the circle closes
    assert [(len(r["prompt"]), r["out"]) for r in ramp] == \
        [(len(r["prompt"]), r["out"]) for r in tail]
    assert np.allclose([r["due"] + 51.0 for r in ramp], [r["due"] for r in tail])
    # the ids are the stream's own: a ramp's prompt is no copy of the window's
    assert not any((a["prompt"] == b["prompt"]).all() for a, b in zip(ramp, tail))


@pytest.mark.parametrize("seconds,start", [(6.0, 0.0), (20.0, -3.0), (102.0, 0.0),
                                           (120.5, -10.0)])
def test_a_stretch_of_any_length_is_cut_from_the_same_circle(seconds, start):
    whole = tg.make_requests(CYCLED, 51, 7, VOCAB)
    part = tg.make_requests(CYCLED, seconds, 7, VOCAB, start=start)
    due = [r["due"] for r in part]
    assert due == sorted(due) and start <= due[0] and due[-1] < start + seconds
    at = {round(r["due"], 6): len(r["prompt"]) for r in whole}
    assert all(at[round(r["due"] % 51.0, 6)] == len(r["prompt"]) for r in part)
    laps = seconds / 51.0
    assert abs(len(part) - 76 * laps) <= 12 and (seconds != 102.0 or len(part) == 152)


def test_a_cycle_is_the_same_seed_s_twice_and_follows_the_rate():
    a = tg.make_requests(CYCLED, 51, SEEDS[-1], VOCAB)
    b = tg.make_requests(CYCLED, 51, SEEDS[-1], VOCAB)
    assert all((x["prompt"] == y["prompt"]).all() and x["due"] == y["due"]
               for x, y in zip(a, b))
    fast = {**CYCLED, "arrivals": {**CYCLED["arrivals"], "rate": 3.0}}
    assert len(tg.make_requests(fast, 51, 3, VOCAB)) == 153


def test_the_cell_s_cycle_is_as_long_as_a_run():
    """Only then does every seed's window hold each request once."""
    bench = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
    assert CHAT["arrivals"]["cycle"]["seconds"] == bench["run_seconds"]


@pytest.mark.parametrize("cv", [1.0, 3.0])
def test_gamma_arrivals_keep_count_and_window(cv):
    rng = np.random.default_rng(0)
    t = tg.arrival_times({"process": "gamma", "cv": cv}, 300, 45.0, rng)
    assert len(t) == 300 and (np.diff(t) >= 0).all() and 0 <= t[0] and t[-1] < 45
    gaps = np.diff(t)
    assert abs(gaps.std() / gaps.mean() - cv) < 0.35 * cv + 0.15


def test_shared_prefix_heads_a_share_of_the_prompts():
    tf = {**CHAT_POISSON, "prefix": {"length": 2048, "share": 0.8, "pool": 2}}
    reqs = tg.make_requests(tf, 45, 5, VOCAB)
    heads = {tuple(r["prompt"][:2048]) for r in reqs if len(r["prompt"]) > 2048}
    shared = sum(1 for r in reqs if len(r["prompt"]) > 2048
                 and tuple(r["prompt"][:2048]) in heads)
    assert len(heads) <= 2 + sum(1 for r in reqs if len(r["prompt"]) > 2048) * 0.3
    assert 0.6 < shared / len(reqs) <= 1.0


def test_cycle_and_fixed_distributions():
    rng = np.random.default_rng(0)
    cyc = tg.stratified({"dist": "cycle", "values": [256, 512, 1024]}, 7, rng)
    assert list(cyc) == [256, 512, 1024, 256, 512, 1024, 256]
    assert set(tg.stratified({"dist": "fixed", "value": 9}, 5, rng)) == {9}


def test_unknown_distribution_or_process_is_an_error():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        tg.stratified({"dist": "zipf"}, 3, rng)
    with pytest.raises(ValueError):
        tg.arrival_times({"process": "bursty"}, 3, 1.0, rng)


# ------------------------------------------------------------ percentiles


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert tg.percentile(v, 90) == 90 and tg.percentile(v, 50) == 50
    assert tg.percentile(v, 100) == 100 and tg.percentile([5.0], 90) == 5.0
    assert tg.percentile([], 90) is None


def test_failed_requests_count_as_missing():
    v = list(range(1, 91))                       # 90 done, 10 failed
    assert tg.percentile(v, 90, missing=10) == 90
    assert tg.percentile(v, 95, missing=10) is None   # the rank is a failure
    assert tg.percentile(v[:80], 90, missing=20) is None


@pytest.mark.parametrize("n,q,ok", [(100, 90, True), (99, 90, False),
                                    (270, 90, True), (200, 95, True),
                                    (199, 95, False), (20, 50, True),
                                    (12, 50, False), (1000, 99, True)])
def test_sample_count_rule(n, q, ok):
    """The highest percentile reported has ten samples beyond it."""
    assert tg.percentile_supported(n, q) is ok


# ---------------------------------------------------------------- the knee


def _row(rate, done=1.0, mid=0, end=0, late=50.0, tpot=26.0):
    return {"rate": rate, "completed_share": done, "backlog_mid": mid,
            "backlog_end": end, "gen_late_p90_ms": late, "tpot_p50_ms": tpot}


def test_knee_on_a_synthetic_sweep():
    table = [_row(2), _row(4), _row(6), _row(8, late=90.0),
             _row(10, mid=3, end=40, late=2555.0), _row(12, done=0.7, end=200)]
    assert tg.find_knee(table) == 8.0
    assert tg.cell_rate(8.0) == 6.0 and tg.cell_rate(8.0, 0.6) == 4.8
    assert tg.cell_rate(9.5) == 7.1


@pytest.mark.parametrize("row,ok", [
    (_row(8), True),
    (_row(8, done=0.96), False),           # (a) too few completed
    (_row(8, mid=2, end=3), False),        # (b) the backlog grew
    (_row(8, mid=3, end=3), True),
    (_row(8, late=104.0), False),          # (c) four rounds late or more
    (_row(8, late=103.9), True)])
def test_each_condition_of_the_knee_rule(row, ok):
    assert tg.rate_sustained(row) is ok


def test_a_pass_above_a_failure_is_not_the_knee():
    assert tg.find_knee([_row(2), _row(4, done=0.5), _row(6)]) == 2.0
    assert tg.find_knee([_row(2, done=0.5)]) is None
