"""serve-chat is held to its own records: its rate, engine and `slo` to the
committed sweep by the rule that chose them, the noise record of PR 40
(`serve-chat.noise.json`, `serve-chat.sweeps-pr40/`) to what its own rows
give under the rule written before they were read, and the cell as it is
committed (table D) to half of the bounds the manifest gives its tails. No JAX."""

import glob
import json
import os
import statistics

import pytest

from perfbench import noise, traffic as tg

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "..", "..")
TRAFFIC = os.path.join(ROOT, "perfbench", "traffic")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CHAT = load(TRAFFIC, "serve-chat.json")
SWEEP = load(TRAFFIC, "serve-chat.sweep.json")
NOISE = load(TRAFFIC, "serve-chat.noise.json")
SIZING = {os.path.basename(p)[:-5]: load(p) for p in
          sorted(glob.glob(os.path.join(TRAFFIC, "serve-chat.sweeps-pr40",
                                        "*.json")))}
MANIFEST = load(ROOT, "BENCHMARK.json")
BOUNDS = {e["name"]: e["bound"] for e in MANIFEST["end_to_end"]}
CELL = "qwen2.5-3b.serve-chat"
TAILS = ("ttft_p80_ms", "tpot_p80_ms")
TABLES = {t["label"]: t for t in NOISE["tables"]}


# -------------------------------------------------------------- the spread


def test_the_spread_leaves_out_the_farthest_run_where_that_narrows_it():
    six = [235.5, 248.2, 265.6, 272.5, 228.0, 235.8]      # PERF.md, PR 36
    mid = statistics.median(six)
    assert noise.trimmed_spread(six) == pytest.approx((265.6 - 228.0) / mid)
    assert noise.trimmed_spread([10, 10, 10, 10, 10, 20]) == 0.0
    assert noise.trimmed_spread([1, 10, 10, 10, 10, 10]) == 0.0
    assert noise.trimmed_spread([9.0, 11.0]) == pytest.approx(0.2)


def test_the_check_s_spread_is_the_quartiles_of_a_set_less_its_farthest_run():
    six = [30.0, 10.0, 12.0, 14.0, 16.0, 18.0]       # median 15; 30 is left out
    q = statistics.quantiles([10.0, 12.0, 14.0, 16.0, 18.0], n=4)
    assert q[0] == 11.0 and q[2] == 17.0
    assert noise.driver_spread(six) == pytest.approx(6.0 / 15.0)
    assert noise.driver_spread([5.0] * 6) == 0.0
    # a far-off run widens the range-based spread and not this one
    assert noise.driver_spread(six) == noise.driver_spread([300.0] + six[1:])


def _row(seed, ttft, tpot, **over):
    return {"seed": seed, "ttft_p80_ms": ttft, "tpot_p80_ms": tpot,
            "failed": 0, "compiles_in_window": 0, "completed_share": 1.0,
            "backlog_mid": 0, "backlog_end": 0, **over}


def test_sets_are_cut_in_the_order_read_and_judged_each_by_its_limit():
    rows = [_row(i, 100.0 + i, 30.0) for i in range(6)] + \
        [_row(10 + i, 100.0, 30.0 + i) for i in range(6)] + [_row(99, 1, 1)]
    one, two = noise.sets_of(rows, TAILS)          # the thirteenth: no set
    assert one["seeds"] == list(range(6)) and two["seeds"] == list(range(10, 16))
    assert one["ttft_p80_ms"]["readings"] == [100.0 + i for i in range(6)]
    assert one["tpot_p80_ms"]["trimmed_spread"] == 0.0
    assert two["tpot_p80_ms"]["trimmed_spread"] == pytest.approx(4 / 32.5)
    limits = {"ttft_p80_ms": 0.04, "tpot_p80_ms": 0.03}
    assert noise.steady([one], limits) and not noise.steady([one, two], limits)
    assert not noise.steady([], limits)            # nothing served holds nothing


@pytest.mark.parametrize("fault", [
    {"failed": 1}, {"compiles_in_window": 2}, {"backlog_end": 3},
    {"completed_share": 0.9}, {"correct": False}, {"sustained": False}])
def test_a_window_that_is_not_whole_unsteadies_its_set(fault):
    rows = [_row(i, 100.0, 30.0) for i in range(5)] + \
        [_row(5, 100.0, 30.0, **fault)]
    sets = noise.sets_of(rows, TAILS)
    assert not sets[0]["whole"]
    assert not noise.steady(sets, {"ttft_p80_ms": 1.0, "tpot_p80_ms": 1.0})


def test_a_result_line_of_run_py_is_a_row_and_a_row_is_itself():
    line = {"correct": True, "attempted": 300, "failed": 0, "seed": 7,
            "metrics": {"ttft_p80_ms": {"value": 900.5, "unit": "ms"},
                        "tpot_p80_ms": {"value": 33.25, "unit": "ms"},
                        "setup_s": {"value": 36.5, "unit": "s"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 12 * 10 ** 9},
            "notes": {"completed_share": 1.0, "backlog_mid": 2,
                      "backlog_end": 1, "compiles_in_window": 0,
                      "out_tok_s": 800.0}}
    row = noise.row_of(line)
    assert row["ttft_p80_ms"] == 900.5 and row["tpot_p80_ms"] == 33.25
    assert row["seed"] == 7 and row["setup_s"] == 36.5 and noise.whole(row)
    assert noise.row_of(row) is row
    line["notes"]["backlog_end"] = 3
    assert not noise.whole(noise.row_of(line))


def test_a_table_keeps_the_rows_asked_for_and_passes_over_other_lines():
    lines = [{"knee": 6.0}, _row(1, 100.0, 30.0, rate=4.5),
             _row(2, 100.0, 30.0, rate=3.0), {"rate": 4.5, "sets": []}]
    table = noise.table_of(lines, "t", TAILS, where={"rate": 4.5},
                           about={"share": 0.75})
    assert [r["seed"] for r in table["rows"]] == [1]
    assert table["share"] == 0.75 and table["sets"] == []
    assert "steady" not in table


# ------------------------------------------- the cell against its own sweep


def test_the_rate_is_the_share_of_the_knee_its_own_sweep_found():
    knee = tg.find_knee(SWEEP["table"])
    assert knee is not None and SWEEP["knee"] == knee
    rate = tg.cell_rate(knee, SWEEP["share_of_knee"])
    assert SWEEP["rate"] == rate and CHAT["arrivals"]["rate"] == rate
    assert SWEEP["workload"] == CELL


def test_the_sweep_ran_the_engine_the_cell_runs():
    engine = {k: v for k, v in CHAT["engine"].items() if k != "sizing"}
    assert SWEEP["engine"] == engine
    assert SWEEP["seconds"] == MANIFEST["run_seconds"]


def test_the_slo_follows_its_rule():
    half = SWEEP["half_knee"]
    assert half["rate"] == round(SWEEP["knee"] / 2, 1) and half["sustained"]
    assert CHAT["slo"]["ttft_ms"] == round(2 * half["ttft_p50_ms"], -1)
    assert CHAT["slo"]["tpot_ms"] == round(2 * half["tpot_p50_ms"])


def test_the_window_carries_its_tail():
    n = tg.request_count(CHAT, MANIFEST["run_seconds"])
    assert tg.percentile_supported(n, CHAT["tail_percentile"])


# -------------------------------------- rule A: the sweeps of the settings


@pytest.mark.parametrize("name", sorted(SIZING))
def test_a_setting_s_knee_is_what_its_table_gives(name):
    doc = SIZING[name]
    assert doc["knee"] == tg.find_knee(doc["table"]) and doc["correct"]
    assert doc["workload"] == CELL and doc["seconds"] == NOISE["seconds"]
    assert doc["memory_peak_bytes"] <= 15.0e9


def test_the_engine_of_the_noise_tables_is_rule_a_s_winner():
    """The highest knee among the settings that count (no program compiled
    in a window); rows and blocks tie at chunk 16, so the chunk decides."""
    counts = {k: d for k, d in SIZING.items()
              if not any(r["compiles_in_window"] for r in d["table"])}
    assert sorted(set(SIZING) - set(counts)) == ["r48b400c64"]
    best = max(d["knee"] for d in counts.values())
    winners = [d["engine"] for d in counts.values() if d["knee"] == best]
    assert best == NOISE["knee"] and len(winners) == 1
    for key in ("max_batch", "num_cache_blocks", "split_fuse_chunk"):
        assert winners[0][key] == NOISE["engine"][key]


# ------------------------------------------------ rules B and C: the tables


@pytest.mark.parametrize("label", sorted(TABLES))
def test_a_table_says_what_its_rows_give(label):
    table = TABLES[label]
    again = noise.table_of(table["rows"], label, table.get("metrics", TAILS),
                           table["limits"])
    assert table["sets"] == again["sets"]
    assert table["steady"] == again["steady"]
    assert table["rate"] == tg.cell_rate(NOISE["knee"], table["share"])
    assert all(len(s["seeds"]) == noise.SET for s in table["sets"])


def test_no_seed_is_read_twice_by_two_kinds_of_run():
    served = {s for t in TABLES.values() if t["label"] != "X"
              for one in t["sets"] for s in one["seeds"]}
    alone = {s for one in TABLES["X"]["sets"] for s in one["seeds"]}
    assert len(alone) == 12 and not served & alone
    assert not {d["seed"] for d in SIZING.values()} & (served | alone)


def _held(rung, limits):
    """The shares at which a rung's tables hold two whole sets under `limits`."""
    return [t["share"] for t in TABLES.values() if t["rung"] == rung
            and len(t["sets"]) == 2 and noise.steady(t["sets"], limits)]


def test_the_ladder_ends_with_the_cell_left_as_it_is():
    """B at its three shares, then C1, C2 and C3 each at 0.75 x the knee:
    none holds, so the record names no rate and the cell keeps the one its
    own sweep gave it."""
    b, c3 = NOISE["limits"]["B"], NOISE["limits"]["C3"]
    assert sorted(t["share"] for t in TABLES.values() if t["rung"] == "B") \
        == [0.5, 0.6, 0.75]
    assert _held("B", b) == []
    assert 0.75 not in _held("C1", b) and 0.75 not in _held("C2", b)
    assert 0.75 not in _held("C2", c3)
    assert NOISE["verdict"]["chosen"] is None
    assert CHAT["arrivals"]["rate"] == SWEEP["rate"]
    assert CHAT["arrivals"].get("process", "poisson") == "poisson"


@pytest.mark.parametrize("name", TAILS)
def test_the_heavy_mix_spreads_a_tail_past_half_its_bound_at_every_share(name):
    """Why the cell could not be moved by rule B: on its own mix, at every
    share of the knee, a set of six reads over half the bound the manifest
    gives the metric (the driver's measure of a bound that is too tight)."""
    for t in TABLES.values():
        if t["rung"] == "B":
            assert max(s[name]["trimmed_spread"] for s in t["sets"]) \
                > BOUNDS[name] / 2, (t["label"], name)


# ------------------------------------------------ D: the cell as committed


@pytest.mark.parametrize("name", TAILS)
def test_the_cell_as_committed_spreads_a_tail_under_half_its_bound(name):
    """Two sets of six `run.py` processes on the same seeds, the second from
    `git archive` of the commit: by the check's own measure each set (and so
    their mean, which is what the check holds to half the bound) is under
    half of the manifest's bound, and the second median is within the bound
    of the first."""
    d = TABLES["D"]
    one, two = d["sets"]
    assert one["whole"] and two["whole"] and one["seeds"] == two["seeds"]
    assert d["rate"] == CHAT["arrivals"]["rate"] and "cycle" in d["mix"]
    for s in (one, two):
        assert s[name]["driver_spread"] <= BOUNDS[name] / 2
    assert abs(two[name]["median"] - one[name]["median"]) \
        <= BOUNDS[name] * one[name]["median"]


def test_one_seed_s_two_runs_lay_farther_apart_than_three_seeds_did():
    """F1, why the schedule and not only the sizes is one for every seed: on
    the tree the check refused, the run moved the tail as far as the seed."""
    rows = TABLES["F1"]["rows"]
    assert [r["seed"] for r in rows][:2] == [rows[0]["seed"]] * 2
    same = abs(rows[0]["ttft_p80_ms"] - rows[1]["ttft_p80_ms"])
    others = [r["ttft_p80_ms"] for r in rows[1:]]
    assert same > max(others) - min(others)
