"""`readers/scopes.py` and `readers/live_share.py`: the metrics that read the
program map, each on a small recorded PAIR (`data/scope_pairs/<cell>.json`:
1,500 op events of a traced window on the chip in the neutral form, the
module events over them, the window, the traced counters, and the rows of
the program's map that those events name; written by the reader itself
under PERFBENCH_DUMP). A tree without the map, a run without a trace: None.
"""

import json
import os
import types

import pytest

from perfbench import trace as tm
from perfbench.manifest import Manifest, problems

M = Manifest()
PAIRS = os.path.join(os.path.dirname(__file__), "data", "scope_pairs")
CELLS = sorted(f[:-5] for f in os.listdir(PAIRS) if f.endswith(".json"))
SCOPE_METRICS = [e for e in M.doc["per_layer"]
                 if M.metric(e["name"])["reader"].startswith("scopes:")]
CASES = [(e["name"], cell) for e in SCOPE_METRICS for cell in CELLS
         if cell in e["workloads"]]


def read(name, ctx):
    decl = M.metric(name)
    return M.reader(decl["reader"])(ctx, **decl.get("params", {}))


def load(cell):
    with open(os.path.join(PAIRS, cell + ".json")) as f:
        return json.load(f)


def ctx_of(cell, pair, **over):
    trace = pair["trace"]
    base = dict(trace=trace, trace_window=tm.trace_window(trace),
                counters=dict(pair["counters"]), workload={"name": cell},
                samples={}, spans={}, device={}, peaks=None, chips=1,
                t_start=0.0, seconds=0.0)
    return types.SimpleNamespace(**{**base, **over})


@pytest.fixture()
def planted(monkeypatch):
    """A context of `cell`'s pair with the pair's map standing in for the
    program's own (`telemetry.program_map`)."""
    from deepspeed_tpu import telemetry

    def make(cell, **over):
        pair = load(cell)
        monkeypatch.setattr(telemetry, "program_map", lambda: pair["maps"])
        return ctx_of(cell, pair, **over), pair
    return make


def test_the_manifest_with_the_new_entries_may_be_sent():
    assert problems(M) == []
    assert len(SCOPE_METRICS) == 17
    names = {e["name"] for e in M.doc["per_layer"]}
    assert {"kv_blocks_live_share.serve", "dense_slots_live_share.gen"} <= names
    # every cell reports how much of its busy time the map could not name
    for w in M.doc["workloads"]:
        assert [e["name"] for e in M.metrics_for(w["name"], "per_layer")
                if e["name"].startswith("scope_unmatched_share.")], w["name"]


def test_every_kind_of_cell_has_a_recorded_pair():
    kinds = {M.traffic(M.workload(c)["traffic"])["kind"] for c in CELLS}
    assert kinds == {"train", "serve", "generate"}


@pytest.mark.parametrize("name,cell", CASES,
                         ids=[f"{n}-{c.split('.')[-1]}" for n, c in CASES])
def test_a_metric_reads_its_pair(planted, name, cell):
    ctx, pair = planted(cell)
    value = read(name, ctx)
    assert value is not None and value >= 0.0
    entry = next(e for e in SCOPE_METRICS if e["name"] == name)
    if entry["unit"] == "%":
        assert value <= 100.0 + 1e-9
    else:   # ms per unit: never more than the excerpt's whole busy time
        from deepspeed_tpu.telemetry import by_scope
        dev = tm.first_device(ctx.trace)
        busy = by_scope(dev["ops"], dev["modules"], ctx.trace_window,
                        maps=pair["maps"])["busy_s"]
        per = ctx.counters[M.metric(name)["params"]["per"]]
        assert value <= 1e3 * busy / per + 1e-9


@pytest.mark.parametrize("cell", CELLS)
def test_the_join_names_the_recorded_events(planted, cell):
    """The profiler's instruction names ARE the compiled text's: of the
    pair's busy time next to nothing is unmatched, and the seconds by row
    sum to the union of the op intervals (the benchmark's own busy time)."""
    from perfbench.readers import scopes
    ctx, _ = planted(cell)
    joined = scopes.joined(ctx)
    ops = tm.first_device(ctx.trace)["ops"]
    union = tm.length(tm.union(tm.spans_of(ops, ctx.trace_window))) / 1e9
    assert joined["busy_s"] == pytest.approx(union, rel=1e-6)
    assert scopes.unmatched_share(ctx) < 1.0
    # the same seconds as the benchmark's own reduction gives by name
    by_name = tm.self_times(ops, ctx.trace_window)
    assert sum(by_name.values()) == pytest.approx(joined["busy_s"], rel=1e-6)


def test_a_train_steps_scopes_tile_its_busy_time(planted):
    from perfbench.readers import scopes
    cell = next(c for c in CELLS if "train" in c)
    ctx, _ = planted(cell)
    parts = [scopes.seconds(ctx, **{k: v for k, v in
                                    M.metric(n)["params"].items()
                                    if k != "per"})
             for n in ("fwd_ms.train", "bwd_ms.train", "loss_ms.train",
                       "optimizer_ms.train")]
    rest = scopes.seconds(ctx, scope="grad_accumulate") \
        + scopes.seconds(ctx, phase="none",
                         not_scope=["chunked_ce", "optimizer",
                                    "grad_accumulate"])
    joined = scopes.joined(ctx)
    assert sum(parts) + rest + sum(joined["unmatched"].values()) == \
        pytest.approx(joined["busy_s"], rel=1e-9)
    assert all(p > 0 for p in parts[:2])


def test_prefill_and_decode_partition_a_generate(planted):
    from perfbench.readers import scopes
    cell = next(c for c in CELLS if "generate" in c)
    ctx, _ = planted(cell)
    prefill = scopes.share_of_busy(ctx, scope="prefill")
    decode = scopes.share_of_busy(ctx, scope="decode")
    both = scopes.share_of_busy(ctx, scope=["prefill", "decode"])
    assert both == 0.0 and prefill > 0 and decode > 0
    assert prefill + decode + scopes.unmatched_share(ctx) <= 100.0 + 1e-9
    # the rest: the cache's zero fill and the casts above the prefill
    assert prefill + decode > 90.0


@pytest.mark.parametrize("name,cell", CASES[:1] + CASES[-1:])
def test_nothing_to_read_is_none_never_an_error(planted, monkeypatch, name,
                                                cell):
    from deepspeed_tpu import telemetry
    ctx, pair = planted(cell)
    # no trace (a run without --trace 1, a rehearsal on a CPU)
    assert read(name, ctx_of(cell, pair, trace=None, trace_window=None)) \
        is None
    assert read(name, ctx_of(cell, pair, trace={"devices": {}, "host": []},
                             trace_window=None)) is None
    # a program that kept nothing, or whose text went stale
    monkeypatch.setattr(telemetry, "program_map", lambda: {})
    assert read(name, ctx_of(cell, pair)) is None
    stale = {m: {**d, "rows": [], "stale": True}
             for m, d in pair["maps"].items()}
    monkeypatch.setattr(telemetry, "program_map", lambda: stale)
    assert read(name, ctx_of(cell, pair)) is None
    # a tree without the map: any commit before it was added
    monkeypatch.delattr(telemetry, "program_map")
    assert read(name, ctx_of(cell, pair)) is None
    monkeypatch.undo()
    monkeypatch.delattr(telemetry, "by_scope")
    assert read(name, ctx_of(cell, pair)) is None


def test_a_counter_of_zero_is_no_number(planted):
    cell = next(c for c in CELLS if "train" in c)
    ctx, _ = planted(cell, counters={"traced_steps": 0})
    assert read("fwd_ms.train", ctx) is None
    assert read("scope_unmatched_share.train", ctx) is not None


# --------------------------------------------- what a kernel walked of its cache


def test_kv_blocks_live_share_sums_the_windows_rounds(monkeypatch):
    from deepspeed_tpu.telemetry import get_span_store
    store = get_span_store()
    store.clear()

    def add(rnd, t0, name, **fields):
        store.add({"name": name, "t0": t0, "t1": t0 + 0.01, "id": rnd * 10,
                   "parent": None, "round": rnd, "uids": None, "engine": "v2",
                   "fields": fields})
    add(1, 5.0, "decode", kv_blocks_live=10, kv_blocks_table=100)   # set-up
    add(2, 11.0, "decode", kv_blocks_live=30, kv_blocks_table=100)
    add(3, 12.0, "chunk", kv_blocks_live=50, kv_blocks_table=100)
    add(4, 13.0, "prefill", token_slots=8)          # carries neither
    ctx = types.SimpleNamespace(t_start=0.0, seconds=10.0,
                                counters={"setup_s": 10.0})
    try:
        assert read("kv_blocks_live_share.serve", ctx) == pytest.approx(40.0)
        ctx.counters = {}
        assert read("kv_blocks_live_share.serve", ctx) is None
    finally:
        store.clear()


def test_dense_slots_live_share_reads_the_hubs_gauges(monkeypatch):
    from deepspeed_tpu.telemetry import TelemetryHub, get_hub
    from deepspeed_tpu.telemetry.hub import set_hub
    was = get_hub()
    set_hub(TelemetryHub(enabled=False))
    try:
        ctx = types.SimpleNamespace()
        assert read("dense_slots_live_share.gen", ctx) is None
        get_hub().gauge("serving_v1/dense_kv_slots_live", 300)
        get_hub().gauge("serving_v1/dense_kv_slots_fetched", 400)
        assert read("dense_slots_live_share.gen", ctx) == pytest.approx(75.0)
    finally:
        set_hub(was)
