"""`readers/setup.py`: where set-up went, from the program's own records.
Planted records and spans give exact numbers; the four metrics and
`setup_compile_s.*` take disjoint parts of the window whatever nests or
overlaps; a program without the records reads as None; the command itself at
toy sizes lists the metrics of each kind of cell."""

import json
import types

import pytest

from perfbench.manifest import Manifest

from .test_rehearsal import result_of, run_cell

M = Manifest()
SUFFIXES = (".serve", ".gen", ".train")
NEW = ("setup_trace_lower_s", "setup_cache_miss_s", "setup_engine_init_s",
       "setup_unattributed_s")
T0 = 1000.0                 # `ctx.t_start` on perf_counter
SETUP = 40.0                # the window opens at 1040


def read(name, ctx):
    decl = M.metric(name)
    return M.reader(decl["reader"])(ctx, **decl.get("params", {}))


def ctx_with(**kw):
    base = dict(samples={}, counters={"setup_s": SETUP}, spans={}, trace=None,
                trace_window=None, device={}, peaks=None, chips=1, t_start=T0,
                seconds=0.0, workload={"name": "qwen2.5-3b.serve-chat"})
    return types.SimpleNamespace(**{**base, **kw})


def rec(kind, start, seconds, program=None, fun_name="f", **more):
    """A record whose own interval is [T0 + start, T0 + start + seconds]."""
    return {"kind": kind, "t": T0 + start + seconds, "seconds": seconds,
            "fun_name": fun_name, "program": program, **more}


def span(name, start, seconds, ident, parent=None, engine="v2", **fields):
    return {"name": name, "t0": T0 + start, "t1": T0 + start + seconds,
            "id": ident, "parent": parent, "depth": 0 if parent is None else 1,
            "round": None, "uids": None, "engine": engine, "fields": fields}


@pytest.fixture()
def program(monkeypatch):
    """`plant(records, spans)`: the program's records and span store, as the
    readers reach them."""
    from deepspeed_tpu import telemetry

    def plant(records, spans):
        def compile_records(kinds=("backend_compile",)):
            return sorted((r for r in records if r["kind"] in kinds),
                          key=lambda r: r["t"])
        store = types.SimpleNamespace(spans=lambda: list(spans))
        monkeypatch.setattr(telemetry, "compile_records", compile_records)
        monkeypatch.setattr(telemetry, "get_span_store", lambda: store)
    return plant


def all_five(ctx, suffix=".serve"):
    got = {n: read(n + suffix, ctx) for n in NEW}
    got["setup_compile_s"] = read("setup_compile_s" + suffix, ctx)
    return got


# One set-up of 40 s, every part a whole number of seconds:
#  0-2   import                                       (engine_init 2)
#  2-5   nothing recorded: weights from the seed      (unattributed 3)
#  5-9   init: plan 5-6, place_params 6-9             (engine_init 4)
#  9-12  the reference: trace 9-10, lower 10-10.5, compile 10.5-12 (miss)
# 12-20  compile span `prefill:32`: pin_layouts 12-15 holding a trace 12-13
#        and an uncached compile 13-14 (engine_init 1); first dispatch 15-20
#        holding a trace 15-17 with an inner trace 15.5-16.5, a lower 17-18,
#        a compile 18-18.5 (hit), then its run 18.5-20 (in no metric)
# 20-30  nothing recorded: warm-up rounds             (unattributed 10)
# 30-35  compile span `decode`: trace 30-31, compile 31-33 (miss), run 33-35
# 35-40  nothing recorded: the ramp                   (unattributed 5)
# 41-43  a compile after the window opened            (in nothing)
RECORDS = [
    rec("trace", 9, 1, fun_name="reference"),
    rec("lower", 10, 0.5, fun_name="jit(reference)"),
    rec("backend_compile", 10.5, 1.5, fun_name="jit(reference)", cache="miss"),
    rec("trace", 12, 1, "prefill:32"),
    rec("backend_compile", 13, 1, "prefill:32", cache="uncached"),
    rec("trace", 15, 2, "prefill:32", fun_name="ds_v2_prefill"),
    rec("trace", 15.5, 1, "prefill:32", fun_name="inner"),
    rec("lower", 17, 1, "prefill:32"),
    rec("backend_compile", 18, 0.5, "prefill:32", cache="hit",
        retrieval_s=0.4, saved_s=7.0),
    rec("trace", 30, 1, "decode"),
    rec("backend_compile", 31, 2, "decode", cache="miss"),
    rec("backend_compile", 41, 2, "late", cache="miss"),
]
SPANS = [
    span("import", 0, 2, 1, engine=None),
    span("plan", 5, 1, 3, parent=2), span("place_params", 6, 3, 4, parent=2),
    span("init", 5, 4, 2),
    span("compile", 12, 3, 5, program="prefill:32", phase="pin_layouts"),
    span("compile", 15, 5, 6, program="prefill:32", phase="first_dispatch"),
    span("compile", 30, 5, 7, program="decode", phase="first_dispatch"),
]


def test_planted_records_give_exact_numbers(program):
    program(RECORDS, SPANS)
    for suffix in SUFFIXES:
        got = all_five(ctx_with(), suffix)
        assert got["setup_trace_lower_s"] == pytest.approx(1.5 + 1 + 3 + 1)
        assert got["setup_compile_s"] == pytest.approx(1.5 + 1 + 0.5 + 2)
        assert got["setup_cache_miss_s"] == pytest.approx(1.5 + 1 + 2)
        assert got["setup_engine_init_s"] == pytest.approx(2 + 4 + 1)
        assert got["setup_unattributed_s"] == pytest.approx(3 + 10 + 5)
        # the rest of the first dispatches is what the sum lacks
        assert SETUP - sum(got[n] for n in got if n != "setup_cache_miss_s") \
            == pytest.approx(1.5 + 2)


def test_the_window_cuts_what_straddles_its_edges(program):
    records = [rec("trace", -1, 3), rec("trace", 38, 4),
               rec("backend_compile", 39, 0.5, cache="miss"),
               rec("backend_compile", 39.8, 0.5, cache="miss")]
    program(records, [span("import", -0.5, 1.5, 1, engine=None),
                      span("init", 36, 6, 2)])
    got = all_five(ctx_with())
    # trace 0-2 and 38-40 less the compiles inside it (39-39.5, 39.8-40)
    assert got["setup_trace_lower_s"] == pytest.approx(2 + 2 - 0.5 - 0.2)
    # whole records by their END, as `setup_compile_s` selects them
    assert got["setup_compile_s"] == got["setup_cache_miss_s"] == 0.5
    assert got["setup_engine_init_s"] == pytest.approx(2.0)     # 36-38
    assert got["setup_unattributed_s"] == pytest.approx(34.0)   # 2-36


NESTED = {
    "a trace inside a trace inside a trace": (
        [rec("trace", 1, 10), rec("trace", 2, 6), rec("trace", 3, 1)], []),
    "traces and lowerings that overlap": (
        [rec("trace", 1, 4), rec("lower", 3, 4), rec("trace", 6, 5),
         rec("lower", 6, 5)], []),
    "a compile inside a trace inside an init": (
        [rec("trace", 2, 6), rec("backend_compile", 3, 2, cache="miss"),
         rec("lower", 8, 1)], [span("import", 0, 1, 1), span("init", 1, 10, 2)]),
    "an engine built inside an engine": (
        [rec("trace", 3, 1)],
        [span("import", 0, 1, 1), span("init", 2, 8, 2),
         span("init", 3, 4, 3, parent=2),
         span("compile", 4, 2, 4, program="p", phase="first_dispatch")]),
    "a compile span inside a compile span": (
        [rec("trace", 11, 1, "a"), rec("backend_compile", 12, 3, "b",
                                       cache="hit")],
        [span("import", 0, 1, 1), span("init", 1, 1, 2),
         span("compile", 10, 10, 3, program="a", phase="first_dispatch"),
         span("compile", 11, 5, 4, program="b", phase="pin_layouts")]),
    "everything covered twice": (
        [rec("trace", 0, 40), rec("lower", 0, 40), rec("trace", 5, 30)],
        [span("import", 0, 40, 1), span("init", 0, 40, 2),
         span("compile", 0, 40, 3, program="p", phase="first_dispatch")]),
    "nothing but the import": ([], [span("import", 0, 1, 1)]),
}


@pytest.mark.parametrize("case", sorted(NESTED))
def test_the_parts_never_sum_past_setup_whatever_nests(case, program):
    records, spans = NESTED[case]
    if not any(s["name"] == "import" for s in spans):
        spans = spans + [span("import", 0, 0.5, 99)]
    program(records, spans)
    got = all_five(ctx_with())
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["setup_cache_miss_s"] <= got["setup_compile_s"] + 1e-9
    parts = sum(got[n] for n in got if n != "setup_cache_miss_s")
    assert parts <= SETUP + 1e-9, got


def test_a_program_without_the_records_reads_as_none(monkeypatch, program):
    from deepspeed_tpu import telemetry
    ctx = ctx_with()
    # the parent's `compile_records` takes no argument and tells no kind
    old = [{"t": T0 + 5, "seconds": 2.0, "fun_name": "a", "program": None}]
    program([], SPANS)
    monkeypatch.setattr(telemetry, "compile_records", lambda: old)
    assert all(read(n + s, ctx) is None for n in NEW for s in SUFFIXES)
    assert read("setup_compile_s.serve", ctx) == 2.0      # as it always was
    # records of every kind, and a store that holds no `import` or `init`
    program(RECORDS, [s for s in SPANS if s["name"] == "compile"])
    assert all(read(n + s, ctx) is None for n in NEW for s in SUFFIXES)
    # a run that never opened its window
    program(RECORDS, SPANS)
    assert all(read(n + ".serve", ctx_with(counters={})) is None for n in NEW)
    # neither a store nor records
    monkeypatch.delattr(telemetry, "get_span_store")
    monkeypatch.delattr(telemetry, "compile_records")
    assert all(read(n + s, ctx) is None for n in NEW for s in SUFFIXES)


def test_the_dump_lists_one_row_a_program(program, monkeypatch, tmp_path):
    program(RECORDS, SPANS)
    monkeypatch.setenv("PERFBENCH_DUMP", str(tmp_path))
    read("setup_unattributed_s.serve", ctx_with())
    doc = json.load(open(tmp_path / "qwen2.5-3b.serve-chat.setup.json"))
    assert doc["setup_s"] == SETUP and doc["import_s"] == 2
    assert doc["cache"] == {"hit": [1, 0.5], "miss": [2, 3.5],
                            "uncached": [1, 1.0]}
    (init,) = doc["init"]
    assert [(c["name"], c["seconds"]) for c in init["children"]] == [
        ("plan", 1), ("place_params", 3)]
    rows = {r["program"]: r for r in doc["programs"]}
    assert [r["program"] for r in doc["programs"]] == [
        "prefill:32", "decode", "reference"]    # by seconds; `late` is not set-up
    first = rows["prefill:32"]
    assert first["first_dispatch_s"] == 8 and first["seconds"] == 8
    assert first["trace_s"] == 3 and first["lower_s"] == 1    # inner: once
    assert first["backend_compile_s"] == 1.5
    assert first["backend_compiles"] == 2
    assert first["cache"] == "hit,uncached"
    assert [(g["from_s"], g["seconds"], g["follows"], g["precedes"])
            for g in doc["gaps"]] == [
        (20, 10, "compile prefill:32", "compile decode"),
        (35, 5, "compile decode", "the window's opening"),
        (2, 3, "import", "init v2")]
    ref = rows["reference"]                   # `jit(f)` and `f` are one row
    assert (ref["trace_s"], ref["lower_s"], ref["backend_compile_s"]) == (
        1, 0.5, 1.5)
    assert ref["first_dispatch_s"] is None and ref["cache"] == "miss"


@pytest.mark.parametrize("cell,suffix", [
    ("qwen2.5-3b.serve-chat", ".serve"),
    ("qwen2.5-3b.generate-batch", ".gen"),
    ("qwen2.5-0.5b.train-2k", ".train")])
def test_traced_rehearsal_lists_the_new_metrics(cell, suffix):
    rc, lines, err = run_cell("--workload", cell, "--seed", "7", "--seconds",
                              "2", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-2000:]
    got = result_of(lines)["rehearsal_metrics"]
    assert {n + suffix for n in NEW} <= set(got)
    v = {n: got[n + suffix]["value"] for n in NEW}
    assert all(x >= 0 for x in v.values())
    assert v["setup_cache_miss_s"] <= \
        got["setup_compile_s" + suffix]["value"] + 1e-9
