"""`readers/program.py`: the program's spans and compile records read after a
run. Synthetic spans planted in the program's own span store, a hand-made
trace and the recorded serving excerpt; the command itself at toy sizes for
the list of metrics. A program without a store or records (any commit before
they were added) reads as None."""

import json
import os
import types

import pytest

from perfbench import trace as tm
from perfbench.manifest import Manifest

from .test_rehearsal import result_of, run_cell

M = Manifest()
DATA = os.path.join(os.path.dirname(__file__), "data")
SERVE = "qwen2.5-3b.serve-chat"
MS = 1e6
OFFSET_S = 1234.5          # perf_counter reads this much more than the trace
PROGRAM_METRICS = ["put_schedule_ms", "put_sync_ms", "put_dispatch_ms",
                   "put_fetch_ms", "put_commit_ms", "slot_fill.serve",
                   "setup_compile_s.serve", "setup_compile_count.serve"]


def read(name, ctx):
    decl = M.metric(name)
    return M.reader(decl["reader"])(ctx, **decl.get("params", {}))


def ctx_with(**kw):
    base = dict(samples={}, counters={}, spans={}, trace=None, trace_window=None,
                device={}, peaks=None, chips=1, t_start=0.0, seconds=0.0,
                workload={"name": SERVE})
    return types.SimpleNamespace(**{**base, **kw})


@pytest.fixture()
def store():
    from deepspeed_tpu.telemetry import get_span_store
    s = get_span_store()
    s.clear()
    yield s
    s.clear()


def plant(store, rnd, start_ms, phases, fed=9, slots=816, skew_ms=0.0):
    """One put round into the store: `schedule`, then a `chunk` whose children
    are `phases` [(name, ms), ...], from `start_ms` on the TRACE's clock."""
    t = OFFSET_S + (start_ms + skew_ms) / 1e3
    ids = iter(range(rnd * 100, rnd * 100 + 50))

    def add(name, dur_ms, parent=None, fields=None):
        nonlocal t
        store.add({"name": name, "t0": t, "t1": t + dur_ms / 1e3,
                   "id": next(ids), "parent": parent, "round": rnd,
                   "uids": (1,), "engine": "v2", "fields": fields or {}})
        t += dur_ms / 1e3

    add("schedule", 1.0)
    t0 = t
    for name, ms in phases:
        add(name, ms, parent=rnd * 100 + 49)
    store.add({"name": "chunk", "t0": t0, "t1": t, "id": rnd * 100 + 49,
               "parent": None, "round": rnd, "uids": (1,), "engine": "v2",
               "fields": {"tokens_fed": fed, "token_slots": slots}})


PHASES = [("feeds", 2.0), ("sync", 0.5), ("dispatch", 1.5), ("fetch", 14.0),
          ("commit", 1.0)]          # + schedule 1.0 = a 20 ms put


def toy_trace(rounds=6, period_ms=25.0):
    """`rounds` pb:round annotations of 20.2 ms, one every 25 ms; the device
    runs from 5.5 ms to 19 ms into each, so it idles under the host's
    schedule/feeds/sync/dispatch (5 ms), its commit (1 ms), and the 5 ms
    between rounds; the first 0.5 ms of every fetch is idle too."""
    host, ops = [["pb:traced", 0.0, rounds * period_ms * MS]], []
    for k in range(rounds):
        s = k * period_ms * MS
        host.append(["pb:round", s, 20.2 * MS])
        ops.append(["%fusion.1 = f32[] fusion()", s + 5.6 * MS, 13.5 * MS])
    return {"devices": {"0": {"ops": ops, "modules": []}}, "host": host}


def planted(store, rounds=6, period_ms=25.0, skews=None):
    for k in range(rounds):
        plant(store, k + 1, k * period_ms + 0.1, PHASES,
              skew_ms=(skews or [0.0] * rounds)[k])


# ------------------------------------------------------------- phase metrics
def test_phase_medians_and_slot_fill_over_the_judged_window(store):
    planted(store)
    plant(store, 7, 6 * 25.0 + 0.1, [(n, 10 * ms) for n, ms in PHASES],
          fed=800, slots=816)                      # after the window closes
    ctx = ctx_with(t_start=OFFSET_S - 10.0, counters={"setup_s": 10.0},
                   seconds=0.150)                  # rounds 1-6, not 7
    assert read("put_schedule_ms", ctx) == pytest.approx(3.0)   # head + feeds
    assert read("put_sync_ms", ctx) == pytest.approx(0.5)
    assert read("put_dispatch_ms", ctx) == pytest.approx(1.5)
    assert read("put_fetch_ms", ctx) == pytest.approx(14.0)
    assert read("put_commit_ms", ctx) == pytest.approx(1.0)
    assert read("slot_fill.serve", ctx) == pytest.approx(100 * 9 / 816)
    ctx.seconds = 0.4                              # now round 7 is judged too
    assert read("slot_fill.serve", ctx) == pytest.approx(
        100 * (6 * 9 + 800) / (7 * 816))


def test_setup_compiles_are_those_before_the_window_opens(monkeypatch):
    from deepspeed_tpu import telemetry
    recs = [{"t": 5.0, "seconds": 2.0, "fun_name": "a", "program": None},
            {"t": 9.0, "seconds": 0.5, "fun_name": "b", "program": "decode"},
            {"t": 30.0, "seconds": 7.0, "fun_name": "c", "program": None}]
    monkeypatch.setattr(telemetry, "compile_records", lambda: recs)
    ctx = ctx_with(t_start=1.0, counters={"setup_s": 10.0})
    for suffix in (".serve", ".gen", ".train"):
        assert read("setup_compile_s" + suffix, ctx) == pytest.approx(2.5)
        assert read("setup_compile_count" + suffix, ctx) == 2


def test_a_program_without_store_or_records_reads_as_nothing(monkeypatch):
    from deepspeed_tpu import telemetry
    monkeypatch.delattr(telemetry, "get_span_store")
    monkeypatch.delattr(telemetry, "compile_records")
    t = toy_trace()
    ctx = ctx_with(trace=t, trace_window=tm.trace_window(t),
                   counters={"setup_s": 1.0, "traced_rounds": 6})
    for name in PROGRAM_METRICS + ["idle_host_prepare_share.serve",
                                   "idle_host_collect_share.serve",
                                   "idle_unattributed_share.serve"]:
        assert read(name, ctx) is None, name


def test_no_trace_no_idle_share(store):
    planted(store)
    ctx = ctx_with(counters={"setup_s": 1.0})
    for under in ("prepare", "collect", "unattributed"):
        assert M.reader("program:idle_share")(ctx, under=under) is None


# ------------------------------------------------- idle time by host phase
def test_alignment_recovers_a_planted_offset(store):
    # every round's start is off by its own jitter; the median is not
    planted(store, skews=[0.0, 0.3, -0.2, 0.0, 0.1, -0.4])
    program = M.module("readers", "program")
    offset, pairs = program.align(toy_trace()["host"],
                                  program.rounds_of(store.spans()))
    assert len(pairs) == 6
    assert offset == pytest.approx(OFFSET_S * 1e9 + 0.1 * MS, abs=0.06 * MS)
    # more rounds in the store than the trace saw: paired from the last back
    store.clear()
    plant(store, 1, -500.0, PHASES)
    for k in range(6):
        plant(store, k + 2, k * 25.0 + 0.1, PHASES)
    offset, pairs = program.align(toy_trace()["host"],
                                  program.rounds_of(store.spans()))
    assert [r[0]["round"] for _, r in pairs] == [2, 3, 4, 5, 6, 7]
    assert offset == pytest.approx(OFFSET_S * 1e9 + 0.1 * MS)


def test_idle_shares_by_phase_sum_to_the_idle_share(store):
    planted(store)
    t = toy_trace()
    ctx = ctx_with(trace=t, trace_window=tm.trace_window(t),
                   counters={"traced_rounds": 6})
    prepare = read("idle_host_prepare_share.serve", ctx)
    collect = read("idle_host_collect_share.serve", ctx)
    rest = read("idle_unattributed_share.serve", ctx)
    # per 25 ms round the device idles 5 ms under schedule..dispatch, 0.5 ms
    # at the head of fetch, 1 ms under commit and 5 ms from the end of one
    # put to the start of the next; before the first put (0.1 ms) and after
    # the last (4.9 ms, to the window's end) no span covers it
    assert prepare == pytest.approx(100 * 6 * 5.0 / 150.0)
    assert collect == pytest.approx(100 * (6 * 1.5 + 5 * 5.0) / 150.0)
    assert rest == pytest.approx(100 * 5.0 / 150.0)
    assert prepare + collect + rest == pytest.approx(
        read("idle_share.serve", ctx))


def test_a_bad_alignment_shows_as_unattributed_not_as_attribution(store):
    # the trace lost the fourth round's annotation and holds a later one:
    # half the pairs are one round off, so no single offset fits, and every
    # span is clipped to a pb:round that is only partly its own
    planted(store)
    t = toy_trace(rounds=7)
    t["host"] = [e for e in t["host"] if e[1] != 3 * 25.0 * MS]
    ctx = ctx_with(trace=t, trace_window=tm.trace_window(t))
    assert read("idle_unattributed_share.serve", ctx) > 5.0
    total = sum(read(n, ctx) for n in ("idle_host_prepare_share.serve",
                                       "idle_host_collect_share.serve",
                                       "idle_unattributed_share.serve"))
    assert total == pytest.approx(read("idle_share.serve", ctx))


def test_on_the_recorded_excerpt_the_three_sum_to_the_idle_share(store):
    """The recorded serving trace (one 824 ms round of the traced segment)
    with program spans planted inside its pb:round."""
    with open(os.path.join(DATA, "serve_trace_excerpt.json")) as f:
        t = json.load(f)
    (mark,) = [e for e in t["host"] if e[0] == "pb:round"]
    plant(store, 1, mark[1] / MS + 0.05,
          [("feeds", 1.0), ("sync", 0.3), ("dispatch", 2.0),
           ("fetch", mark[2] / MS - 6.0), ("commit", 1.0)])
    ctx = ctx_with(trace=t, trace_window=tm.trace_window(t))
    parts = [read(n, ctx) for n in ("idle_host_prepare_share.serve",
                                    "idle_host_collect_share.serve",
                                    "idle_unattributed_share.serve")]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(read("idle_share.serve", ctx))
    assert parts[0] > 0        # the device waits while the host prepares


# ------------------------------------------------------- the new kernel names
def test_kernel_metrics_read_the_new_names_and_the_old_still_match():
    ops = [["%self_attn_paged_decode.3 = bf16[] custom-call()", 0, 2 * MS],
           ["%self_attn_paged_prefill.7 = bf16[] custom-call()", 2 * MS, 6 * MS],
           ["%self_attn_dense_decode.1 = bf16[] custom-call()", 8 * MS, 1 * MS],
           ["%self_attn_flash_fwd.2 = bf16[] custom-call()", 9 * MS, 1 * MS],
           ["%self_attn_flash_bwd.5 = bf16[] custom-call()", 10 * MS, 3 * MS],
           ["%fusion.9 = bf16[] fusion()", 13 * MS, 3 * MS]]
    t = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx = ctx_with(trace=t, trace_window=(0.0, 16 * MS),
                   counters={"traced_rounds": 2, "traced_decode_steps": 4})
    assert read("paged_decode_ms", ctx) == pytest.approx(1.0)
    assert read("paged_prefill_ms", ctx) == pytest.approx(3.0)
    assert read("dense_decode_attn_ms.gen", ctx) == pytest.approx(0.25)
    assert read("flash_attn_share.x4", ctx) == pytest.approx(25.0)
    # what PR 23's metrics search for is a prefix of every new name
    assert read("paged_attn_ms", ctx) == pytest.approx(13.0 / 2)
    assert read("flash_attn_share.train", ctx) == pytest.approx(100 * 13 / 16)


# ------------------------------------------------------------ the command
def test_traced_rehearsal_lists_every_new_program_metric():
    rc, lines, err = run_cell("--workload", SERVE, "--seed", "2147483659",
                              "--seconds", "3", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-2000:]
    got = result_of(lines)["rehearsal_metrics"]
    assert set(PROGRAM_METRICS) <= set(got)
    phases = sum(got[n]["value"] for n in PROGRAM_METRICS[:5])
    assert 0 < phases <= got["round_p50_ms"]["value"] * 1.05
    assert 0 < got["slot_fill.serve"]["value"] <= 100
    assert got["setup_compile_count.serve"]["value"] >= 4   # the four programs
    # nothing ran on a device: no idle share and no kernel time is reported
    assert not [n for n in got if n.startswith("idle_") or "paged" in n]


@pytest.mark.parametrize("cell,suffix", [
    ("qwen2.5-0.5b.train-2k", ".train"), ("qwen2.5-3b.generate-batch", ".gen")])
def test_setup_metrics_in_the_other_kinds_of_cell(cell, suffix):
    rc, lines, err = run_cell("--workload", cell, "--seed", "5", "--seconds",
                              "1", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-2000:]
    got = result_of(lines)["rehearsal_metrics"]
    assert got["setup_compile_s" + suffix]["value"] > 0
    assert got["setup_compile_count" + suffix]["value"] >= 1
