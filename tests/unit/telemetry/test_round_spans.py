"""The phases of a v2 `put` round, the counts made where the work happens,
the span store that outlives the requests, and set-up by program.

Host-only arithmetic first (fake clock, no jax program). Then ONE scripted
`put` sequence on a tiny engine, run once per module with the tracer on and
once with it off; the tests read what those two runs left behind.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry import (TelemetryHub, compile_records,
                                     compile_span, get_span_store)
from deepspeed_tpu.telemetry.hub import get_hub, set_hub
from deepspeed_tpu.telemetry.spans import RequestTracer, SpanStore
from deepspeed_tpu.utils import groups

from .test_spans import FakeClock

PHASES = ["feeds", "sync", "dispatch", "fetch", "commit"]


@pytest.fixture()
def store():
    s = get_span_store()
    s.clear()
    yield s
    s.clear()


def _tracer(force=True):
    clk = FakeClock()
    return RequestTracer(engine="test", clock=clk, force=force), clk


# ------------------------------------------------------------ host-only
def test_spans_carry_id_parent_and_round(store):
    tr, clk = _tracer()
    tr.round = 7
    with tr.span("chunk", uids=(1,)):
        clk.t += 1.0
        with tr.span("inner"):
            clk.t += 1.0
    tr.round = None
    with tr.span("flush"):
        clk.t += 0.5
    inner, chunk, flush = store.spans()
    assert [s["name"] for s in (inner, chunk, flush)] == ["inner", "chunk",
                                                         "flush"]
    assert inner["parent"] == chunk["id"] and chunk["parent"] is None
    assert len({inner["id"], chunk["id"], flush["id"]}) == 3
    assert inner["round"] == chunk["round"] == 7 and flush["round"] is None
    assert chunk["uids"] == (1,) and chunk["engine"] == "test"


def test_phases_follow_each_other_and_tile_their_parent(store):
    tr, clk = _tracer()
    with tr.span("decode", uids=(3,)):
        for name, dt in zip(PHASES, (1, 2, 3, 4, 5)):
            fields = tr.phase(name)
            fields["n"] = dt
            clk.t += dt
    *kids, parent = store.spans()
    assert [k["name"] for k in kids] == PHASES
    assert all(k["parent"] == parent["id"] and k["uids"] == (3,)
               for k in kids)
    assert [k["fields"]["n"] for k in kids] == [1, 2, 3, 4, 5]
    # each child starts where the one before it ended; none is left over
    assert kids[0]["t0"] == parent["t0"] and kids[-1]["t1"] == parent["t1"]
    assert all(a["t1"] == b["t0"] for a, b in zip(kids, kids[1:]))
    assert sum(k["t1"] - k["t0"] for k in kids) == parent["t1"] - parent["t0"]


def test_an_exception_closes_the_open_phase(store):
    tr, clk = _tracer()
    with pytest.raises(RuntimeError):
        with tr.span("chunk"):
            tr.phase("dispatch")
            clk.t += 1.0
            raise RuntimeError("boom")
    assert [s["name"] for s in store.spans()] == ["dispatch", "chunk"]
    assert tr.current() == (None, None)


def test_store_is_on_the_tracers_clock_and_outlives_the_requests(store):
    tr, clk = _tracer()
    clk.t += 5.0                        # the tracer's epoch is 100.0
    tr.begin_request(1)
    with tr.span("prefill", uids=(1,)):
        clk.t += 2.0
    tr.end_request(1, new_tokens=1)     # prunes the tracer's own intervals
    (s,) = store.spans()
    assert (s["t0"], s["t1"]) == (105.0, 107.0)       # not from the epoch
    assert store.spans(t0=106.0) == [] and store.spans(t1=107.0) == [s]


def test_store_is_bounded():
    s = SpanStore(cap=3)
    for i in range(5):
        s.add({"t0": i, "t1": i + 1})
    assert [r["t0"] for r in s.spans()] == [2, 3, 4] and len(s) == 3


def test_inactive_tracer_stores_nothing(store):
    set_hub(TelemetryHub(enabled=False))
    tr, _ = _tracer(force=False)
    with tr.span("chunk", uids=(1,)):
        pass
    assert len(store) == 0 and tr.spans_recorded == 0


def test_compile_span_counts_the_backend_compiles_inside_it(store, tmp_path):
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    try:
        before = len(compile_records())
        with compile_span("toy:program", "v2", phase="pin_layouts"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
        (s,) = store.spans()
        assert s["name"] == "compile" and s["engine"] == "v2"
        assert s["fields"]["program"] == "toy:program"
        assert s["fields"]["phase"] == "pin_layouts"
        assert s["fields"]["backend_compiles"] >= 1
        assert 0 < s["fields"]["backend_compile_s"] <= s["t1"] - s["t0"]
        new = compile_records()[before:]
        assert new and all(r["program"] == "toy:program" for r in new)
        assert get_hub().counters["compiles_total"] >= 1
        assert get_hub().counters["compile_seconds_total"] > 0
        (ev,) = [json.loads(l) for l in open(path)]
        assert ev["kind"] == "compile" and ev["program"] == "toy:program"
    finally:
        set_hub(TelemetryHub(enabled=False))


# ------------------------------------------------- one scripted put sequence
MAX_BATCH, CHUNK = 4, 8
P_SHORT = [5, 6, 7, 8, 9]                 # 5 tokens: the lone bucketed prefill
P_LONG = list(range(10, 78))              # 68 tokens: chunks of 8 x 8 and a 4
# A chunk round is FILLED: the one prompt prefilling takes all four rows of
# the width, 32 tokens a round, and its last four tokens one row.
# round: token slots computed, tokens fed
#  1 prefill bucket 32                      32, 5
#  2 decode, 4 rows                          4, 1
#  3 fused: 4 x 8 chunk slots + 4 rows      36, 32 + 1
#  4 fused                                  36, 32 + 1
#  5 chunk alone                            32, 4
SLOTS, FED, ROUNDS = 140, 76, 5
# round: rows of the program, of which live (cursor below capacity)
#  2 decode                                  4, 1 (uid 1)
#  3 fused: 4 chunk rows + 4 decode rows     8, 4 chunk + 1: uid 2 joins in
#                                               this round, and its cursor is
#                                               still parked in the decode
#                                               half, which runs first
#  4 fused                                   8, 4 chunk + 2: uid 2 rides the
#                                               decode half mid-prefill
#  5 chunk alone                             4, 1
ROWS = [(4, 1), (8, 5), (8, 6), (4, 1)]
PARKED = sum(rows - live for rows, live in ROWS)
# rounds with a decode half: (row, block) pairs that hold tokens under its
# rows, of the 4 x 8 entries of their block tables (blocks of 16 tokens)
#  2 decode                                  uid 1 at 5 tokens: 1
#  3 fused                                   uid 1 at 6: 1 (uid 2 still parked)
#  4 fused                                   uid 1 at 7: 1, uid 2 at 32: 2
BLOCKS = [(1, 32), (1, 32), (3, 32)]
# chunk rounds: rows that carry tokens, the prompts they belong to, the width
CHUNK_ROWS = [(4, 1, 4), (4, 1, 4), (1, 1, 4)]
REFILLED = sum(rows - seqs for rows, seqs, _ in CHUNK_ROWS)


def _script(model, params, traced, profile_dir=None):
    groups.reset_topology()
    eng = InferenceEngineV2(model, params=params, max_batch=MAX_BATCH,
                            max_seq_len=128, split_fuse_chunk=CHUNK,
                            cache_block_size=16, prefix_sharing=False)
    eng.tracer.force = traced
    if profile_dir:
        jax.profiler.start_trace(str(profile_dir))
    outs = []
    out = eng.put([1], [np.asarray(P_SHORT, np.int32)], argmax_only=True)
    outs.append(dict(out))
    for feed in ([1], [1, 2], [1], []):
        toks = [[int(out[1])] if u == 1 else np.asarray(P_LONG, np.int32)
                for u in feed]
        new = eng.put(feed, toks, argmax_only=True)
        outs.append(dict(new))
        out = {**out, **new}
    eng._flush_batch([1, 2])
    if profile_dir:
        jax.profiler.stop_trace()
    return eng, [{k: int(v) for k, v in o.items()} for o in outs]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    set_hub(TelemetryHub(enabled=False))
    store = get_span_store()
    store.clear()
    off, outs_off = _script(model, params, traced=False)
    # set-up is recorded whatever the tracer's state: each program's
    # `compile` span, the engine's `init` span and its parts
    stored_off = len([s for s in store.spans() if s["name"] != "compile"
                      and s["name"] != "init" and s["parent"] is None])
    store.clear()
    logdir = tmp_path_factory.mktemp("profile")
    on, outs_on = _script(model, params, traced=True, profile_dir=logdir)
    spans = store.spans()
    store.clear()
    return {"off": off, "on": on, "outs_off": outs_off, "outs_on": outs_on,
            "stored_off": stored_off, "spans": spans, "logdir": logdir}


def test_counts_are_exact_tracing_on_or_off(runs):
    for eng in (runs["off"], runs["on"]):
        c = eng.serving_counters
        assert (c["token_slots_computed"], c["tokens_fed"],
                c["rounds"]) == (SLOTS, FED, ROUNDS)
        assert c["table_syncs"] >= 2          # both prompts took new blocks
        snap = eng.telemetry_snapshot()
        assert snap["token_slots_computed"] == SLOTS and snap["rounds"] == 5
    parents = [s for s in runs["spans"]
               if s["name"] in ("prefill", "chunk", "decode")]
    assert [(s["fields"]["token_slots"], s["fields"]["tokens_fed"])
            for s in parents] == [(32, 5), (4, 1), (36, 33), (36, 33), (32, 4)]
    assert [s["round"] for s in parents] == [1, 2, 3, 4, 5]


def test_rows_live_and_parked_add_up_to_the_programs_rows(runs):
    """`rows_parked` counts what the paged kernels skip: tracing on or
    off in the counters, and on each `decode` / `chunk` span beside
    `rows_live`, the two adding up to the rows of the program that ran."""
    for eng in (runs["off"], runs["on"]):
        assert eng.serving_counters["rows_parked"] == PARKED
        assert eng.telemetry_snapshot()["rows_parked"] == PARKED
        assert not eng._unparked.any()               # everything flushed
    spans = [s for s in runs["spans"] if s["name"] in ("chunk", "decode")]
    assert len(spans) == len(ROWS)
    for s, (rows, live) in zip(spans, ROWS):
        f = s["fields"]
        width = (MAX_BATCH if s["name"] == "decode" else
                 f["width"] + (MAX_BATCH if f["fused"] else 0))
        assert f["rows_live"] + f["rows_parked"] == width == rows
        assert f["rows_live"] == live


def test_kv_blocks_live_against_the_tables_entries(runs):
    """`kv_blocks_live` counts the (row, block) pairs the paged decode
    kernel walks, `kv_blocks_table` the table entries under the same rows:
    tracing on or off in the counters, and on every span whose program has
    a decode half (`decode`, a fused `chunk`), and on no other."""
    for eng in (runs["off"], runs["on"]):
        for key, total in zip(("kv_blocks_live", "kv_blocks_table"),
                              map(sum, zip(*BLOCKS))):
            assert eng.serving_counters[key] == total
            assert eng.telemetry_snapshot()[key] == total
    spans = [s["fields"] for s in runs["spans"]
             if s["name"] in ("chunk", "decode")]
    halves = [f for f in spans if f.get("fused", True)]
    assert [(f["kv_blocks_live"], f["kv_blocks_table"])
            for f in halves] == BLOCKS
    assert all("kv_blocks_live" not in f and "kv_blocks_table" not in f
               for f in spans if not f.get("fused", True))


def test_a_decode_wave_counts_its_blocks_step_by_step(store):
    """A wave of `k` steps runs the decode kernel `k` times over cursors
    that advance: its span carries the (row, block) pairs summed over the
    steps (the prompt's 14 tokens cross into a second block of 16 on the
    way), against `k` times the table's entries."""
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    set_hub(TelemetryHub(enabled=False))
    groups.reset_topology()
    eng = InferenceEngineV2(model, params=params, max_batch=MAX_BATCH,
                            max_seq_len=128, split_fuse_chunk=CHUNK,
                            cache_block_size=16, prefix_sharing=False)
    eng.tracer.force = True
    eng.generate([list(range(3, 17))], max_new_tokens=7)
    waves = [s["fields"] for s in store.spans() if s["name"] == "decode_wave"]
    assert waves and sum(f["k"] for f in waves) >= 4
    seen, live, table = 14, 0, 0
    for f in waves:
        want = sum(-(-(seen + i) // 16) for i in range(f["k"]))
        assert (f["kv_blocks_live"], f["kv_blocks_table"]) == (
            want, f["k"] * MAX_BATCH * 8)
        seen, live, table = seen + f["k"], live + want, table + f["k"] * 32
    assert live > sum(f["k"] for f in waves)      # some step held two blocks
    assert (eng.serving_counters["kv_blocks_live"],
            eng.serving_counters["kv_blocks_table"]) == (live, table)


def test_chunk_spans_tell_rows_from_sequences(runs):
    """A `chunk` span's `rows` are the rows of its `width` that carry
    tokens and `sequences` the prompts they belong to; `rows_refilled`
    counts, tracing on or off, the rows a prompt took beyond its first."""
    for eng in (runs["off"], runs["on"]):
        assert eng.serving_counters["rows_refilled"] == REFILLED
        assert eng.telemetry_snapshot()["rows_refilled"] == REFILLED
    chunks = [s["fields"] for s in runs["spans"] if s["name"] == "chunk"]
    assert [(f["rows"], f["sequences"], f["width"])
            for f in chunks] == CHUNK_ROWS
    # every row that carries tokens is a live row of the program, and only
    # a prompt's last row feeds less than a whole chunk
    for f, decoding in zip(chunks, (1, 1, 0)):
        assert f["rows_live"] - f["rows"] in (decoding, decoding + 1)
        assert (f["rows"] - 1) * CHUNK < f["tokens_fed"] - decoding \
            <= f["rows"] * CHUNK


def test_tracing_off_makes_no_record_and_changes_no_output(runs):
    assert runs["off"].tracer.spans_recorded == 0
    assert runs["stored_off"] == 0
    assert runs["off"].tracer.last_requests == {}
    assert runs["outs_on"] == runs["outs_off"]            # bit-identical
    assert runs["on"].recompiles.pinned_misses == 0
    assert runs["off"].recompiles.pinned_misses == 0
    assert runs["on"].tracer.spans_recorded > 0


def test_children_nest_under_their_round_and_cover_it(runs):
    by_id = {s["id"]: s for s in runs["spans"]}
    parents = [s for s in runs["spans"]
               if s["name"] in ("prefill", "chunk", "decode")]
    assert len(parents) == ROUNDS
    for p in parents:
        kids = [s for s in runs["spans"] if s["parent"] == p["id"]]
        assert [k["name"] for k in kids] == PHASES
        assert all(k["round"] == p["round"] and k["uids"] == p["uids"]
                   for k in kids)
        covered = sum(k["t1"] - k["t0"] for k in kids)
        assert covered >= 0.98 * (p["t1"] - p["t0"])
        sync, dispatch = kids[1], kids[2]
        assert set(sync["fields"]) == {"dirty", "cow_copies"}
        assert dispatch["fields"]["program"].split(":")[0] in (
            "prefill", "decode", "fused_batch", "chunk_batch")
        # round 3, the first chunk round, compiles the whole family of
        # batched chunk programs (`chunk_batch`, and `fused_batch` at its
        # one width of 4 rows), so rounds 4 and 5 dispatch what is compiled
        assert dispatch["fields"]["compiled"] is (p["round"] <= 3)
    # the head of put is a span of its own, in every round
    heads = [s for s in runs["spans"] if s["name"] == "schedule"]
    assert {s["round"] for s in heads} == {1, 2, 3, 4, 5}
    assert all(s["parent"] is None for s in heads)
    # a program's first dispatch is a `compile` span under the `dispatch`
    # that made it: its own, or the first chunk round's for its family
    compiles = [s for s in runs["spans"] if s["name"] == "compile"]
    assert [(c["fields"]["program"], c["round"]) for c in compiles] == [
        ("prefill:32", 1), ("decode", 2),
        (f"chunk_batch:{CHUNK}", 3),
        (f"fused_batch:{CHUNK}:{MAX_BATCH}", 3)]
    for c in compiles:
        assert by_id[c["parent"]]["name"] == "dispatch"
        assert c["fields"]["backend_compiles"] >= 1
    assert by_id[compiles[-1]["parent"]]["fields"]["program"] == \
        compiles[-1]["fields"]["program"]
    widths = [s["fields"].get("width") for s in parents]
    assert widths == [None, None, MAX_BATCH, MAX_BATCH, MAX_BATCH]


def test_a_put_driven_request_leaves_nothing_unattributed(runs):
    for uid in (1, 2):
        r = runs["on"].tracer.last_requests[uid]
        assert r["unattributed_frac"] < 0.01, r
        assert "schedule" in {k.replace("_other", "") for k in r["spans"]}


def test_a_plain_profile_shows_the_ds_spans(runs):
    """What an operator's own `jax.profiler` trace of a put loop holds: the
    program's spans as `ds:` annotations in the host plane."""
    import glob
    (path,) = glob.glob(str(runs["logdir"] / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("ds:")}
    assert {"ds:schedule", "ds:prefill", "ds:chunk", "ds:decode", "ds:flush",
            "ds:compile"} | {"ds:" + p for p in PHASES} <= names
