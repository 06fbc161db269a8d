"""The reduction from a device trace to numbers, on hand-made traces and on
a small piece of a trace recorded on the chip (`data/`). No JAX."""

import json
import os

import pytest

from perfbench import trace as tm

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6   # nanoseconds


def ev(name, start_ms, dur_ms):
    return [name, start_ms * MS, dur_ms * MS]


def toy():
    """Two rounds on one device. Round 1: a while of two fusions and a
    kernel; idle; round 2: a copy and a kernel; then a flush with no op."""
    ops = [ev("while.7", 0, 6), ev("fusion.11", 0, 2), ev("fusion.12", 2, 2),
           ev("self_attn.3", 4, 1.5),
           ev("copy.4", 10, 3), ev("self_attn.5", 13, 1)]
    host = [ev("pb:traced", 0, 20), ev("pb:round", -0.5, 8),
            ev("pb:round", 9, 6), ev("pb:flush", 15, 3)]
    return {"devices": {"0": {"ops": ops, "modules": [ev("jit_decode", 0, 6),
                                                      ev("jit_decode", 10, 4)]}},
            "host": host}


def test_op_name_strips_the_instruction_and_its_number():
    assert tm.op_name("%fusion.123 = bf16[8]{0} fusion(%p), kind=kLoop") == "fusion"
    assert tm.op_name("self_attn.38 = (f32[2]) custom-call(...)") == "self_attn"
    assert tm.op_name("bitcast_add_fusion.10") == "bitcast_add_fusion"
    assert tm.op_name("all-gather-start.2.1") == "all-gather-start"
    assert tm.op_name("copy") == "copy"


def test_union_subtract_clip():
    assert tm.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tm.length(tm.union([(0, 2), (1, 3)])) == 3
    assert tm.clip([(0, 10), (20, 30)], (5, 25)) == [(5, 10), (20, 25)]
    assert tm.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tm.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert tm.subtract([(0, 4)], []) == [(0, 4)]


def test_window_is_the_marked_one():
    t = toy()
    assert tm.trace_window(t) == (0.0, 20 * MS)
    t["host"] = [e for e in t["host"] if e[0] != "pb:traced"]
    assert tm.trace_window(t) == (0.0, 14 * MS)


def test_busy_is_a_union_not_a_sum():
    t = toy()
    w = tm.trace_window(t)
    assert tm.busy_seconds(t, w) == pytest.approx(10e-3)   # 6 + 4, not 16.5
    gaps = tm.idle_gaps(t["devices"]["0"]["ops"], w)
    assert gaps == [(6 * MS, 10 * MS), (14 * MS, 20 * MS)]


def test_busy_is_averaged_over_devices():
    t = toy()
    t["devices"]["1"] = {"ops": [ev("fusion.1", 0, 20)], "modules": []}
    assert tm.busy_seconds(t, (0.0, 20 * MS)) == pytest.approx(15e-3)


def test_idle_gaps_go_to_the_host_annotation_they_fall_in():
    t = toy()
    w = tm.trace_window(t)
    got = tm.attribute_gaps(tm.idle_gaps(t["devices"]["0"]["ops"], w), t["host"])
    assert got["pb:round"] == pytest.approx(3.5e-3)    # 6..7.5, 9..10, 14..15
    assert got["pb:flush"] == pytest.approx(3e-3)      # 15..18
    assert got["(unannotated)"] == pytest.approx(3.5e-3)   # 7.5..9, 18..20
    assert sum(got.values()) == pytest.approx(10e-3)
    assert "pb:traced" not in got


def test_self_time_takes_the_children_out_of_the_while():
    st = tm.self_times(toy()["devices"]["0"]["ops"])
    assert st["while"] == pytest.approx(0.5e-3)        # 6 - 2 - 2 - 1.5
    assert st["fusion"] == pytest.approx(4e-3)
    assert st["self_attn"] == pytest.approx(2.5e-3)
    assert sum(st.values()) == pytest.approx(10e-3)    # = busy


def test_kernel_time_by_name_and_window():
    ops = toy()["devices"]["0"]["ops"]
    assert tm.seconds_matching(ops, "^self_attn") == pytest.approx(2.5e-3)
    assert tm.seconds_matching(ops, "^self_attn", (0.0, 5 * MS)) == \
        pytest.approx(1e-3)
    assert tm.seconds_matching(ops, "nothing") == 0


def test_exposed_collective_time():
    ops = [ev("while.1", 0, 30),
           ev("all-gather-start.1", 0, 1), ev("fusion.1", 1, 8),
           ev("all-gather-done.1", 9, 3),            # waited: exposed
           ev("fusion.2", 12, 8), ev("all-reduce.4", 20, 5),
           ev("fusion.3", 25, 5)]
    assert tm.exposed_collective_seconds(ops, (0.0, 30 * MS)) == \
        pytest.approx(9e-3)
    # a compute op on another line of the same device hides a collective
    both = ops + [ev("fusion.9", 20, 5)]
    assert tm.exposed_collective_seconds(both, (0.0, 30 * MS)) == \
        pytest.approx(4e-3)


def test_breakdown_shape():
    t = toy()
    b = tm.breakdown(t, tm.trace_window(t))
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0] == ["fusion", pytest.approx(4e-3)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {n for n, _ in b["idle_gaps"]} == {"pb:round", "pb:flush",
                                              "(unannotated)"}


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tm.busy_seconds({"devices": {}, "host": []}, (0, 1))


def test_excerpt_keeps_the_first_ops_of_the_window():
    t = toy()
    small = tm.excerpt(t, ops=4)
    assert len(small["devices"]["0"]["ops"]) == 4
    assert all(e[1] < 6 * MS for e in small["devices"]["0"]["ops"])
    assert [e[0] for e in small["host"]] == ["pb:traced", "pb:round"]


# ----------------------------------------------------- the recorded trace

RECORDED = os.path.join(HERE, "data", "serve_trace_excerpt.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_reduces(recorded):
    """A piece of `qwen2.5-3b.serve-chat`'s traced window from the chip:
    the names the readers look for are there and the parts add up."""
    ops = tm.first_device(recorded)["ops"]
    w = (min(e[1] for e in ops), max(e[1] + e[2] for e in ops))
    busy = tm.busy_seconds(recorded, w)
    gaps = tm.idle_gaps(ops, w)
    assert 0 < busy <= (w[1] - w[0]) / 1e9
    assert busy + tm.length(gaps) / 1e9 == pytest.approx((w[1] - w[0]) / 1e9)
    st = tm.self_times(ops, w)
    assert sum(st.values()) == pytest.approx(busy, rel=1e-6)
    assert tm.seconds_matching(ops, "^self_attn", w) > 0
    assert any(e[0] == "pb:round" for e in recorded["host"])
    by = tm.attribute_gaps(gaps, recorded["host"])
    assert sum(by.values()) == pytest.approx(tm.length(gaps) / 1e9)
    assert by.get("pb:round", 0) > 0
    assert all(" = " not in e[0] for e in ops)
