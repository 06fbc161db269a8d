"""Each reader against a hand-made run record: what it reads, and that it
reads nothing (None) where there is nothing to read. No JAX."""

import types

import pytest

from perfbench import harness
from perfbench.manifest import Manifest

from .test_trace import MS, toy

M = Manifest()
PEAK_BYTES = 9_893_342_720   # a train run's memory_stats peak


def ctx_with(**kw):
    base = dict(samples={}, counters={}, spans={}, trace=None, trace_window=None,
                device={}, peaks=None, chips=1)
    return types.SimpleNamespace(**{**base, **kw})


def read(name, ctx):
    decl = M.metric(name)
    return M.reader(decl["reader"])(ctx, **decl.get("params", {}))


def test_tail_metrics_count_failed_requests_as_missing():
    ctx = ctx_with(samples={"ttft_ms": list(range(1, 71)),
                            "tpot_ms": [100.0] * 70},
                   counters={"missing": 6})
    assert read("ttft_p80_ms", ctx) == 61          # rank ceil(0.8 * 76)
    assert read("ttft_p50_ms", ctx) == 38
    assert read("ttft_p90_ms", ctx) == 69
    assert read("tpot_p80_ms", ctx) == 100.0
    ctx.counters["missing"] = 30                   # the rank is a failure
    assert read("ttft_p80_ms", ctx) is None


def test_counters_shares_and_means():
    ctx = ctx_with(counters={"slo_met": 57, "attempted": 76, "setup_s": 31.5,
                             "backlog_end": 0, "recompiles_in_window": 0,
                             "out_tok_s": 1900.0},
                   samples={"occupancy": [50.0, 70.0], "step_ms": [600, 610, 620]})
    assert read("slo_share", ctx) == 75.0
    assert read("setup_s", ctx) == 31.5
    assert read("backlog_end", ctx) == 0 and read("recompiles_in_window", ctx) == 0
    assert read("batch_occupancy", ctx) == 60.0
    assert read("step_ms.train", ctx) == 610
    assert read("out_tok_s", ctx) == 1900.0


def test_nothing_recorded_nothing_reported():
    ctx = ctx_with()
    for name in ("ttft_p80_ms", "slo_share", "queue_wait_p50_ms",
                 "idle_share.serve", "paged_attn_ms", "host_gap_share.serve",
                 "exposed_coll_share.x4", "mfu.train", "peak_hbm_gb.train",
                 "flash_attn_share.train", "train_tok_s"):
        assert read(name, ctx) is None, name


def test_queue_wait_comes_from_the_programs_spans():
    ctx = ctx_with(spans={"requests": [{"queue_s": 0.010}, {"queue_s": 0.030},
                                       {"queue_s": 0.020}, {"queue_s": None}]})
    assert read("queue_wait_p50_ms", ctx) == pytest.approx(20.0)


def test_mfu_needs_the_chips_peak():
    counters = {"train_tok_s": 27000.0, "flops_per_token": 3.2e9}
    assert read("mfu.train", ctx_with(counters=counters)) is None   # a CPU
    ctx = ctx_with(counters=counters, peaks={"bf16_tflops": 197.0}, chips=1)
    assert read("mfu.train", ctx) == pytest.approx(43.86, abs=0.01)
    ctx.chips = 4
    assert read("mfu.train", ctx) == pytest.approx(43.86 / 4, abs=0.01)


def test_trace_readers_on_the_toy_trace():
    t = toy()
    ctx = ctx_with(trace=t, trace_window=(0.0, 20 * MS),
                   counters={"traced_rounds": 2})
    assert read("idle_share.serve", ctx) == pytest.approx(50.0)
    assert read("host_gap_share.serve", ctx) == pytest.approx(17.5)  # 3.5 of 20 ms
    assert read("paged_attn_ms", ctx) == pytest.approx(1.25)         # 2.5 ms / 2
    assert read("flash_attn_share.train", ctx) == pytest.approx(25.0)
    assert read("exposed_coll_share.x4", ctx) == 0.0


def test_peak_hbm():
    ctx = ctx_with(device={"memory_peak_bytes": PEAK_BYTES})
    assert read("peak_hbm_gb.train", ctx) == pytest.approx(9.893, abs=1e-3)


def test_rehearsal_overrides_and_sweep_settings():
    tf = {"arrivals": {"process": "poisson", "rate": 1.5},
          "engine": {"max_batch": 48, "split_fuse_chunk": 16}}
    small = harness._merge(tf, {"arrivals": {"rate": 4.0},
                                "engine": {"max_batch": 4}})
    assert small["arrivals"] == {"process": "poisson", "rate": 4.0}
    assert small["engine"] == {"max_batch": 4, "split_fuse_chunk": 16}
    assert tf["engine"]["max_batch"] == 48          # the file's dict is untouched
    harness.set_path(tf, "arrivals.rate", 2.5)
    assert tf["arrivals"]["rate"] == 2.5
