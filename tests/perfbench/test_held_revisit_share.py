"""`held_revisit_share.gen` (PR 66), a data file alone: the expert layer's
`weight_tile_revisits` over `experts_touched`, both counted inside the
generate programs, read by the reader that reads the other counter shares;
in the three cells whose decode step puts experts astride its grouped GEMM's
16-row tiles."""

import types

import pytest

from perfbench.manifest import Manifest, problems

M = Manifest()
NAME = "held_revisit_share.gen"
CELLS = ["nemotron3-nano-30b-l14-ep2.generate-reason",
         "ling3-flash-l6-ep4.generate-reason-1k",
         "trinity-mini-l16-ep8.generate-agent-8k"]


@pytest.fixture
def hub():
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    yield set_hub(TelemetryHub(enabled=False))
    set_hub(TelemetryHub(enabled=False))


def read(cell):
    decl = M.metric(NAME)
    ctx = types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={},
        sizes=M.config(M.workload(cell)["config"]),
        traffic=M.traffic(M.workload(cell)["traffic"]), manifest=M, chips=1)
    return M.reader(decl["reader"])(ctx, **decl["params"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_lists_the_share_and_reads_it_off_the_hub(cell, hub):
    assert problems(M) == []
    decl = next(m for m in M.metrics_for(cell, "per_layer")
                if m["name"] == NAME)
    assert decl["workloads"] == CELLS == M.metric(NAME)["workloads"]
    assert (decl["layer"], decl["moves"], decl["better"], decl["unit"],
            decl["source"]) == ("expert layer", "out_tok_s", "lower", "%",
                                "program_counter")
    # no reader of its own: the one the other counter shares are read by
    assert M.metric(NAME)["reader"] == \
        M.metric("experts_touched_share.gen")["reader"]
    hub.counter("serving_v1/experts_touched", 60)
    hub.counter("serving_v1/weight_tile_revisits", 9)
    assert read(cell) == pytest.approx(15.0)


@pytest.mark.parametrize("counted", [{}, {"serving_v1/experts_touched": 60},
                                     {"serving_v1/weight_tile_revisits": 0}])
def test_a_program_that_counts_neither_reports_nothing(counted, hub):
    """The parent counts no revisits (and its Nemotron-H no touched experts
    either): the reader returns None and the line leaves the metric out."""
    for name, value in counted.items():
        hub.counter(name, value)
    assert read(CELLS[0]) is None


def test_no_step_astride_a_tile_reads_zero(hub):
    hub.counter("serving_v1/experts_touched", 60)
    hub.counter("serving_v1/weight_tile_revisits", 0)
    assert read(CELLS[0]) == 0.0
