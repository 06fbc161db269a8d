"""`tools/tpucomms/hlo.py` beyond collectives: every instruction of every
computation, what a fusion HOLDS, and the scope an instruction was traced
under. On a hand-written module, and on the recorded text of one
x4-shaped train step (llama-tiny, two layers, ZeRO-3 on a dp2 x tp2 mesh of
four CPU devices: `data/x4_step_cpu.hlo.txt.gz`, the tables of file names
taken off). stdlib-only, like the parser."""

import gzip
import os

import pytest

from deepspeed_tpu.tools.tpucomms import hlo

SIZES = {"data": 2, "model": 2}

HAND = """\
HloModule jit_hand_step, is_scheduled=true, num_partitions=4

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y)
}

%inner_fused (p0: bf16[8,128]{1,0:T(8,128)(2,1)}) -> bf16[8,128]{1,0:T(8,128)(2,1)} {
  %p0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.7 = bf16[8,128]{1,0:T(8,128)(2,1)} all-reduce(%p0), channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add.clone
  ROOT %dynamic-update-slice.2 = bf16[8,128]{1,0:T(8,128)(2,1)} dynamic-update-slice(%p0, %all-reduce.7, %p0)
}

%outer_fused (q0: bf16[8,128]{1,0:T(8,128)(2,1)}) -> bf16[8,128]{1,0:T(8,128)(2,1)} {
  %q0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %convolution.1 = bf16[8,128]{1,0:T(8,128)(2,1)} convolution(%q0, %q0), dim_labels=bf_io->bf
  ROOT %fusion.inner = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%convolution.1), kind=kLoop, calls=%inner_fused
}

%body (carry: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %carry = (s32[], bf16[8,128]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[8,128]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=1
  %copy.5 = bf16[8,128]{1,0:T(8,128)(2,1)} copy(%gte.1)
  %fusion.123 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%copy.5), kind=kOutput, calls=%outer_fused, metadata={op_name="jit(hand_step)/micro/transpose(jvp(Model))/layers/q_proj/dot_general" source_file="a.py" source_line=3}
  %gmm.4 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(%fusion.123), custom_call_target="tpu_custom_call", metadata={op_name="jit(hand_step)/micro/jvp(Model)/layers/experts/gmm"}
  %all-gather-start.2 = (bf16[4,128], bf16[8,128]) all-gather-start(%gmm.4), channel_id=5, replica_groups=[2,2]<=[4], dimensions={0}, metadata={op_name="jit(hand_step)/micro/jvp(Model)/layers/all_gather"}
  %all-gather-done.2 = bf16[8,128]{1,0:T(8,128)(2,1)} all-gather-done(%all-gather-start.2)
  ROOT %tuple.3 = (s32[], bf16[8,128]) tuple(%gte.1, %all-gather-done.2)
}

%cond (c: (s32[], bf16[8,128])) -> pred[] {
  %c = (s32[], bf16[8,128]) parameter(0)
  ROOT %lt.1 = pred[] compare(%c, %c), direction=LT
}

ENTRY %main.1_spmd (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %tuple.1 = (s32[], bf16[8,128]) tuple(%a, %a)
  %while.2 = (s32[], bf16[8,128]) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(hand_step)/micro/jvp(Model)/while"}
  %gte.9 = bf16[8,128]{1,0:T(8,128)(2,1)} get-tuple-element(%while.2), index=1
  ROOT %multiply_subtract_fusion = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%gte.9), kind=kLoop, calls=%add.clone, metadata={op_name="jit(hand_step)/optimizer/sub;jit(hand_step)/optimizer/mul"}
}
"""


@pytest.fixture(scope="module")
def hand():
    name, rows = hlo.instruction_rows(HAND, SIZES)
    return name, {r["instr"]: r for r in rows}


def test_a_module_lists_every_instruction_with_what_it_names():
    mod = hlo.parse_module(HAND)
    assert (mod.name, mod.entry) == ("jit_hand_step", "main.1_spmd")
    assert sorted(mod.computations) == sorted(
        ["add.clone", "inner_fused", "outer_fused", "body", "cond",
         "main.1_spmd"])
    body = {i.name: i for i in mod.computations["body"]}
    # a TPU tiling's parentheses and a tuple's do not hide the opcode
    assert body["fusion.123"].opcode == "fusion"
    assert body["fusion.123"].kind == "kOutput"
    assert body["fusion.123"].called("calls") == ("outer_fused",)
    assert body["gmm.4"].target == "tpu_custom_call"
    assert body["all-gather-start.2"].opcode == "all-gather-start"
    assert body["all-gather-start.2"].collective.kind == "all-gather"
    assert body["all-gather-done.2"].collective is None
    assert body["copy.5"].op_name == ""
    loop = {i.name: i for i in mod.computations["main.1_spmd"]}["while.2"]
    assert loop.called("body") == ("body",)
    assert loop.called("condition") == ("cond",)
    # the collectives' reader sits on the same parse
    assert [c.kind for c in hlo.parse_collectives(HAND)] == [
        "all-reduce", "all-gather"]


def test_rows_are_the_instructions_a_trace_can_name(hand):
    name, rows = hand
    assert name == "jit_hand_step"
    # entry and what control flow reaches; the inside of a fusion is no row
    assert "while.2" in rows and "fusion.123" in rows and "lt.1" in rows
    assert "all-reduce.7" not in rows and "convolution.1" not in rows
    assert rows["fusion.123"]["loop"] == "body"
    assert rows["lt.1"]["loop"] == "body"
    assert rows["while.2"]["loop"] is None


def test_holds_follows_calls_through_nested_fusions_with_axes(hand):
    _, rows = hand
    # fusion.123 -> outer_fused (a convolution) -> inner_fused (an all-reduce
    # over `data`, a dynamic-update-slice); the reducer `add.clone` is a
    # `to_apply`, not a call, and adds nothing
    assert rows["fusion.123"]["holds"] == [
        "all-reduce[data]", "convolution", "dynamic-update-slice"]
    assert rows["gmm.4"]["holds"] == ["custom-call:tpu_custom_call"]
    assert rows["all-gather-start.2"]["holds"] == ["all-gather-start[model]"]
    assert rows["copy.5"]["holds"] == []
    # without a mesh the collective is listed bare
    bare = {r["instr"]: r for r in hlo.instruction_rows(HAND)[1]}
    assert bare["fusion.123"]["holds"][0] == "all-reduce"


def test_scope_phase_and_what_a_bare_copy_inherits(hand):
    _, rows = hand
    f = rows["fusion.123"]
    assert f["scope"] == \
        "micro/transpose(jvp(Model))/layers/q_proj/dot_general"
    assert f["phase"] == "bwd"
    assert set(hlo.scope_names(f["scope"])) >= {"micro", "Model", "layers",
                                                "q_proj"}
    assert rows["gmm.4"]["phase"] == "fwd"
    # a fusion's merged metadata: the first
    opt = rows["multiply_subtract_fusion"]
    assert (opt["scope"], opt["phase"]) == ("optimizer/sub", None)
    # a copy the compiler placed in a loop body takes the `while`'s scope
    assert rows["copy.5"]["scope"] == "micro/jvp(Model)/while"
    assert rows["copy.5"]["inferred"] is True
    assert "inferred" not in f


@pytest.mark.parametrize("op_name,scope,phase", [
    ("jit(step)/jit(main)/pjit(inner)/head/dot_general", "head/dot_general",
     None),
    ("jit(f)/transpose(jvp(a/b))/mul", "transpose(jvp(a/b))/mul", "bwd"),
    ("jit(f)/jvp(micro)/while/body/closed_call/layers/tanh",
     "jvp(micro)/while/body/closed_call/layers/tanh", "fwd"),
    ("", "", None)])
def test_scope_of_takes_the_jit_wrappers_off(op_name, scope, phase):
    assert hlo.scope_of(op_name) == scope
    assert hlo.phase_of(scope) == phase


# ------------------------------------------------- the recorded x4-shaped step


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "x4_step_cpu.hlo.txt.gz")
    with gzip.open(path, "rt") as f:
        return hlo.instruction_rows(f.read(), SIZES)


def test_recorded_step_names_its_scopes(recorded):
    name, rows = recorded
    assert name == "jit_ds_train_train_batch"
    names = [set(hlo.scope_names(r["scope"])) for r in rows]
    for scope in ("micro", "layers", "chunked_ce", "optimizer",
                  "grad_accumulate"):
        assert any(scope in n for n in names), scope
    # every product of the step lies in the layers or in the loss
    dots = [r for r in rows if "dot" in r["holds"]]
    assert len(dots) > 20
    for r in dots:
        n = set(hlo.scope_names(r["scope"]))
        assert n & {"layers", "chunked_ce"}, r
        assert "optimizer" not in n
    assert {r["phase"] for r in dots} == {"fwd", "bwd"}


def test_recorded_step_tells_the_dw_reductions_from_the_tp_ones(recorded):
    _, rows = recorded
    held = {}
    for r in rows:
        for h in r["holds"]:
            if "[" in h:
                held.setdefault(h, []).append(r)
    # ZeRO-3 over `data`: parameter gathers, and the gradients' reductions
    # in the backward; the layers' tensor-parallel ones over `model`
    assert "all-gather[data]" in held
    over_data = held["all-reduce[data]"]
    assert any(r["phase"] == "bwd" and r["loop"] for r in over_data)
    assert all("model" not in h for h in ("all-reduce[data]",))
    assert "all-reduce[model]" in held
    assert any(k.startswith("collective-permute[") for k in held)
