"""tpucomms unit tests: HLO collective parsing + replica_groups→axis
decoding, the analytic ZeRO volume model vs real compiled fingerprints,
a seeded misplanned-PartitionSpec fixture caught as an unplanned
all-gather, CLI exit codes over a monkeypatched matrix, and baseline
round-trip. Engine-matrix builds (multi-second compiles) are slow."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.tools.tpucomms import hlo, verify
from deepspeed_tpu.tools.tpucomms import contracts as _contracts  # noqa: F401
from deepspeed_tpu.tools.tpucomms.core import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)
from deepspeed_tpu.tools.tpucomms.fingerprint import fingerprint_hlo
from deepspeed_tpu.tools.tpucomms.put import (
    CommsProgram,
    SERVING_DECLARED,
    analytic_step_bytes,
)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import MeshTopology

# tp2 × dp4 over the virtual 8-dev mesh (model innermost/fastest)
SIZES = {"pipe": 1, "repl": 1, "data": 4, "expert": 1, "sequence": 1,
         "model": 2}


def _mesh():
    groups.reset_topology()
    topo = MeshTopology(tp=2, dp=4)
    groups.initialize(topo)
    return topo.mesh


def _ids(violations):
    return sorted({v.contract for v in violations})


# ------------------------------------------------------------- hlo parsing


def test_parse_explicit_replica_groups():
    assert hlo.parse_replica_groups("{{0,1},{2,3}}") == ((0, 1), (2, 3))
    assert hlo.parse_replica_groups("{}") == ()


def test_parse_iota_replica_groups():
    # [4,2]<=[8]: 4 groups of 2 consecutive partitions
    assert hlo.parse_replica_groups("[4,2]<=[8]") == \
        ((0, 1), (2, 3), (4, 5), (6, 7))
    # transposed iota: [2,4]<=[4,2]T(1,0) → strided groups
    assert hlo.parse_replica_groups("[2,4]<=[4,2]T(1,0)") == \
        ((0, 2, 4, 6), (1, 3, 5, 7))


def test_partition_coords_row_major():
    sizes = tuple(SIZES[a] for a in hlo.MESH_AXES)
    # model is innermost: partition 1 differs from 0 only in model
    assert hlo.partition_coords(0, sizes) == (0, 0, 0, 0, 0, 0)
    assert hlo.partition_coords(1, sizes) == (0, 0, 0, 0, 0, 1)
    assert hlo.partition_coords(2, sizes) == (0, 0, 1, 0, 0, 0)


def test_groups_to_axes_decoding():
    # consecutive pairs vary only in 'model'
    axes, regular = hlo.groups_to_axes(((0, 1), (2, 3), (4, 5), (6, 7)),
                                       SIZES)
    assert (axes, regular) == (("model",), True)
    # stride-2 groups of 4 vary only in 'data'
    axes, regular = hlo.groups_to_axes(((0, 2, 4, 6), (1, 3, 5, 7)), SIZES)
    assert (axes, regular) == (("data",), True)
    # empty groups = every device in one group = all non-trivial axes
    axes, regular = hlo.groups_to_axes((), SIZES)
    assert (axes, regular) == (("data", "model"), True)
    # a group that is NOT a cartesian product of axis subsets
    axes, regular = hlo.groups_to_axes(((0, 3), (1, 2), (4, 7), (5, 6)),
                                       SIZES)
    assert not regular


def test_wire_byte_conventions():
    txt = """
HloModule m
ENTRY %main (p0: f32[8,16]) -> f32[16,16] {
  %ag = f32[16,16]{1,0} all-gather(f32[8,16]{1,0} %p0), replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, use_global_device_ids=true
  %ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %ag), replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add
  ROOT %rs = f32[4,16]{1,0} reduce-scatter(f32[16,16]{1,0} %ar), replica_groups={{0,2,4,6},{1,3,5,7}}, dimensions={0}, to_apply=%add
}
"""
    ops = hlo.parse_collectives(txt)
    assert [op.kind for op in ops] == ["all-gather", "all-reduce",
                                      "reduce-scatter"]
    ag, ar, rs = ops
    assert ag.wire_bytes == 16 * 16 * 4            # gathered output bytes
    assert ar.wire_bytes == 2 * 16 * 16 * 4        # 2x operand bytes
    assert rs.wire_bytes == 4 * 16 * 4 * 4         # output x group_size
    fp = fingerprint_hlo("t", txt, SIZES)
    assert fp.op_counts == {"all-gather": 1, "all-reduce": 1,
                            "reduce-scatter": 1}
    assert fp.bytes_by_axis[("model",)] == ag.wire_bytes
    assert fp.bytes_by_axis[("data",)] == ar.wire_bytes + rs.wire_bytes


# --------------------------------------------- decoding on the real mesh


def test_axis_decode_on_compiled_program():
    """One tiny compiled program per collective flavor: the decoded axes
    must match the PartitionSpecs that produced them."""
    mesh = _mesh()
    rep = NamedSharding(mesh, P())
    jf = jax.jit(lambda x: jnp.sum(x),
                 in_shardings=(NamedSharding(mesh, P("data")),),
                 out_shardings=rep)
    txt = jf.lower(jax.ShapeDtypeStruct((8, 4), jnp.float32)) \
            .compile().as_text()
    ops = hlo.parse_collectives(txt)
    assert ops, "expected a cross-data reduction"
    assert {hlo.op_axes(op, SIZES) for op in ops} == {(("data",), True)}


def test_seeded_misplanned_spec_unplanned_allgather():
    """THE drift fixture: a serving weight whose ROW dim is sharded over
    'data' under a data-sharded batch — GSPMD must all-gather the full
    weight every step. tpucomms reports it on both serving contracts."""
    mesh = _mesh()
    sh = NamedSharding(mesh, P("data", None))
    jf = jax.jit(lambda x, w: x @ w, in_shardings=(sh, sh),
                 out_shardings=sh)
    args = (jax.ShapeDtypeStruct((8, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 32), jnp.float32))
    put = CommsProgram(name="serve:bad", fn=jf, args=args, sizes_map=SIZES,
                       declared_axes=SERVING_DECLARED, kind="serving",
                       weight_shapes=frozenset({((16, 32), "f32")}))
    out = verify([put])
    assert "no-unplanned-allgather" in _ids(out)
    assert "axis-confinement" in _ids(out)
    assert any("(16, 32)" in v.message for v in out
               if v.contract == "no-unplanned-allgather")


def test_planned_tp_serving_clean():
    """The clean twin: column-sharded weight over 'model' with the
    output left model-sharded — no weight gather, model-only comms."""
    mesh = _mesh()
    rep = NamedSharding(mesh, P())
    wsh = NamedSharding(mesh, P(None, "model"))
    jf = jax.jit(lambda x, w: x @ w, in_shardings=(rep, wsh),
                 out_shardings=NamedSharding(mesh, P(None, "model")))
    args = (jax.ShapeDtypeStruct((8, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 32), jnp.float32))
    put = CommsProgram(name="serve:ok", fn=jf, args=args, sizes_map=SIZES,
                       declared_axes=SERVING_DECLARED, kind="serving",
                       weight_shapes=frozenset({((16, 32), "f32")}))
    assert verify([put]) == []


def test_axis_confinement_clean_vs_violating():
    mesh = _mesh()
    rep = NamedSharding(mesh, P())
    jf = jax.jit(lambda x: jnp.sum(x),
                 in_shardings=(NamedSharding(mesh, P("data")),),
                 out_shardings=rep)
    args = (jax.ShapeDtypeStruct((8, 4), jnp.float32),)
    ok = CommsProgram(name="t:ok", fn=jf, args=args, sizes_map=SIZES,
                      declared_axes=frozenset({"data"}))
    assert verify([ok], contracts=["axis-confinement"]) == []
    bad = CommsProgram(name="t:bad", fn=jf, args=args, sizes_map=SIZES,
                       declared_axes=frozenset({"model"}))
    out = verify([bad], contracts=["axis-confinement"])
    assert _ids(out) == ["axis-confinement"]
    assert "data" in out[0].message


# ------------------------------------------------------- analytic volumes


def test_analytic_step_bytes_model():
    P_ = 1000
    assert analytic_step_bytes(3, P_, gas=2) == 6000   # 3P per micro
    assert analytic_step_bytes(2, P_, gas=2) == 5000   # 2P per micro + P
    assert analytic_step_bytes(1, P_, gas=1) == 3000
    assert analytic_step_bytes(0, P_, gas=4) == 8000   # grad reduce only


def test_volume_budget_contract():
    fp_sizes = {"data": 8}
    put = CommsProgram(name="t", fn=None, args=(), sizes_map=fp_sizes,
                       budget_bytes=100, budget_note="unit")
    # inject a pre-built fingerprint over budget
    txt = """
ENTRY %main (p0: f32[64]) -> f32[64] {
  ROOT %ar = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={}, to_apply=%add
}
"""
    put._fp = fingerprint_hlo("t", txt, fp_sizes)
    assert put.fingerprint().total_bytes == 2 * 64 * 4
    out = verify([put], contracts=["comm-volume-budget"])
    # 512 B over a 100 B budget is still inside the absolute slack; the
    # slack exists for O(words) counters, so shrink it via a huge op
    assert out == []
    big = "f32[1048576]"
    txt_big = f"""
ENTRY %main (p0: {big}) -> {big} {{
  ROOT %ar = {big}{{0}} all-reduce({big}{{0}} %p0), replica_groups={{}}, to_apply=%add
}}
"""
    put2 = CommsProgram(name="t2", fn=None, args=(), sizes_map=fp_sizes,
                        budget_bytes=100, budget_note="unit")
    put2._fp = fingerprint_hlo("t2", txt_big, fp_sizes)
    out = verify([put2], contracts=["comm-volume-budget"])
    assert _ids(out) == ["comm-volume-budget"]
    assert "unit" in out[0].message


@pytest.mark.slow
def test_zero3_train_fingerprint_matches_analytic():
    """The acceptance criterion: the real ZeRO-3 train step's measured
    collective volume lands within the 3×P-per-micro analytic budget
    (LICM hoists loop-invariant gathers, so observed ≈ P + gas·2P) and
    is nonvacuous (at least one full param-volume on the wire)."""
    from deepspeed_tpu.tools.tpucomms.put import build_train_comms
    puts = build_train_comms(gas=2)
    assert verify(puts) == []
    tb = [p for p in puts if p.name == "train:train_batch"]
    assert tb and tb[0].budget_bytes
    fp = tb[0].fingerprint()
    assert fp.source == "hlo"
    p_bytes = tb[0].budget_bytes // (3 * 2)      # budget = 3·P·gas
    assert fp.total_bytes <= tb[0].budget_bytes * 1.25 + (1 << 20)
    assert fp.total_bytes >= 2 * p_bytes, \
        "volume contract is vacuous: almost nothing on the wire"
    assert set(fp.bytes_by_axis) == {("data",)}, \
        "pure-dp ZeRO-3 must communicate only over 'data'"


@pytest.mark.slow
@pytest.mark.parametrize("stage", [1, 2])
def test_zero12_train_fingerprint_within_budget(stage):
    """ZeRO-1/2 replicate params: the wire carries the grad reduction
    (2×P per micro as all-reduce on this XLA) and no param gathers."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.tools.tpucomms.put import (
        TRAIN_DECLARED, _token_mlp, _tree_bytes)

    groups.reset_topology()
    model, params = _token_mlp(64)
    gas = 2
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        loss_fn=lambda p, b, r: model.apply({"params": p}, b["x"], b["y"]),
        config={"train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": stage}})
    engine.recompiles.record_signatures = True
    rng = np.random.default_rng(0)
    rows = engine.topology.dense_dp_size * 4 * gas
    batch = {"x": rng.standard_normal((rows, 64)).astype(np.float32),
             "y": rng.standard_normal((rows, 64)).astype(np.float32)}
    engine.train_batch(batch=batch)
    p_bytes = _tree_bytes(engine.state.params)
    fn = engine._jit_cache["train_batch"]
    args = engine.recompiles.abstract["train_batch"]
    put = CommsProgram(
        name=f"train:z{stage}", fn=fn, args=args,
        sizes_map=dict(engine.topology.sizes),
        declared_axes=TRAIN_DECLARED, kind="train", loop_multiplier=gas,
        budget_bytes=analytic_step_bytes(stage, p_bytes, gas))
    assert verify([put]) == []
    fp = put.fingerprint()
    assert fp.total_bytes <= put.budget_bytes * 1.25 + (1 << 20)
    assert fp.total_bytes >= p_bytes, \
        "grad reduction missing from the fingerprint"


# ----------------------------------------------------- baseline + the CLI


def test_baseline_round_trip(tmp_path):
    v1 = Violation("axis-confinement", "train:train_batch", "msg a")
    v2 = Violation("no-unplanned-allgather", "v2:decode", "msg b")
    path = str(tmp_path / ".tpucomms-baseline.json")
    save_baseline(path, [v1, v2])
    baseline = load_baseline(path)
    assert new_violations([v1, v2], baseline) == []
    v3 = Violation("comm-volume-budget", "train:train_batch", "msg c")
    assert new_violations([v1, v3], baseline) == [v3]


def _fake_matrix(violating):
    def build(include=("train",)):
        known = {"train", "v1", "v2", "v2_layer_scan"}
        unknown = [k for k in include if k not in known]
        if unknown:
            raise KeyError(f"unknown matrix component(s): {unknown}")
        mesh = _mesh()
        if violating:
            sh = NamedSharding(mesh, P("data", None))
            jf = jax.jit(lambda x, w: x @ w, in_shardings=(sh, sh),
                         out_shardings=sh)
            return [CommsProgram(
                name="fake:bad", fn=jf,
                args=(jax.ShapeDtypeStruct((8, 16), jnp.float32),
                      jax.ShapeDtypeStruct((16, 32), jnp.float32)),
                sizes_map=SIZES, declared_axes=SERVING_DECLARED,
                kind="serving",
                weight_shapes=frozenset({((16, 32), "f32")}))]
        return [CommsProgram(name="fake:ok", fn=jax.jit(lambda x: x + 1),
                             args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                             sizes_map=SIZES,
                             declared_axes=frozenset())]
    return build


def test_cli_exit_codes(monkeypatch, tmp_path):
    from deepspeed_tpu.tools.tpucomms import put as put_mod
    from deepspeed_tpu.tools.tpucomms.cli import main

    monkeypatch.chdir(tmp_path)  # no repo baseline in scope
    monkeypatch.setattr(put_mod, "build_comms_matrix",
                        _fake_matrix(violating=False))
    assert main(["--no-baseline"]) == 0

    monkeypatch.setattr(put_mod, "build_comms_matrix",
                        _fake_matrix(violating=True))
    assert main(["--no-baseline"]) == 1
    assert main(["--select", "bogus-contract"]) == 2
    assert main(["--include", "nonsense"]) == 2

    # baseline flow: grandfather the violations, then exit 0
    baseline = str(tmp_path / "bl.json")
    assert main(["--update-baseline", "--baseline", baseline]) == 0
    assert main(["--baseline", baseline]) == 0


def test_cli_list_contracts(capsys):
    from deepspeed_tpu.tools.tpucomms.cli import main
    assert main(["--list-contracts"]) == 0
    out = capsys.readouterr().out
    assert "axis-confinement" in out
    assert "comm-volume-budget" in out
    assert "no-unplanned-allgather" in out


def test_cli_exclude(monkeypatch, tmp_path):
    from deepspeed_tpu.tools.tpucomms import put as put_mod
    from deepspeed_tpu.tools.tpucomms.cli import main
    seen = {}

    def build(include):
        seen["include"] = tuple(include)
        return []
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(put_mod, "build_comms_matrix", build)
    assert main(["--no-baseline", "--exclude", "v1,v2_layer_scan"]) == 0
    assert seen["include"] == ("train", "v2")


# -------------------------------------------------- the real matrix (slow)


@pytest.mark.slow
def test_serving_matrix_clean():
    from deepspeed_tpu.tools.tpucomms.put import build_comms_matrix
    puts = build_comms_matrix(include=("v1", "v2"))
    assert puts
    assert verify(puts) == []
    # single-device serving engines must be comm-free
    for p in puts:
        assert p.fingerprint().total_bytes == 0, p.name
