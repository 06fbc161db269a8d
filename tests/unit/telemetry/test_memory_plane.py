"""MemoryPlane residency-ledger tests (telemetry/memory.py, docs/memory.md).

Contracts pinned here:
- ledger semantics: overwrite-by-name, owner release (incl. the weakref
  finalizer on engine GC), logical rows excluded from physical totals,
  watermarks, adjust, reconcile tolerance;
- tier routing: the backend's DEFAULT memory kind reads as `hbm` even on
  the CPU mesh (whose default kind is literally named "unpinned_host"),
  numpy trees read as `host`, NVMe placeholders as `nvme`;
- registration is metadata-only — never a device fetch;
- the engine matrix (v1 dequant / layer_scan / capacity, v2 paged, the
  train step) reconciles registered bytes against the byte FORMULAS
  (dense tree bytes, `at_rest_bytes`, `kv_cache_bytes`,
  `CapacityPlan.peak_hbm_bytes`) within 2%;
- capacity's registered HBM watermark never exceeds the plan bound;
- the plane adds zero pinned-program recompile misses and registers at
  dispatch granularity (a repeated generate changes nothing).

The satellite grid test asserts `choose_serve_mode` / `CapacityPlan` /
`KVBudget` / MemoryPlane all consume ONE kv-byte number per
(model, dtype, kv_dtype, batch) point.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.capacity_scan import (decode_workspace_bytes,
                                                   kv_cache_bytes,
                                                   round_up_len)
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry.memory import (MemoryPlane, get_plane, leaf_bytes,
                                            owner_for, scratch_plane,
                                            tier_of_leaf, tier_of_sharding,
                                            tree_bytes)
from deepspeed_tpu.utils import groups

MB = 1 << 20


# ------------------------------------------------------------ ledger basics
def test_register_overwrites_same_name_and_releases_by_owner():
    plane = MemoryPlane(emit_events=False)
    plane.register("a", component="params", tier="hbm", nbytes=100, owner="e1")
    plane.register("a", component="params", tier="hbm", nbytes=40, owner="e1")
    plane.register("b", component="kv_cache", tier="hbm", nbytes=7, owner="e2")
    assert plane.total(tier="hbm") == 47          # overwrite, not accumulate
    plane.release_owner("e1")
    assert plane.total(tier="hbm") == 7
    plane.release("b")
    assert plane.total() == 0


def test_unknown_component_and_tier_are_refused():
    plane = MemoryPlane(emit_events=False)
    with pytest.raises(ValueError, match="component"):
        plane.register("x", component="weights", tier="hbm", nbytes=1)
    with pytest.raises(ValueError, match="tier"):
        plane.register("x", component="params", tier="vmem", nbytes=1)


def test_logical_rows_excluded_from_totals_and_watermarks():
    plane = MemoryPlane(emit_events=False)
    plane.register("pool", component="kv_cache", tier="hbm", nbytes=1000)
    plane.register("occupancy", component="kv_cache", tier="hbm", nbytes=600,
                   logical=True)
    assert plane.total(tier="hbm") == 1000        # the view never double-counts
    assert plane.watermark("hbm") == 1000
    snap = plane.snapshot()
    assert snap["logical"] == {"occupancy": 600}
    assert snap["tiers"]["hbm"] == 1000


def test_watermark_survives_release_and_adjust_floors_at_zero():
    plane = MemoryPlane(emit_events=False)
    plane.register("a", component="staging", tier="hbm", nbytes=100)
    plane.release("a")
    plane.register("a", component="staging", tier="hbm", nbytes=30)
    assert plane.watermark("hbm") == 100
    plane.adjust("acc", 10, component="params", tier="nvme", owner="sw")
    plane.adjust("acc", 10, component="params", tier="nvme", owner="sw")
    assert plane.total(tier="nvme", owner="sw") == 20
    plane.adjust("acc", -100, component="params", tier="nvme", owner="sw")
    assert plane.total(tier="nvme", owner="sw") == 0


def test_reconcile_tolerance_boundary():
    plane = MemoryPlane(emit_events=False)
    plane.register("p", component="params", tier="hbm", nbytes=98)
    assert plane.reconcile("exact-2pct", 100)["ok"]          # drift == -0.02
    bad = plane.reconcile("past-2pct", 100, tolerance=0.01)
    assert not bad["ok"] and bad["registered_bytes"] == 98


def test_owner_finalizer_releases_rows_on_gc():
    """Registered bytes track LIVE objects — bench's cross-phase leak
    check relies on torn-down engines releasing their rows at GC."""
    class Holder:
        pass
    with scratch_plane(emit_events=False) as plane:
        h = Holder()
        tag = owner_for(h, "Holder")
        assert owner_for(h, "Holder") == tag     # assigned once
        plane.register("x", component="params", tier="hbm", nbytes=50,
                       owner=tag)
        assert plane.total(owner=tag) == 50
        del h
        gc.collect()
        assert plane.total(owner=tag) == 0


# ------------------------------------------------------------- tier routing
def test_tier_of_default_backend_placement_is_hbm():
    """The CPU backend's DEFAULT memory kind is named 'unpinned_host' —
    it must still read as the compute tier or every CPU-mesh
    reconciliation would see zero 'hbm' bytes."""
    arr = jnp.arange(64.0)
    assert tier_of_sharding(arr.sharding) == "hbm"
    assert tier_of_leaf(arr) == "hbm"


def test_tier_of_numpy_and_nvme_leaves():
    assert tier_of_leaf(np.zeros(8)) == "host"

    class NVMeRef:                                # duck-typed by class name
        shape, dtype = (4,), np.dtype(np.float32)
    assert tier_of_leaf(NVMeRef()) == "nvme"
    assert leaf_bytes(NVMeRef()) == 16            # shape×itemsize fallback


def test_tree_bytes_counts_quantized_dicts_and_skips_scalars():
    q8 = {"__q8__": np.zeros((8, 8), np.int8),
          "scales": np.zeros((8, 1), np.float32)}
    tree = {"layer": q8, "step": 3, "flag": None}
    assert tree_bytes(tree) == 64 + 32


def test_registration_never_fetches_device_data(monkeypatch):
    """Bytes come from shapes/nbytes METADATA only (a fetch would sync the
    host with the device) — registering a placed tree must not device_get."""
    arr = jnp.arange(256.0)

    def boom(*a, **k):
        raise AssertionError("device fetch during MemoryPlane registration")
    monkeypatch.setattr(jax, "device_get", boom)
    with scratch_plane(emit_events=False) as plane:
        plane.register_tree("t", component="params", tree={"a": arr},
                            owner="o")
        assert plane.total(component="params", owner="o") == arr.nbytes


# --------------------------------------------------- engine-matrix reconcile
def _tiny(**overrides):
    cfg = llama_config("llama-tiny", dtype=jnp.float32, **overrides)
    model, params = materialize_params(cfg)
    return cfg, model, params


def _engine(model, params, **kw):
    groups.reset_topology()
    return deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                        **kw)


def test_v1_dequant_reconciles_and_registers_at_dispatch_granularity():
    """Dense params reconcile exactly; KV/workspace rows equal the same
    formulas `choose_serve_mode` uses; a repeated generate adds no rows,
    no new peaks, and no pinned recompiles (zero new hot-loop work)."""
    cfg, model, params = _tiny()
    dense = tree_bytes(params)                    # fp32 host == serving fp32
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
    with scratch_plane(emit_events=False) as plane:
        eng = _engine(model, params)
        owner = owner_for(eng, type(eng).__name__)
        res = plane.reconcile("dense_params", dense, component="params",
                              owner=owner)
        assert res["ok"], res
        eng.generate(ids, max_new_tokens=4)
        ml = round_up_len(8 + 4)
        assert plane.total(component="kv_cache", owner=owner) == \
            kv_cache_bytes(cfg, 2, ml, eng._config.dtype)
        assert plane.total(component="workspace", owner=owner) == \
            decode_workspace_bytes(cfg, 2, ml, eng._config.dtype)
        before = {a.name: (a.tier, a.nbytes) for a in plane.allocations()}
        peaks = {t: plane.watermark(t) for t in ("hbm", "host")}
        eng.generate(ids, max_new_tokens=4)       # same key: nothing moves
        after = {a.name: (a.tier, a.nbytes) for a in plane.allocations()}
        assert before == after
        assert {t: plane.watermark(t) for t in ("hbm", "host")} == peaks
        assert eng.recompiles.pinned_misses == 0


@pytest.mark.slow
def test_v1_layer_scan_reconciles_int8_at_rest_bytes():
    from deepspeed_tpu.inference.quantized_layer_scan import at_rest_bytes
    cfg, model, params = _tiny()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
    with scratch_plane(emit_events=False) as plane:
        eng = _engine(model, params, quant={"enabled": True, "group_size": 64},
                      serve_mode="layer_scan")
        owner = owner_for(eng, type(eng).__name__)
        predicted = at_rest_bytes(eng.params)["total"]
        res = plane.reconcile("int8_at_rest", predicted, component="params",
                              owner=owner)
        assert res["ok"], res
        eng.generate(ids, max_new_tokens=4)
        assert plane.total(component="kv_cache", owner=owner) > 0


@pytest.mark.slow
def test_v1_capacity_watermark_within_plan_bound():
    """Acceptance: capacity-mode registered HBM never exceeds
    CapacityPlan.peak_hbm_bytes, and the host tier carries the parked
    tree (registered vs the runner's own RAM accounting, ≤2%)."""
    cfg, model, params = _tiny()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
    with scratch_plane(emit_events=False) as plane:
        eng = _engine(model, params, serve_mode="capacity")
        owner = owner_for(eng, type(eng).__name__)
        eng.generate(ids, max_new_tokens=4)
        runner = eng._capacity
        bound = runner.plan_for(2, 8, 4).peak_hbm_bytes
        assert plane.watermark("hbm", owner=owner) <= bound
        host_pred = runner.plan.host_bytes
        res = plane.reconcile("capacity_host_tier", host_pred, tier="host",
                              owner=owner)
        assert res["ok"], res
        assert plane.total(component="staging", owner=owner) > 0


@pytest.mark.slow
def test_v2_paged_reconciles_real_cache_nbytes():
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    cfg, model, params = _tiny()
    groups.reset_topology()
    with scratch_plane(emit_events=False) as plane:
        v2 = InferenceEngineV2(model, params=params, max_batch=2,
                               max_seq_len=64, kv_layout="paged")
        owner = owner_for(v2, type(v2).__name__)
        assert plane.total(component="params", owner=owner) == \
            tree_bytes(v2.params)
        assert plane.total(component="kv_cache", owner=owner) == \
            tree_bytes(v2.cache)
        prompts = [list(range(8)), list(range(8, 16))]
        v2.generate(prompts, max_new_tokens=4)
        # logical occupancy rose during serving and returned to 0 at flush
        assert plane.snapshot()["logical"].get(f"{owner}:kv_blocks", 0) == 0
        assert v2.recompiles.pinned_misses == 0


def test_train_state_reconciles_params_and_opt_state():
    cfg, model, params = _tiny()
    from deepspeed_tpu.models.llama import (init_params_and_specs,
                                            llama_loss_fn)
    _, specs = init_params_and_specs(cfg)
    groups.reset_topology()
    with scratch_plane(emit_events=False) as plane:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1, "steps_per_print": 0,
                    "optimizer": {"type": "FusedAdam",
                                  "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3}},
            loss_fn=llama_loss_fn(model), base_param_specs=specs)
        owner = owner_for(engine, type(engine).__name__)
        st = engine.state
        assert plane.reconcile("train_params", tree_bytes(st.params),
                               component="params", owner=owner)["ok"]
        opt_pred = tree_bytes([t for t in (st.master, st.opt_state,
                                           st.scaler) if t is not None])
        assert plane.reconcile("train_opt_state", opt_pred,
                               component="opt_state", owner=owner)["ok"]


# --------------------------------------------- satellite 4: formula agreement
@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_kv_byte_formula_agreement_across_consumers(kv_dtype, batch):
    """One (model, dtype, kv_dtype, batch) point → ONE kv-byte number,
    whether read from `kv_cache_bytes`, `KVBudget.per_seq_kv_bytes`, a
    `CapacityPlan`, or the MemoryPlane's formula-registered v1 row (the
    v1 registration path IS kv_cache_bytes — pinned by the dequant
    engine test above)."""
    from deepspeed_tpu.inference.kv_block_manager import model_kv_budget
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    ml = round_up_len(48)
    direct = kv_cache_bytes(cfg, batch, ml, jnp.float32, kv_dtype=kv_dtype)
    budget = model_kv_budget(cfg, hbm_bytes=1 << 30, resident_bytes=0,
                             max_len=ml, dtype=jnp.float32,
                             kv_dtype=kv_dtype)
    assert budget.per_seq_kv_bytes * batch == direct     # linear in batch
    if kv_dtype == "int8":
        dense = kv_cache_bytes(cfg, batch, ml, jnp.float32)
        assert direct < dense                            # int8 shrinks KV


@pytest.mark.slow
def test_capacity_plan_kv_term_is_the_shared_formula():
    cfg, model, params = _tiny()
    with scratch_plane(emit_events=False):
        eng = _engine(model, params, serve_mode="capacity")
        plan = eng._capacity.plan_for(3, 16, 8)
        assert plan.kv_bytes == kv_cache_bytes(cfg, 3, round_up_len(16 + 8),
                                               eng._config.dtype)
        assert plan.workspace_bytes == decode_workspace_bytes(
            cfg, 3, round_up_len(16 + 8), eng._config.dtype)


def test_int8_kv_flips_choose_serve_mode_row():
    """The decision-table corner the accounting exists for: the same tree
    at the same HBM picks capacity with dense KV but layer_scan once the
    int8 cache shrinks the overhead — all from the one shared formula."""
    from deepspeed_tpu.inference.config import choose_serve_mode
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    ml = round_up_len(4096)
    kv_dense = kv_cache_bytes(cfg, 64, ml, jnp.float32)
    kv_int8 = kv_cache_bytes(cfg, 64, ml, jnp.float32, kv_dtype="int8")
    assert kv_int8 < kv_dense
    ws = decode_workspace_bytes(cfg, 64, ml, jnp.float32)
    int8_b, layer_b, dense_b = 100 * MB, 5 * MB, 200 * MB
    hbm = int((int8_b + layer_b + ws + (kv_int8 + kv_dense) // 2) / 0.8)

    def mode(kv):
        return choose_serve_mode(
            quantized=True, layout_ok=True, multi_device=False,
            dense_bytes=dense_b, int8_bytes=int8_b, layer_bytes=layer_b,
            kv_bytes=kv, workspace_bytes=ws, hbm_bytes=hbm)
    assert mode(kv_dense) == "capacity"
    assert mode(kv_int8) == "layer_scan"
