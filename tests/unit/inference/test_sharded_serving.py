"""Multi-device serving (r7): layer_scan on a pure-TP mesh rides the
shard_map int8 kernel wrappers instead of falling back to dequant; the
auto decision table aggregates HBM over the mesh; unsupported meshes fall
back LOUDLY; span/recompile program names carry the mesh fingerprint
(single-device names unchanged — stability contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.config import choose_serve_mode
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import MeshTopology

GB = 1 << 30


def _tp_topology(tp=2):
    groups.reset_topology()
    return groups.initialize(MeshTopology(tp=tp, devices=jax.devices()[:tp]))


def _quant_engine(serve_mode="layer_scan", **extra):
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    return deepspeed_tpu.init_inference(
        model, params=params, dtype="fp32",
        quant={"enabled": True, "group_size": 64},
        serve_mode=serve_mode, **extra)


# ---------------------------------------------- choose_serve_mode (pure)

def _bytes_7b():
    # 7B-class shape: dense 13.5 GB, int8 ~7 GB, 16 GB/device HBM
    return dict(dense_bytes=int(13.5 * GB), int8_bytes=7 * GB,
                layer_bytes=int(0.42 * GB), kv_bytes=1 * GB,
                workspace_bytes=int(0.5 * GB), hbm_bytes=16 * GB)


def test_choose_serve_mode_aggregates_hbm_over_mesh():
    # single device: int8 layer scan fits, dense dequant would crowd
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=False, **_bytes_7b()) == "layer_scan"
    # the r7 bugfix row: same tree on a 2-chip TP mesh must STAY on
    # layer_scan (sharded kernels), not fall to capacity/dequant
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=True, n_devices=2,
                             tp_shardable=True, **_bytes_7b()) == "layer_scan"
    # 4 chips: aggregate HBM clears the dequant crowding bound (0.5·64 GB)
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=True, n_devices=4,
                             tp_shardable=True, **_bytes_7b()) == "dequant"
    # multi-device but NOT tp-shardable: layer_scan unavailable → dequant
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=True, n_devices=2,
                             tp_shardable=False, **_bytes_7b()) == "dequant"


def test_choose_serve_mode_multi_device_last_resort_is_layer_scan():
    # nothing fits, capacity is single-device-only → layer_scan (it at
    # least shards the weights), never a silent wrong "capacity"
    big = dict(dense_bytes=200 * GB, int8_bytes=100 * GB,
               layer_bytes=3 * GB, kv_bytes=2 * GB,
               workspace_bytes=1 * GB, hbm_bytes=16 * GB)
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=True, n_devices=2,
                             tp_shardable=True, **big) == "layer_scan"
    assert choose_serve_mode(quantized=True, layout_ok=True,
                             multi_device=False, **big) == "capacity"


# ------------------------------------------------- engine on a TP mesh

@pytest.mark.slow
def test_tp2_layer_scan_no_dequant_fallback_and_parity():
    """Acceptance: serve_mode='layer_scan' on a 2-device mesh keeps the
    layer-scan path (the pre-r7 engine forced dequant on ANY multi-device
    mesh) and matches single-device serving. Row-parallel matmuls psum in
    a different reduction order, so compare logits to tolerance and
    demand near-total token agreement, not bit-equality."""
    groups.reset_topology()
    ref = _quant_engine()
    assert ref.serve_mode == "layer_scan"
    ids = np.random.default_rng(0).integers(0, 256, (2, 8))
    ref_logits = np.asarray(ref.forward(ids))
    ref_toks = np.asarray(ref.generate(ids, max_new_tokens=6))

    _tp_topology()
    tp = _quant_engine()
    assert tp.serve_mode == "layer_scan"
    got_logits = np.asarray(tp.forward(ids))
    np.testing.assert_allclose(got_logits, ref_logits,
                               atol=1e-4 * np.abs(ref_logits).max())
    got_toks = np.asarray(tp.generate(ids, max_new_tokens=6))
    assert got_toks.shape == ref_toks.shape
    assert (got_toks == ref_toks).mean() > 0.9


@pytest.mark.slow
def test_tp2_fused_layer_scan_runs_sharded_kernel(monkeypatch):
    """The fused path on a TP mesh must actually invoke the shard_map
    int8 kernel wrapper (spied), not silently take the naive dequant
    matmul, and still generate the same tokens as the naive TP engine."""
    from deepspeed_tpu.ops.pallas import quantized_matmul as qmm
    calls = []
    real = qmm.sharded_quantized_matmul

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)
    monkeypatch.setattr(qmm, "sharded_quantized_matmul", spy)

    ids = np.random.default_rng(1).integers(0, 256, (2, 8))
    _tp_topology()
    naive = _quant_engine(fused_int8=False)
    a = np.asarray(naive.generate(ids, max_new_tokens=4))
    _tp_topology()
    fused = _quant_engine(fused_int8=True)
    assert fused.serve_mode == "layer_scan"
    b = np.asarray(fused.generate(ids, max_new_tokens=4))
    assert calls, "TP fused layer_scan never reached the sharded kernel"
    assert a.shape == b.shape == (2, 12)
    assert (a == b).mean() > 0.9


@pytest.mark.slow
def test_unsupported_mesh_falls_back_to_dequant_loudly(tmp_path):
    """layer_scan requested on a mesh with a second nontrivial axis: the
    engine serves dequant and says so (WARN + kernel_fallback event)."""
    import json
    from deepspeed_tpu.ops.pallas import sharded
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    groups.reset_topology()
    groups.initialize(MeshTopology(ep=4, devices=jax.devices()))  # +data2
    sharded._WARNED.clear()
    hub = set_hub(TelemetryHub(enabled=True,
                               jsonl_path=str(tmp_path / "f.jsonl")))
    try:
        eng = _quant_engine(serve_mode="layer_scan")
        hub.flush()
    finally:
        set_hub(TelemetryHub(enabled=False))
    assert eng.serve_mode == "dequant"
    events = [json.loads(l) for l in open(tmp_path / "f.jsonl")]
    falls = [e for e in events if e["kind"] == "kernel_fallback"]
    assert falls and falls[0]["kernel"] == "quantized_matmul"


def test_tp_cache_shardings_head_shard_vs_replicated():
    """v2 cache pinning: on a pure-TP mesh the pools/caches pin with the
    KV-head dim over 'model' (the at-rest layout the sharded decode
    kernels read); indivisible heads or mixed meshes pin replicated."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.inference.kv_cache import (
        KVCache, PagedKVCache, tp_cache_shardings)
    topo = _tp_topology()
    dense = KVCache.create(num_layers=2, batch=2, max_len=16,
                           kv_heads=4, head_dim=8, dtype=jnp.float32)
    pins = tp_cache_shardings(dense, topo.mesh)
    assert pins.k.spec == P(None, None, None, "model", None)
    assert pins.index.spec == P()
    paged = PagedKVCache.create(num_layers=2, batch=2, max_len=16,
                                kv_heads=4, head_dim=8, num_blocks=8,
                                block_size=4, dtype=jnp.float32, staged=True)
    pins = tp_cache_shardings(paged, topo.mesh)
    assert pins.k.pool.spec == P(None, "model", None, None, None)
    assert pins.k.stage.spec == P(None, None, "model", None)
    assert pins.k.tables.spec == P()
    # KV heads don't divide tp → everything replicated (bare kernels)
    odd = KVCache.create(num_layers=1, batch=2, max_len=16,
                         kv_heads=3, head_dim=8, dtype=jnp.float32)
    pins = tp_cache_shardings(odd, topo.mesh)
    assert pins.k.spec == P()
    # mixed mesh → replicated
    groups.reset_topology()
    topo = groups.initialize(MeshTopology(ep=4, devices=jax.devices()))
    pins = tp_cache_shardings(dense, topo.mesh)
    assert pins.k.spec == P()


@pytest.mark.slow
def test_tp2_program_names_carry_mesh_fingerprint():
    """Recompile-detector program identities gain '@model2' on the TP
    mesh; a second same-key generate is still a pinned-program hit.
    (Single-device names are covered by the existing pin test —
    unchanged, the stability contract.)"""
    _tp_topology()
    eng = _quant_engine()
    ids = np.random.default_rng(2).integers(0, 256, (2, 6))
    eng.generate(ids, max_new_tokens=3)
    eng.generate(ids, max_new_tokens=3)
    assert any(p.startswith("layer_scan@model2:")
               for p in eng.recompiles._seen)
    assert eng.recompiles.misses == 0
    assert eng._program_name((2, 6, 3, None)).endswith("@model2")
