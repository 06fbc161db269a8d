"""ZeRO-Inference capacity serve mode tests (inference/capacity_scan.py).

The contracts this file pins:
- capacity-mode generate() is BIT-EXACT vs the resident engine (bf16-path
  and int8), with layer params verifiably host-resident between steps;
- the double-buffer prefetch dispatches layer l+1's transfer BEFORE layer
  l's result is awaited (the overlap that makes decode PCIe-bound);
- HBM peak accounting: plan.peak == resident + 2·slice + KV + workspace
  with each term matching the real placement;
- the `auto` serve-mode decision table accounts KV + workspace bytes;
- serving telemetry carries h2d_bytes_step / prefetch_stall_ms and the
  capacity programs are pinned in the RecompileDetector.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import capacity_scan
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.utils import groups

MB = 1 << 20
GB = 1 << 30


def _tiny(**overrides):
    cfg = llama_config("llama-tiny", dtype=jnp.float32, **overrides)
    return materialize_params(cfg)


def _engine(model, params, **kw):
    groups.reset_topology()
    return deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                        **kw)


# ------------------------------------------------------------------- parity
def test_capacity_generate_matches_resident_bf16_path():
    """Acceptance: capacity generate() == resident engine token for token
    on the unquantized path (greedy AND sampling); plain forward within 4
    float32 ulps OF THE LARGEST LOGIT. The two forwards are two differently
    compiled programs (one whole-model scan against a host-driven loop over
    a block program), whose float32 sums the compiler may order differently:
    the driver read 1.19e-07 between them on every run since the seed, which
    is 2 ulps of the largest logit (0.63) here. A logit is a sum of products
    that cancel, so its error scales with the terms and not with the result:
    an element-wise ulp count reads 16383 on a logit near zero, and bit
    equality of logits was never a contract a compiler keeps."""
    model, params = _tiny()
    ids = np.random.default_rng(0).integers(0, 256, (2, 8))
    ref = _engine(model, params)
    cap = _engine(model, params, serve_mode="capacity")
    assert ref.serve_mode == "dequant" and cap.serve_mode == "capacity"
    np.testing.assert_array_equal(
        np.asarray(ref.generate(ids, max_new_tokens=6)),
        np.asarray(cap.generate(ids, max_new_tokens=6)))
    np.testing.assert_array_equal(
        np.asarray(ref.generate(ids, max_new_tokens=4, temperature=0.7,
                                top_k=8, seed=3)),
        np.asarray(cap.generate(ids, max_new_tokens=4, temperature=0.7,
                                top_k=8, seed=3)))
    a, b = np.asarray(ref.forward(ids)), np.asarray(cap.forward(ids))
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=4 * np.spacing(np.abs(a).max()))


@pytest.mark.slow
def test_capacity_generate_matches_resident_int8():
    """int8 variant: the host-side per-layer quantization is the same
    function (and same post-cast values) the resident layer-scan engine
    uses, so capacity is BIT-EXACT vs resident layer_scan on any prompt —
    including the sampling path. (The whole-tree dequant engine also
    quantizes embed/lm_head, which layer-stacked modes keep full precision,
    so cross-checking against it uses the r6 contract prompt where the
    near-tie-free argmax agrees.)"""
    model, params = _tiny()
    quant = {"enabled": True, "group_size": 64}
    ls = _engine(model, params, quant=quant, serve_mode="layer_scan")
    cap = _engine(model, params, quant=quant, serve_mode="capacity")
    assert ls.serve_mode == "layer_scan" and cap.serve_mode == "capacity"
    ids = np.random.default_rng(1).integers(0, 256, (2, 8))
    np.testing.assert_array_equal(
        np.asarray(ls.generate(ids, max_new_tokens=6)),
        np.asarray(cap.generate(ids, max_new_tokens=6)))
    np.testing.assert_array_equal(
        np.asarray(ls.generate(ids, max_new_tokens=4, temperature=0.7,
                               top_k=8, seed=3)),
        np.asarray(cap.generate(ids, max_new_tokens=4, temperature=0.7,
                                top_k=8, seed=3)))
    ids0 = np.random.default_rng(0).integers(0, 256, (2, 8))
    ref = _engine(model, params, quant=quant, serve_mode="dequant")
    np.testing.assert_array_equal(
        np.asarray(ref.generate(ids0, max_new_tokens=6)),
        np.asarray(cap.generate(ids0, max_new_tokens=6)))


@pytest.mark.slow
def test_capacity_sync_staging_parity():
    """`double_buffer: false` (the A/B baseline) is the same math, only
    the staging schedule changes."""
    model, params = _tiny()
    ids = np.random.default_rng(2).integers(0, 256, (2, 6))
    ref = _engine(model, params)
    sync = _engine(model, params, serve_mode="capacity",
                   capacity={"double_buffer": False})
    assert sync._capacity.double_buffer is False
    np.testing.assert_array_equal(
        np.asarray(ref.generate(ids, max_new_tokens=5)),
        np.asarray(sync.generate(ids, max_new_tokens=5)))


# ---------------------------------------------------------------- residency
def test_capacity_params_host_resident_between_steps():
    """The engine's layer tier must live in HOST memory (plain numpy — not
    jax device arrays) before, between and after generates; only
    embed/norm/head are device-resident."""
    model, params = _tiny()
    cap = _engine(model, params, serve_mode="capacity")
    runner = cap._capacity

    def assert_host():
        assert runner.host_resident()
        for lt in cap.params["layers"]:
            for leaf in jax.tree_util.tree_leaves(lt):
                assert isinstance(leaf, np.ndarray)
                assert not isinstance(leaf, jax.Array)

    assert_host()
    ids = np.random.default_rng(0).integers(0, 256, (2, 6))
    cap.generate(ids, max_new_tokens=3)
    assert_host()
    cap.generate(ids, max_new_tokens=3)
    assert_host()
    # the resident tier IS on device
    for leaf in jax.tree_util.tree_leaves(runner.resident):
        assert isinstance(leaf, jax.Array)


# ----------------------------------------------------------- prefetch order
def test_prefetch_dispatched_before_result_awaited(monkeypatch):
    """Acceptance: layer l+1's transfer is DISPATCHED before layer l's
    slice is awaited, and before layer l's block RESULT is awaited — the
    double-buffer overlap contract."""
    events = []
    orig_transfer = capacity_scan.CapacityRunner._transfer_layer

    def transfer_layer(self, l):
        events.append(("transfer", l))
        return orig_transfer(self, l)

    monkeypatch.setattr(capacity_scan.CapacityRunner, "_transfer_layer",
                        transfer_layer)
    awaited_transfers = []
    monkeypatch.setattr(
        capacity_scan, "_await_transfer",
        lambda tree: events.append(("await_transfer",
                                    len(awaited_transfers))) or
        awaited_transfers.append(1))
    results = []
    monkeypatch.setattr(
        capacity_scan, "_await_result",
        lambda tree: events.append(("await_result", len(results))) or
        results.append(1))

    model, params = _tiny(num_hidden_layers=4)
    cap = _engine(model, params, serve_mode="capacity")
    ids = np.random.default_rng(0).integers(0, 256, (2, 6))
    cap.generate(ids, max_new_tokens=1)  # one pass, L=4

    first = {}
    for i, ev in enumerate(events):
        first.setdefault(ev, i)
    L = 4
    for l in range(L - 1):
        # transfer l+1 dispatched before the (prefetched) slice l is awaited
        assert first[("transfer", l + 1)] < first[("await_transfer", l)], \
            events
    # ... and before layer l's block result is awaited (await_result k is
    # layer k's output, awaited one iteration later by the throttle)
    for k in range(L - 1):
        assert first[("transfer", k + 1)] < first[("await_result", k)], \
            events


def test_sync_mode_never_prefetches(monkeypatch):
    """The A/B baseline stages layer l only at iteration l — transfer l+1
    is dispatched strictly AFTER layer l's result await."""
    events = []
    orig_transfer = capacity_scan.CapacityRunner._transfer_layer

    def transfer_layer(self, l):
        events.append(("transfer", l))
        return orig_transfer(self, l)

    monkeypatch.setattr(capacity_scan.CapacityRunner, "_transfer_layer",
                        transfer_layer)
    results = []
    monkeypatch.setattr(
        capacity_scan, "_await_result",
        lambda tree: events.append(("await_result", len(results))) or
        results.append(1))
    model, params = _tiny(num_hidden_layers=4)
    sync = _engine(model, params, serve_mode="capacity",
                   capacity={"double_buffer": False})
    ids = np.random.default_rng(0).integers(0, 256, (2, 6))
    sync.generate(ids, max_new_tokens=1)
    first = {}
    for i, ev in enumerate(events):
        first.setdefault(ev, i)
    for l in range(3):
        assert first[("await_result", l)] < first[("transfer", l + 1)], \
            events


# ------------------------------------------------------------- HBM accounting
def test_capacity_plan_matches_documented_formula():
    """Acceptance: peak ≈ 2 layer slices + KV + workspace (+ the resident
    embed/norm/head), each term recomputed here from first principles and
    asserted against the placement plan."""
    model, params = _tiny(num_hidden_layers=8)
    cfg = model.cfg
    cap = _engine(model, params, serve_mode="capacity")
    runner = cap._capacity
    b, s, new = 2, 8, 8
    plan = runner.plan_for(b, s, new)

    # slice term: the largest per-layer host slice actually parked
    per_layer = [sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(lt))
                 for lt in cap.params["layers"]]
    assert plan.slice_bytes == max(per_layer)
    # resident term: exactly the device-placed non-layer leaves
    assert plan.resident_bytes == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(runner.resident))
    # KV term: 2 (K+V) · L · B · M · Hkv · D · itemsize at the key's shapes
    max_len = capacity_scan.round_up_len(s + new)
    item = jnp.dtype(cap.config.dtype).itemsize
    assert plan.kv_bytes == (2 * cfg.num_hidden_layers * b * max_len
                             * cfg.num_key_value_heads * cfg.head_dim * item)
    # workspace term: the documented activation + logits formula
    assert plan.workspace_bytes == (
        b * max_len * (2 * cfg.hidden_size + 2 * cfg.intermediate_size)
        * item + b * cfg.vocab_size * 4)
    # the peak formula itself
    assert plan.peak_hbm_bytes == (plan.resident_bytes + 2 * plan.slice_bytes
                                   + plan.kv_bytes + plan.workspace_bytes)
    # capacity peak undercuts the resident tree + KV + workspace whenever
    # there are >2 layers' worth of weights to stream
    dense = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    assert plan.resident_bytes + 2 * plan.slice_bytes < dense


def test_capacity_weight_bytes_accounting():
    """h2d_bytes_step = one full sweep of host slices; weight_bytes_step
    adds the device-resident final-norm + lm_head reads (embedding gather
    excluded), mirroring the layer-scan accounting."""
    model, params = _tiny()
    cap = _engine(model, params, serve_mode="capacity")
    runner = cap._capacity
    h2d = runner.h2d_bytes_pass()
    assert h2d == sum(
        leaf.nbytes for lt in cap.params["layers"]
        for leaf in jax.tree_util.tree_leaves(lt))
    wb, wb_dense = cap._weight_bytes_per_step()
    norm_head = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            {"norm": runner.resident["norm"],
             "head": runner.resident.get("lm_head")}))
    assert wb == h2d + norm_head
    assert wb_dense >= wb  # equal when unquantized
    # int8 halves what streams
    q = _engine(model, params, serve_mode="capacity",
                quant={"enabled": True, "group_size": 64})
    qwb, qwb_dense = q._weight_bytes_per_step()
    assert 0 < qwb < qwb_dense


# ----------------------------------------------------------- auto decision
def test_serve_mode_auto_decision_table():
    """Satellite: the `auto` rule accounts KV + workspace bytes, not just
    weight residency — each row of the documented table."""
    from deepspeed_tpu.inference.config import choose_serve_mode
    base = dict(quantized=True, layout_ok=True, multi_device=False,
                dense_bytes=13 * GB, int8_bytes=7 * GB,
                layer_bytes=420 * MB, kv_bytes=150 * MB,
                workspace_bytes=200 * MB, hbm_bytes=16 * GB)
    # no HBM size → can't account → dequant (resident)
    assert choose_serve_mode(**{**base, "hbm_bytes": 0}) == "dequant"
    # tiny quantized model → whole-tree dequant
    assert choose_serve_mode(**{**base, "dense_bytes": 400 * MB,
                                "int8_bytes": 120 * MB,
                                "layer_bytes": 20 * MB,
                                "kv_bytes": 10 * MB,
                                "workspace_bytes": 10 * MB}) == "dequant"
    # 7B int8 on a 16 GB v5e → layer_scan (the r6 measured boundary)
    assert choose_serve_mode(**base) == "layer_scan"
    # 30B-class int8 (int8 tree alone crowds HBM) → capacity
    assert choose_serve_mode(**{**base, "dense_bytes": 60 * GB,
                                "int8_bytes": 30 * GB,
                                "layer_bytes": 1 * GB}) == "capacity"
    # KV/workspace flip the SAME weights from layer_scan to capacity:
    # an int8 tree that fits alone but not beside a long-context cache
    assert choose_serve_mode(**{**base, "int8_bytes": 11 * GB,
                                "kv_bytes": 3 * GB}) == "capacity"
    assert choose_serve_mode(**{**base, "int8_bytes": 11 * GB,
                                "kv_bytes": 100 * MB}) == "layer_scan"
    # unquantized: resident while it fits (the proven 162 tok/s 7B path) …
    assert choose_serve_mode(**{**base, "quantized": False}) == "dequant"
    # … capacity once it can't (70B bf16), unless KV shrinks it back
    assert choose_serve_mode(**{**base, "quantized": False,
                                "dense_bytes": 140 * GB}) == "capacity"
    # and KV pushes a borderline resident tree over the edge
    assert choose_serve_mode(**{**base, "quantized": False,
                                "dense_bytes": 14 * GB,
                                "kv_bytes": 2 * GB}) == "capacity"
    # streaming unsupported → dequant regardless of size
    assert choose_serve_mode(**{**base, "dense_bytes": 60 * GB,
                                "layout_ok": False}) == "dequant"
    assert choose_serve_mode(**{**base, "dense_bytes": 60 * GB,
                                "multi_device": True}) == "dequant"


def test_serve_mode_auto_kv_dtype_rows():
    """r8 rows: `kv_cache_dtype` feeds the SAME decision table through
    `kv_cache_bytes(..., kv_dtype=)` — a long-context cache that tips a
    7B int8 tree off-device at bf16 KV stays resident at int8 KV."""
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    from deepspeed_tpu.inference.config import choose_serve_mode

    class C:  # 7B-class dims
        num_hidden_layers = 32
        num_key_value_heads = 32
        num_attention_heads = 32
        hidden_size = 4096
        intermediate_size = 11008
        vocab_size = 32000
        head_dim = 128

    kv_dense = kv_cache_bytes(C, 4, 4096, jnp.bfloat16)
    kv_int8 = kv_cache_bytes(C, 4, 4096, jnp.bfloat16, kv_dtype="int8")
    # the accounting contract: ≤ half + the 4/head_dim scale overhead
    assert kv_int8 <= kv_dense // 2 + kv_dense * 4 // (2 * C.head_dim) + 1
    base = dict(quantized=True, layout_ok=True, multi_device=False,
                dense_bytes=13 * GB, int8_bytes=7 * GB + 800 * MB,
                layer_bytes=420 * MB, workspace_bytes=400 * MB,
                hbm_bytes=16 * GB)
    assert choose_serve_mode(**base, kv_bytes=kv_dense) == "capacity"
    assert choose_serve_mode(**base, kv_bytes=kv_int8) == "layer_scan"


def test_engine_auto_picks_capacity_when_nothing_fits(monkeypatch):
    """Engine-level auto: with a (faked) accelerator memory so small that
    neither the resident tree nor the int8 layer scan fits beside KV +
    workspace, auto resolves to capacity."""
    from deepspeed_tpu.accelerator import get_accelerator
    acc = get_accelerator()
    monkeypatch.setattr(acc, "total_memory", lambda: 2 * MB)
    model, params = _tiny()
    cap = _engine(model, params, serve_mode="auto")
    assert cap.serve_mode == "capacity"
    q = _engine(model, params, serve_mode="auto",
                quant={"enabled": True, "group_size": 64})
    assert q.serve_mode == "capacity"
    # plenty of memory → resident, exactly as before
    monkeypatch.setattr(acc, "total_memory", lambda: 16 * GB)
    big = _engine(model, params, serve_mode="auto")
    assert big.serve_mode == "dequant"


def test_capacity_fallback_on_unsupported_tree():
    """Non-llama layouts fall back to dequant (resident) with a warning,
    mirroring layer_scan's gate — gpt2's tree has no self_attn/mlp split."""
    from deepspeed_tpu.models.gpt2 import gpt2_config, init_gpt2
    cfg = gpt2_config("gpt2-tiny", dtype=jnp.float32)
    model, params, _ = init_gpt2(cfg)
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                       serve_mode="capacity")
    assert eng.serve_mode == "dequant"
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 6))
    assert np.asarray(eng.generate(ids, max_new_tokens=3)).shape == (2, 9)


# ---------------------------------------------------------------- NVMe tier
def test_capacity_nvme_tier_parity(tmp_path):
    """The coldest layers park on NVMe through the aio engine and stream
    back per pass — same tokens, bytes actually on disk, RAM tier smaller."""
    try:
        from deepspeed_tpu.op_builder import AsyncIOBuilder
        AsyncIOBuilder().load()
    except Exception as e:  # pragma: no cover - env without a compiler
        pytest.skip(f"aio engine unavailable: {e}")
    model, params = _tiny()
    ids = np.random.default_rng(3).integers(0, 256, (2, 6))
    ref = _engine(model, params)
    a = np.asarray(ref.generate(ids, max_new_tokens=5))
    nv = _engine(model, params, serve_mode="capacity",
                 capacity={"nvme_dir": str(tmp_path), "nvme_layers": 1})
    runner = nv._capacity
    assert runner.plan.nvme_layers == 1 and runner.plan.nvme_bytes > 0
    swps = [f for f in os.listdir(tmp_path) if f.endswith(".swp")]
    assert swps, "no swap files written"
    assert len(runner._ram) == runner.num_layers - 1
    np.testing.assert_array_equal(
        a, np.asarray(nv.generate(ids, max_new_tokens=5)))
    # second generate re-reads the parked layers from disk
    np.testing.assert_array_equal(
        a, np.asarray(nv.generate(ids, max_new_tokens=5)))


# ---------------------------------------------------------------- telemetry
def test_capacity_serving_telemetry_and_pinning(tmp_path):
    """Satellite: serving events carry h2d_bytes_step + prefetch_stall_ms
    (host-side accounting, no extra device fetches) and the capacity
    program is pinned — repeat generates are cache hits."""
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    hub = set_hub(TelemetryHub(enabled=True,
                               jsonl_path=str(tmp_path / "s.jsonl")))
    try:
        model, params = _tiny()
        cap = _engine(model, params, serve_mode="capacity")
        ids = np.random.default_rng(0).integers(0, 256, (2, 6))
        cap.generate(ids, max_new_tokens=3)
        cap.generate(ids, max_new_tokens=3)
    finally:
        set_hub(TelemetryHub(enabled=False))
    events = [json.loads(l) for l in open(tmp_path / "s.jsonl")]
    serving = [e for e in events if e["kind"] == "serving"]
    assert serving
    rec = serving[-1]
    assert rec["serve_mode"] == "capacity"
    assert rec["h2d_bytes_step"] == cap._capacity.h2d_bytes_pass() > 0
    assert rec["prefetch_stall_ms"] >= 0
    assert 0 < rec["weight_bytes_step"] <= rec["weight_bytes_step_dense"]
    assert cap.recompiles.pinned_default is True
    assert any(p.startswith("capacity:") for p in cap.recompiles._seen)
    assert cap.recompiles.misses == 0


# ------------------------------------------------------------ checkpoint e2e
@pytest.mark.slow
def test_hf_checkpoint_to_capacity_serve(tmp_path):
    """End-to-end at tiny scale: on-disk HF checkpoint (sharded safetensors
    + index) → converter → capacity engine, parity vs the resident engine —
    the `hf7b_decode.py --capacity` path."""
    pytest.importorskip("safetensors")
    import benchmarks.hf7b_decode as hf
    tiny = dict(hf.CFG, vocab_size=128, hidden_size=64,
                intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4)
    old = hf.CFG
    hf.CFG = tiny
    try:
        hf.synthesize(str(tmp_path))
    finally:
        hf.CFG = old
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32,
                                       param_dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 128, (2, 6))
    ref = _engine(model, params)
    a = np.asarray(ref.generate(ids, max_new_tokens=4))
    cap = _engine(model, params, serve_mode="capacity")
    np.testing.assert_array_equal(
        a, np.asarray(cap.generate(ids, max_new_tokens=4)))
    qcap = _engine(model, params, serve_mode="capacity",
                   quant={"enabled": True, "group_size": 64})
    qref = _engine(model, params, serve_mode="dequant",
                   quant={"enabled": True, "group_size": 64})
    np.testing.assert_array_equal(
        np.asarray(qref.generate(ids, max_new_tokens=4)),
        np.asarray(qcap.generate(ids, max_new_tokens=4)))
