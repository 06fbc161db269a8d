"""Unified telemetry tests (r6 tentpole): in-step MetricsState computed in
the compiled step and delivered WITH the loss in one host fetch; MoE router
load/drop telemetry; the recompile detector (unit + a deliberately
perturbed pinned serving program); TelemetryHub JSONL/Prometheus; the
summarizer CLI."""

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import groups
from tests.simple_model import simple_params, base_config


def _mse_loss_fn(model):
    return lambda p, b, r: model.apply({"params": p}, b["x"], b["y"])


def _engine(tmp_path=None, stage=3, gas=2, flush_every=1, **extra):
    groups.reset_topology()
    model, params = simple_params()
    cfg = base_config(stage=stage, mbs=1, gas=gas, **extra)
    if tmp_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "jsonl_path": str(tmp_path / "run.jsonl"),
                            "prometheus_path": str(tmp_path / "prom.txt"),
                            "flush_every": flush_every}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, loss_fn=_mse_loss_fn(model),
        config=cfg)
    return engine, model


def _batch(engine, gas, rows_per_micro=None, seed=0):
    rng = np.random.default_rng(seed)
    rows = (rows_per_micro or engine.topology.dense_dp_size) * gas
    return {"x": rng.standard_normal((rows, 8)).astype(np.float32),
            "y": rng.standard_normal((rows, 8)).astype(np.float32)}


# --------------------------------------------------------------- MetricsState
def test_metrics_state_parity_with_host_reference():
    """Acceptance: grad norm (and param norm) from the in-step MetricsState
    equal a host-side reference computed from the same initial params —
    the engine accumulates grad(loss_i / GAS) over the window's micros."""
    gas = 2
    engine, model = _engine(stage=3, gas=gas)
    params0 = jax.device_get(engine.state.params)
    batch = _batch(engine, gas)

    engine.train_batch(batch=batch)
    m = engine.last_metrics

    loss_fn = _mse_loss_fn(model)
    # engine folds the flat batch to (gas, rows/gas, ...): micro i is the
    # i-th contiguous row block
    rows = batch["x"].shape[0] // gas
    ref = None
    for i in range(gas):
        mb = {k: v[i * rows:(i + 1) * rows] for k, v in batch.items()}
        g = jax.grad(lambda p: loss_fn(p, mb, None)[0] / gas)(params0)
        ref = g if ref is None else jax.tree_util.tree_map(
            lambda a, b_: a + b_, ref, g)
    ref_norm = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(l.astype(jnp.float32)))
        for l in jax.tree_util.tree_leaves(ref))))
    param_norm0 = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(jnp.asarray(l, jnp.float32)))
        for l in jax.tree_util.tree_leaves(params0))))

    np.testing.assert_allclose(m["grad_norm"], ref_norm, rtol=1e-4)
    np.testing.assert_allclose(m["param_norm"], param_norm0, rtol=1e-5)
    assert m["global_step"] == 1
    assert m["overflow"] is False and m["skipped_steps"] == 0
    assert m["loss_scale"] == 1.0
    # engine accessor rides the same in-step value — no extra program run
    np.testing.assert_allclose(engine.get_global_grad_norm(), ref_norm,
                               rtol=1e-4)


def test_metrics_single_fetch_per_step(tmp_path, monkeypatch):
    """Acceptance: metrics are delivered WITH the loss in a single host
    fetch — exactly one jax.device_get per step at flush_every=1, whose
    payload carries both, and no other device round-trips."""
    engine, _ = _engine(tmp_path, stage=3, gas=2, flush_every=1)
    batch = _batch(engine, 2)
    engine.train_batch(batch=batch)  # compile outside the counted window

    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)
    monkeypatch.setattr(jax, "device_get", counting)
    for _ in range(3):
        engine.train_batch(batch=batch)
    monkeypatch.undo()

    assert len(calls) == 3  # ONE fetch per step, nothing else
    for payload in calls:
        loss, metrics = payload[0]  # batched [(loss, MetricsState)]
        assert loss is not None and metrics is not None

    lines = [json.loads(l) for l in
             open(tmp_path / "run.jsonl") if l.strip()]
    steps = [e for e in lines if e["kind"] == "train_step"]
    assert len(steps) == 4
    for e in steps:
        assert "loss" in e and "grad_norm" in e and "param_norm" in e
    # dispatch-to-dispatch step time appears from the second step on
    assert any("step_time_s" in e for e in steps[1:])
    # prometheus exposition refreshed at flush
    prom = open(tmp_path / "prom.txt").read()
    assert "deepspeed_tpu_steps_total" in prom
    assert "deepspeed_tpu_grad_norm" in prom


@pytest.mark.slow
def test_moe_router_metrics_in_step():
    """Acceptance: an MoE family reports per-layer router load/drop from
    inside the compiled step. Load is the fraction of T·k assignments per
    expert (sums to 1 per layer on the ragged path); drop ∈ [0, 1]."""
    from deepspeed_tpu.models.qwen2_moe import (
        init_qwen2_moe, qwen2_moe_config, qwen2_moe_loss_fn)
    groups.reset_topology()
    cfg = qwen2_moe_config("qwen2moe-tiny", dtype=jnp.float32)
    model, params, specs = init_qwen2_moe(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, base_param_specs=specs,
        loss_fn=qwen2_moe_loss_fn(model),
        config=base_config(stage=0, mbs=1, gas=1, lr=1e-3))
    rng = np.random.default_rng(0)
    dp = engine.topology.dense_dp_size
    batch = {"input_ids": rng.integers(0, cfg.vocab_size,
                                       size=(dp, 16)).astype(np.int32)}
    engine.train_batch(batch=batch)
    m = engine.last_metrics

    load = np.asarray(m["router_load"])
    drop = np.asarray(m["router_drop"])
    assert load.shape == (cfg.num_hidden_layers, cfg.num_experts)
    assert drop.shape == (cfg.num_hidden_layers,)
    np.testing.assert_allclose(load.sum(axis=1), 1.0, rtol=1e-5)
    assert ((drop >= 0.0) & (drop <= 1.0)).all()
    assert m["moe_aux_loss"] > 0.0
    assert m["lm_loss"] > 0.0


# ---------------------------------------------------------- recompile detector
@pytest.fixture
def _propagating_logger(monkeypatch):
    # the DeepSpeedTPU logger writes to its own stdout handler with
    # propagate=False — let records reach the root so caplog sees them
    from deepspeed_tpu.utils.logging import logger as ds_logger
    monkeypatch.setattr(ds_logger, "propagate", True)


def test_recompile_detector_unit(caplog, _propagating_logger):
    """Satellite: same-shape call → 0 misses, new shape → 1; pinned misses
    warn."""
    from deepspeed_tpu.telemetry import RecompileDetector
    det = RecompileDetector("unit")
    x = jnp.zeros((2, 2))
    assert det.observe("p", (x,)) is False          # first = the compile
    assert det.observe("p", (jnp.zeros((2, 2)),)) is False
    assert det.misses == 0 and det.compiles == 1
    assert det.observe("p", (jnp.zeros((3, 2)),)) is True
    assert det.misses == 1
    # dtype changes are cache misses too
    assert det.observe("p", (jnp.zeros((3, 2), jnp.int32),)) is True
    assert det.misses == 2 and det.pinned_misses == 0

    with caplog.at_level(logging.WARNING):
        det.observe("p", (jnp.zeros((4, 2)),), pinned=True)
    assert det.pinned_misses == 1
    assert "pinned program 'p'" in caplog.text
    assert det.stats()["programs"] == 1


def test_recompile_miss_reports_changed_components(
        caplog, _propagating_logger, tmp_path):
    """A pinned miss names WHICH signature components moved vs the first
    dispatch (shape/dtype/sharding/committed) — in the warning text and
    the `recompile` event's `changed` field."""
    from deepspeed_tpu.telemetry import RecompileDetector, TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(tmp_path / "r.jsonl")))
    try:
        det = RecompileDetector("unit", pinned_default=True)
        det.observe("p", (jnp.zeros((2, 2)),))
        with caplog.at_level(logging.WARNING):
            det.observe("p", (jnp.zeros((3, 2)),))             # shape only
            det.observe("p", (jnp.zeros((3, 2), jnp.int32),))  # + dtype
            det.observe("p", ("static-arg",))                  # structure-ish
    finally:
        set_hub(TelemetryHub(enabled=False))
    assert "changed: shape" in caplog.text
    assert "dtype, shape" in caplog.text       # sorted component list
    events = [json.loads(l) for l in open(tmp_path / "r.jsonl")]
    changed = [e["changed"] for e in events if e["kind"] == "recompile"]
    assert changed[0] == ["shape"]
    assert changed[1] == ["dtype", "shape"]
    assert changed[2] == ["static"]


def test_recompile_detector_flags_perturbed_serving_program(
        caplog, _propagating_logger):
    """Acceptance: deliberately perturbing a pinned v2 serving program's
    input signature (de-committing the pinned cache leaves — exactly the
    Round-4 silent-recompile bug class) logs ≥1 warning."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import llama_config, materialize_params
    groups.reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    rng = np.random.default_rng(0)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    out = v2.put([7], [np.asarray(prompt)])          # prefill
    v2.put([7], [[int(np.argmax(out[7]))]])          # decode: pins 'decode'
    assert v2.recompiles.pinned_misses == 0          # pinned run is clean

    # round-trip through numpy: same values, but uncommitted leaves — the
    # jit cache keys on shardings, so the decode program recompiles
    # (admission-time table syncs would re-pin; a pure decode round
    # dispatches the perturbed cache as-is, like the original r4 bug)
    v2.cache = jax.tree_util.tree_map(
        lambda x: jnp.asarray(np.asarray(x)), v2.cache)
    with caplog.at_level(logging.WARNING):
        v2.put([7], [[1]])                           # decode again
    assert v2.recompiles.pinned_misses >= 1
    assert "pinned program" in caplog.text
    snap = v2.telemetry_snapshot()
    assert snap["pinned_recompiles"] >= 1
    assert 0.0 <= snap["kv_util_peak"] <= 1.0


def test_v2_serving_counters_after_generate():
    """generate() populates the serving snapshot: TTFT stamps, decode
    throughput, token/flush counters."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import llama_config, materialize_params
    groups.reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (5, 7, 6)]
    v2.generate(prompts, max_new_tokens=4)
    snap = v2.telemetry_snapshot()
    assert snap["queries"] == 3 and snap["unstamped_queries"] == 0
    assert snap["generated_tokens"] >= 3 * 4
    assert snap["flushed_sequences"] == 3
    assert snap["ttft_p50_s"] is not None and snap["decode_tok_s"] > 0
    assert 0.0 < snap["kv_util_peak"] <= 1.0


# ----------------------------------------------------------------- hub / CLI
def test_hub_jsonl_prometheus_and_merges(tmp_path):
    from deepspeed_tpu.telemetry import TelemetryHub
    hub = TelemetryHub(enabled=True,
                       jsonl_path=str(tmp_path / "t.jsonl"),
                       prometheus_path=str(tmp_path / "p.txt"),
                       flush_every=2)
    hub.step_event(step=1, loss=np.float32(2.5), metrics=None)
    assert not os.path.exists(tmp_path / "t.jsonl")  # still deferred
    hub.step_event(step=2, loss=np.float32(2.25), metrics=None)  # → flush
    lines = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    assert [e["kind"] for e in lines][:2] == ["train_step", "train_step"]
    assert lines[0]["loss"] == 2.5

    hub.counter("recompiles_total", 3)
    hub.gauge("mfu", 0.6)
    hub.write_prometheus()
    prom = open(tmp_path / "p.txt").read()
    assert "# TYPE deepspeed_tpu_recompiles_total counter" in prom
    assert "deepspeed_tpu_recompiles_total 3" in prom
    assert "deepspeed_tpu_mfu 0.6" in prom

    # comms merge: trace-time totals land as one 'comms' event
    from deepspeed_tpu.comm.comms_logging import get_comms_logger
    clog = get_comms_logger()
    clog.enabled = True
    clog.record("all_reduce", 1024, 0.5)
    clog.record("all_reduce", 2048, 0.1)
    hub.comms_event()
    clog.enabled = False
    clog.reset()
    events = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    comms = [e for e in events if e["kind"] == "comms"]
    assert comms and comms[-1]["ops"]["all_reduce"]["bytes"] == 3072
    assert comms[-1]["ops"]["all_reduce"]["count"] == 2


def test_comms_logger_totals_math():
    from deepspeed_tpu.comm.comms_logging import CommsLogger
    log = CommsLogger(enabled=True)
    log.record("all_gather", 100, 0.25)
    log.record("all_gather", 100, 0.25)
    log.record("all_gather", 300, None)
    t = log.totals()
    assert t["all_gather"]["count"] == 3
    assert t["all_gather"]["bytes"] == 500
    assert abs(t["all_gather"]["latency_s"] - 0.5) < 1e-9


def test_summarizer_cli(tmp_path, capsys):
    """Satellite: `python -m deepspeed_tpu.telemetry --summarize run.jsonl`
    prints a step-time/memory table."""
    from deepspeed_tpu.telemetry.__main__ import main
    path = tmp_path / "run.jsonl"
    events = [
        {"ts": 1.0, "kind": "train_step", "step": 1, "loss": 10.0,
         "grad_norm": 1.5, "skipped_steps": 0},
        {"ts": 2.0, "kind": "train_step", "step": 2, "loss": 8.0,
         "step_time_s": 0.5, "grad_norm": 1.2, "skipped_steps": 0},
        {"ts": 3.0, "kind": "memory", "step": None,
         "peak_bytes_in_use": 12 << 30, "peak_hbm_gb": 12.4},
        {"ts": 5.0, "kind": "serving", "queries": 96, "ttft_p50_s": 0.4,
         "decode_tok_s": 2500.0, "kv_util_peak": 0.8},
        {"ts": 6.0, "kind": "recompile", "program": "decode",
         "pinned": True},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    assert main(["--summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "step time" in out and "0.5" in out
    assert "peak HBM" in out and "12.4" in out
    assert "loss 10 → 8" in out
    assert "recompiles 1 (pinned 1)" in out
    assert "queries 96" in out


def test_summarizer_percentiles_and_trace_export(tmp_path, capsys):
    """Satellite: `--summarize ... --percentiles` prints the SLA histogram
    table + the per-serve-mode request table; `--export-trace OUT` writes a
    parseable Chrome-trace JSON from the same file."""
    from deepspeed_tpu.telemetry.__main__ import main
    path = tmp_path / "run.jsonl"
    events = [
        {"ts": 10.0, "kind": "trace_epoch", "engine": "v2",
         "epoch_unix": 10.0},
        {"ts": 10.6, "kind": "span", "name": "prefill", "t0_s": 0.1,
         "t1_s": 0.6, "dur_ms": 500.0, "depth": 0, "uids": [1],
         "slots": [0], "fields": None},
        {"ts": 10.9, "kind": "request_span", "uid": 1, "engine": "v2",
         "slot": 0, "serve_mode": "dequant", "status": "finished",
         "prompt_tokens": 4, "new_tokens": 8, "admit_s": 0.05,
         "done_s": 0.9, "queue_s": 0.0, "e2e_s": 0.85, "ttft_s": 0.55,
         "tpot_s": 0.05, "spans": {"prefill": 0.5},
         "unattributed_s": 0.0, "unattributed_frac": 0.0, "fields": None},
        {"ts": 10.95, "kind": "request_span", "uid": 2, "engine": "v2",
         "slot": 1, "serve_mode": "layer_scan", "status": "finished",
         "prompt_tokens": 4, "new_tokens": 4, "admit_s": 0.1,
         "done_s": 0.95, "e2e_s": 0.85, "ttft_s": 0.6, "tpot_s": 0.08,
         "spans": {}, "unattributed_s": 0.01, "unattributed_frac": 0.012},
        {"ts": 11.0, "kind": "histogram", "name": "ttft_s", "unit": "s",
         "count": 2, "mean": 0.575, "p50": 0.55, "p95": 0.6, "p99": 0.6,
         "min": 0.55, "max": 0.6, "buckets": {"0.75": 2}},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    trace_out = tmp_path / "trace.json"
    assert main(["--summarize", str(path), "--percentiles",
                 "--export-trace", str(trace_out)]) == 0
    out = capsys.readouterr().out
    assert "histograms (streaming, fixed log buckets):" in out
    assert "ttft_s" in out and "0.55" in out
    assert "requests by serve mode" in out
    assert "dequant" in out and "layer_scan" in out
    assert "0.012" in out                    # worst unattributed surfaces
    trace = json.loads(trace_out.read_text())
    evs = trace["traceEvents"]
    assert any(e.get("name") == "prefill" for e in evs)
    assert all(e.get("ts", 0) >= 0 and e.get("dur", 0) >= 0 for e in evs)


def test_trace_capture_writes_profile(tmp_path):
    """engine.trace / trace_capture produce an on-disk profile dir."""
    from deepspeed_tpu.telemetry.tracing import annotate, trace_capture
    logdir = str(tmp_path / "trace")
    with trace_capture(logdir):
        with annotate("ds:test"):
            jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones((8,))))
    found = [os.path.join(r, f) for r, _, fs in os.walk(logdir) for f in fs]
    assert found, "profiler trace produced no files"


# ----------------------------------------------------------- nvme counters
def test_nvme_swapper_counters(tmp_path):
    try:
        from deepspeed_tpu.runtime.swap_tensor.async_swapper import (
            AsyncTensorSwapper)
        sw = AsyncTensorSwapper(str(tmp_path / "swap"))
    except Exception as e:  # builder toolchain unavailable in some envs
        pytest.skip(f"aio engine unavailable: {e}")
    arr = np.arange(1024, dtype=np.float32)
    sw.swap_out("t", arr)
    sw.synchronize()
    got = sw.swap_in("t")
    sw.synchronize()
    np.testing.assert_array_equal(got, arr)
    c = sw.counters
    assert c["writes"] == 1 and c["reads"] == 1
    assert c["write_bytes"] == arr.nbytes and c["read_bytes"] == arr.nbytes
    assert c["syncs"] == 2 and c["errors"] == 0
    assert c["backend"] in ("io_uring", "threads")
