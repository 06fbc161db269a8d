"""FastGen v2 big-model serve modes under the continuous batcher.

The PR contract this file pins:

- v2 owns its parameter placement via the shared serve-mode resolver
  (``inference/serve_modes.py``) — ``serve_mode=`` on the constructor
  routes dequant / int8 layer_scan / capacity, with the r7
  ``make_block_fn`` body driving v2's bucketed programs. Bit-exact
  oracle: v2 layer_scan ≡ v1 layer_scan and v2 capacity ≡ v2 layer_scan
  (the r7 gotcha — whole-tree dequant quantizes embed/head where the
  layer-stacked modes keep them dense — means layer_scan vs dequant is
  NOT a valid pair on quantized trees).
- Pin-once program family: after ``warmup()`` a sweep over prompt
  lengths, batch compositions, and sampling configs causes ZERO
  RecompileDetector misses. Streamed-mode program names carry an
  ``@{serve_mode}`` suffix; dequant names are unchanged (stability
  contract, like the @kv_int8 suffix).
- The r9 OOM degradation ladder rides v2 placement (retry loop in
  ``_place_with_recovery``) and compile (``generate()`` wrapper):
  refs dropped before re-placement, ``_forced_mode`` pins the rung,
  ``serve_mode_degraded`` events, bit-exact vs a natively-lower engine.
- Speculative decoding rides v2's staged-KV append as the k+1 verify
  window for single-sequence steps; ragged batches fall back loudly to
  vanilla decode.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.resilience.faults import configure_faults
from deepspeed_tpu.utils import groups

QUANT = {"enabled": True}
PROMPTS = [[5, 6, 7, 8], [9, 10, 11]]


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    return model, params


def _v2(model, params, **kw):
    groups.reset_topology()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    return InferenceEngineV2(model, params=params, **kw)


def _v1(model, params, **kw):
    groups.reset_topology()
    kw.setdefault("dtype", "fp32")
    return deepspeed_tpu.init_inference(model, params=params, **kw)


def _v1_generate(eng, prompts, n):
    return [list(np.asarray(eng.generate(np.asarray([p]),
                                         max_new_tokens=n))[0])
            for p in prompts]


# --------------------------------------------------------------- validation

def test_streamed_mode_forces_slot_layout(tiny):
    model, params = tiny
    eng = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    assert eng.serve_mode == "layer_scan"
    assert eng.kv_layout == "slot"
    assert eng._quantized


def test_explicit_paged_with_streamed_mode_raises(tiny):
    model, params = tiny
    groups.reset_topology()
    with pytest.raises(ValueError, match="paged"):
        InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                          serve_mode="layer_scan", quant=QUANT,
                          kv_layout="paged")


def test_int8_kv_refused_on_streamed_modes(tiny):
    model, params = tiny
    groups.reset_topology()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                          serve_mode="layer_scan", quant=QUANT,
                          kv_cache_dtype="int8")


def test_spec_config_errors(tiny):
    model, params = tiny
    groups.reset_topology()
    with pytest.raises(ValueError, match="draft"):
        InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                          speculative={"enabled": True, "draft": "model"})
    groups.reset_topology()
    with pytest.raises(ValueError, match="k"):
        InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                          speculative={"enabled": True, "k": 0})


# ------------------------------------------------------------ parity matrix

@pytest.mark.slow
def test_v2_layer_scan_bitexact_vs_v1(tiny):
    model, params = tiny
    ref = _v1(model, params, quant=QUANT, serve_mode="layer_scan",
              max_batch_size=2, max_out_tokens=64)
    assert ref.serve_mode == "layer_scan"
    oref = _v1_generate(ref, PROMPTS, 6)
    eng = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    assert eng.generate(PROMPTS, max_new_tokens=6) == oref


@pytest.mark.slow
def test_v2_capacity_bitexact_vs_layer_scan(tiny):
    """The true bit-exact pair (r7): capacity shares make_block_fn with
    layer_scan, so greedy decode is identical by construction."""
    model, params = tiny
    ls = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    ols = ls.generate(PROMPTS, max_new_tokens=6)
    cap = _v2(model, params, serve_mode="capacity", quant=QUANT)
    assert cap.serve_mode == "capacity"
    assert cap._capacity is not None
    assert cap.generate(PROMPTS, max_new_tokens=6) == ols


@pytest.mark.slow
def test_v2_dequant_int8_bitexact_vs_v1(tiny):
    """Both engines whole-tree-quantize then dequantize the same tree —
    identical values in, identical greedy tokens out."""
    model, params = tiny
    ref = _v1(model, params, quant=QUANT, serve_mode="dequant",
              max_batch_size=2, max_out_tokens=64)
    oref = _v1_generate(ref, PROMPTS, 6)
    eng = _v2(model, params, serve_mode="dequant", quant=QUANT)
    assert eng.serve_mode == "dequant"
    assert eng.generate(PROMPTS, max_new_tokens=6) == oref


@pytest.mark.slow
def test_v2_kv_int8_runs_with_program_suffix(tiny):
    """Token parity under int8 KV is not a valid oracle on tiny random
    models (r10: argmax near-ties) — pin the program naming, accounting,
    and zero-miss contracts instead."""
    model, params = tiny
    eng = _v2(model, params, quant=QUANT, kv_cache_dtype="int8")
    out = eng.generate(PROMPTS, max_new_tokens=6)
    assert all(len(o) == len(p) + 6 for o, p in zip(out, PROMPTS))
    progs = sorted(eng.recompiles._seen)
    assert progs and all("@kv_int8" in p for p in progs), progs
    snap = eng.telemetry_snapshot()
    assert snap["kv_dtype"] == "int8"
    assert eng.recompiles.misses == 0


# --------------------------------------------------------- pin-once sweep

@pytest.mark.slow
@pytest.mark.parametrize("mode_kw", [
    {},
    {"serve_mode": "layer_scan", "quant": QUANT},
], ids=["dequant", "layer_scan"])
def test_warmup_pins_bucket_family_zero_misses(tiny, mode_kw):
    """After warmup, a sweep over ≥3 prompt-length buckets (32/64/128),
    mixed batch compositions, and a second sampling config must not
    recompile any pinned serving program."""
    model, params = tiny
    vocab = int(model.cfg.vocab_size)
    groups.reset_topology()
    eng = InferenceEngineV2(model, params=params, max_batch=4,
                            max_seq_len=192, **mode_kw)
    eng.warmup(buckets=(32, 64, 128), max_new_tokens=4)
    assert eng.recompiles.misses == 0
    rng = np.random.RandomState(7)
    for n in (20, 32, 50, 64, 100, 128):
        eng.generate([rng.randint(1, vocab, size=(n,)).tolist()],
                     max_new_tokens=4)
    eng.generate([rng.randint(1, vocab, size=(40,)).tolist(),
                  rng.randint(1, vocab, size=(90,)).tolist()],
                 max_new_tokens=4)
    assert eng.recompiles.misses == 0, sorted(eng.recompiles._seen)


@pytest.mark.slow
def test_streamed_program_names_carry_mode_suffix(tiny):
    model, params = tiny
    eng = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    eng.generate([PROMPTS[0]], max_new_tokens=4)
    progs = sorted(eng.recompiles._seen)
    assert progs and all("@layer_scan" in p for p in progs), progs
    # dequant names stay unsuffixed — the stability contract
    deq = _v2(model, params)
    deq.generate([PROMPTS[0]], max_new_tokens=4)
    assert all("@" not in p for p in deq.recompiles._seen), \
        sorted(deq.recompiles._seen)


# -------------------------------------------------------------- degradation

@pytest.mark.slow
def test_placement_oom_degrades_bitexact(tiny):
    model, params = tiny
    ref = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    oref = ref.generate(PROMPTS, max_new_tokens=6)
    configure_faults("param_placement/dequant:oom@1")
    try:
        eng = _v2(model, params, serve_mode="dequant", quant=QUANT)
    finally:
        configure_faults(None)
    assert eng.serve_mode == "layer_scan"
    assert eng._forced_mode == "layer_scan"
    assert eng.generate(PROMPTS, max_new_tokens=6) == oref


@pytest.mark.slow
def test_compile_oom_degrades_live_engine_with_event(tiny, tmp_path):
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    model, params = tiny
    ref = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    oref = ref.generate(PROMPTS, max_new_tokens=6)
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(tmp_path / "d.jsonl")))
    try:
        eng = _v2(model, params, serve_mode="dequant", quant=QUANT)
        assert eng.serve_mode == "dequant"
        configure_faults("program_compile/dequant:oom@1")
        try:
            out = eng.generate(PROMPTS, max_new_tokens=6)
        finally:
            configure_faults(None)
    finally:
        set_hub(TelemetryHub(enabled=False))
    assert eng.serve_mode == "layer_scan"
    assert out == oref
    events = [json.loads(l) for l in open(tmp_path / "d.jsonl")]
    degr = [e for e in events if e["kind"] == "serve_mode_degraded"]
    assert [(e["from_mode"], e["to_mode"], e["stage"]) for e in degr] == \
        [("dequant", "layer_scan", "compile")]
    assert degr[0]["engine"] == "v2"


@pytest.mark.slow
def test_degrade_optout_reraises(tiny):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.resilience.faults import InjectedOOM
    model, params = tiny
    cfg = DeepSpeedInferenceConfig(
        resilience={"degrade_on_oom": False})
    configure_faults("param_placement/dequant:oom@1")
    try:
        groups.reset_topology()
        with pytest.raises(InjectedOOM):
            InferenceEngineV2(model, config=cfg, params=params, max_batch=2,
                              max_seq_len=64, serve_mode="dequant",
                              quant=QUANT)
    finally:
        configure_faults(None)


# ---------------------------------------------------------------- spec

@pytest.mark.slow
def test_spec_greedy_bitexact_vs_vanilla(tiny):
    model, params = tiny
    van = _v2(model, params)
    ov = van.generate([PROMPTS[0]], max_new_tokens=8)
    eng = _v2(model, params, speculative={"enabled": True, "k": 3})
    assert eng._spec_enabled
    assert eng.generate([PROMPTS[0]], max_new_tokens=8) == ov
    c = eng.serving_counters
    assert c["spec_rounds"] > 0
    assert c["spec_draft_tokens"] == c["spec_rounds"] * 3
    snap = eng.telemetry_snapshot()
    assert snap["speculative"] and snap["spec_k"] == 3
    assert snap["acceptance_rate"] is not None
    assert eng.recompiles.misses == 0


@pytest.mark.slow
def test_spec_sampled_runs_zero_miss(tiny):
    model, params = tiny
    eng = _v2(model, params, speculative={"enabled": True, "k": 3})
    out = eng.generate([PROMPTS[0]], max_new_tokens=6,
                       temperature=0.8, top_k=20, seed=3)
    assert len(out[0]) == len(PROMPTS[0]) + 6
    assert eng.recompiles.misses == 0


@pytest.mark.slow
def test_spec_ragged_batch_falls_back_to_vanilla(tiny):
    """Two live sequences per step = ragged batching; spec steps aside
    (warn-once) and the wave decodes vanilla — outputs match the
    spec-free engine bit-exactly."""
    model, params = tiny
    van = _v2(model, params)
    ov = van.generate(PROMPTS, max_new_tokens=6)
    eng = _v2(model, params, speculative={"enabled": True, "k": 3})
    assert eng.generate(PROMPTS, max_new_tokens=6) == ov
    assert eng.serving_counters["spec_rounds"] == 0


@pytest.mark.slow
def test_spec_composes_with_layer_scan(tiny):
    model, params = tiny
    van = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    ov = van.generate([PROMPTS[0]], max_new_tokens=8)
    eng = _v2(model, params, serve_mode="layer_scan", quant=QUANT,
              speculative={"enabled": True, "k": 3})
    assert eng._spec_enabled
    assert eng.generate([PROMPTS[0]], max_new_tokens=8) == ov
    assert eng.serving_counters["spec_rounds"] > 0


@pytest.mark.slow
def test_spec_disabled_on_capacity_with_warning(tiny):
    model, params = tiny
    eng = _v2(model, params, serve_mode="capacity", quant=QUANT,
              speculative={"enabled": True, "k": 3})
    assert not eng._spec_enabled
    # still serves fine
    ls = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    assert eng.generate([PROMPTS[0]], max_new_tokens=6) == \
        ls.generate([PROMPTS[0]], max_new_tokens=6)


# ------------------------------------------------------------- telemetry

@pytest.mark.slow
def test_telemetry_snapshot_serve_mode_fields(tiny):
    model, params = tiny
    eng = _v2(model, params, serve_mode="layer_scan", quant=QUANT)
    eng.generate([PROMPTS[0]], max_new_tokens=4)
    snap = eng.telemetry_snapshot()
    assert snap["serve_mode"] == "layer_scan"
    assert snap["weight_bytes_step"] > 0
    assert snap["weight_bytes_step_dense"] > snap["weight_bytes_step"]
    assert snap["speculative"] is False and snap["spec_k"] is None
