"""v2 engine request-span tracing — the tentpole's engine-level contract.

Pinned here:
- tracing is FREE when the hub is disabled (zero recorded spans) and
  FETCH-FREE when enabled: generate() output is bit-identical on vs off
  and the RecompileDetector stays at zero pinned misses either way;
- every finished request emits a `request_span` whose wall time decomposes
  into the named serving spans with `unattributed_frac` < 1% (CPU mesh);
- span lifecycle edge cases: degrade mid-generate (traces survive the
  engine rebuild), spec ragged fallback-to-vanilla, fork()/COW
  attribution, and a DS_TPU_FAULTS run where every fired fault/retry is
  mirrored 1:1 in the tracer's instants.

Engine-level tests compile serving programs (multi-second on the 1-core
box) — all marked slow; the fast span arithmetic lives in test_spans.py.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.resilience.faults import clear_faults, configure_faults
from deepspeed_tpu.telemetry import TelemetryHub, get_span_store
from deepspeed_tpu.telemetry.hub import set_hub
from deepspeed_tpu.utils import groups

pytestmark = pytest.mark.slow

QUANT = {"enabled": True}


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    return model, params


@pytest.fixture(autouse=True)
def _clean():
    clear_faults()
    yield
    clear_faults()
    set_hub(TelemetryHub(enabled=False))


def _v2(model, params, **kw):
    groups.reset_topology()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    return InferenceEngineV2(model, params=params, **kw)


def _events(path):
    return [json.loads(l) for l in open(path)]


PROMPTS = [[5, 6, 7, 8], [9, 10, 11]]


def test_tracing_off_is_free_on_is_fetch_free_and_bit_identical(tiny,
                                                                tmp_path):
    model, params = tiny
    store = get_span_store()
    store.clear()
    off = _v2(model, params)
    out_off = off.generate(PROMPTS, max_new_tokens=6)
    assert off.tracer.spans_recorded == 0            # free when disabled
    assert off.tracer.last_requests == {}
    # nothing stored either, but set-up, which is recorded whatever the
    # tracer's state: each program's `compile` span, the engine's `init`
    # span and its parts
    assert {s["name"] for s in store.spans()} == {
        "compile", "init", "plan", "place_params", "alloc_cache",
        "build_programs"}
    assert off.recompiles.pinned_misses == 0
    assert off.serving_counters["token_slots_computed"] >= \
        off.serving_counters["tokens_fed"] > 0      # counted all the same

    set_hub(TelemetryHub(enabled=True, jsonl_path=str(tmp_path / "t.jsonl")))
    on = _v2(model, params)
    out_on = on.generate(PROMPTS, max_new_tokens=6)
    assert out_on == out_off                         # bit-identical
    assert on.tracer.spans_recorded > 0
    assert on.recompiles.pinned_misses == 0          # zero new dispatches
    assert on.tracer.requests_finished == len(PROMPTS)


def test_request_span_decomposition_and_histograms(tiny, tmp_path):
    model, params = tiny
    path = tmp_path / "t.jsonl"
    hub = TelemetryHub(enabled=True, jsonl_path=str(path))
    set_hub(hub)
    eng = _v2(model, params)
    eng.generate(PROMPTS, max_new_tokens=6)
    events = _events(path)
    reqs = [e for e in events if e["kind"] == "request_span"]
    assert len(reqs) == len(PROMPTS)
    known = {"admit", "prefill", "chunk", "decode", "decode_wave",
             "spec_round", "mixed_round", "flush", "degrade", "round"}
    for r in reqs:
        assert r["engine"] == "v2" and r["status"] == "finished"
        assert r["serve_mode"] == "dequant"
        # the final wave's token retires the row before it is appended to
        # seq.tokens, so the count is max_new or max_new-1 by retirement path
        assert r["new_tokens"] in (5, 6)
        assert {k.replace("_other", "") for k in r["spans"]} <= known
        # the stall-accounting invariant: <1% of wall time unattributed
        assert r["unattributed_frac"] < 0.01, r
        assert r["ttft_s"] is not None and r["tpot_s"] is not None
        assert r["done_s"] > r["admit_s"] >= 0
    # depth-0 decode waves + the trace_epoch anchor + streaming histograms
    spans = [e for e in events if e["kind"] == "span"]
    assert any(s["name"] == "decode_wave" and s["depth"] == 0
               for s in spans)
    assert sum(e["kind"] == "trace_epoch" for e in events) == 1
    hists = {e["name"]: e for e in events if e["kind"] == "histogram"}
    assert set(hists) == {"ttft_s", "tpot_s", "e2e_s"}
    assert hists["e2e_s"]["count"] == len(PROMPTS)
    assert hists["e2e_s"]["p50"] is not None
    # in-process mirrors of the same stream
    assert hub.histograms["ttft_s"].n == len(PROMPTS)


def test_put_driven_spans_and_flush(tiny, tmp_path):
    model, params = tiny
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    eng = _v2(model, params)
    out = eng.put([7], [np.asarray(PROMPTS[0], np.int32)])
    eng.put([7], [[int(np.argmax(out[7]))]])
    eng.flush(7)
    s = eng.tracer.last_requests[7]
    assert s["prompt_tokens"] == 4 and s["new_tokens"] == 1
    names = {k.replace("_other", "") for k in s["spans"]}
    assert "prefill" in names and "decode" in names and "flush" in names
    assert any(e["kind"] == "span" and e["name"] == "prefill"
               and e["fields"]["tokens"] == 4 for e in _events(path))


def test_degrade_mid_generate_traces_survive_rebuild(tiny, tmp_path):
    model, params = tiny
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    eng = _v2(model, params, serve_mode="dequant", quant=QUANT)
    configure_faults("program_compile/dequant:oom@1")
    try:
        eng.generate(PROMPTS, max_new_tokens=4)
    finally:
        clear_faults()
    assert eng.serve_mode == "layer_scan"
    events = _events(path)
    reqs = [e for e in events if e["kind"] == "request_span"]
    # in-flight traces ride through the rebuild: one span per request,
    # closed under the POST-degrade mode, containing the degrade span
    assert len(reqs) == len(PROMPTS)
    for r in reqs:
        assert r["serve_mode"] == "layer_scan"
        assert "degrade" in r["spans"]
    deg = [e for e in events if e["kind"] == "span"
           and e["name"] == "degrade"]
    assert len(deg) == 1
    assert deg[0]["fields"] == {"from_mode": "dequant",
                                "to_mode": "layer_scan",
                                "stage": "compile"}
    # the resilience instants mirrored into the tracer 1:1 with the file
    file_kinds = sorted(e["kind"] for e in events
                        if e["kind"] in ("fault", "serve_mode_degraded"))
    assert sorted(i["kind"] for i in eng.tracer.instants
                  if i["kind"] != "recompile") == file_kinds


def test_spec_fallback_to_vanilla_still_traced(tiny, tmp_path):
    model, params = tiny
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    eng = _v2(model, params, speculative={"enabled": True, "k": 2})
    eng.generate(PROMPTS, max_new_tokens=4)   # 2 live rows → ragged fallback
    reqs = [e for e in _events(path) if e["kind"] == "request_span"]
    assert len(reqs) == len(PROMPTS)
    for r in reqs:
        assert r["status"] == "finished" and r["unattributed_frac"] < 0.01
        # the vanilla rounds attributed; no spec_round ever opened
        assert "spec_round" not in r["spans"]


def test_fork_cow_attribution(tiny, tmp_path):
    model, params = tiny
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(tmp_path / "t.jsonl")))
    groups.reset_topology()
    eng = InferenceEngineV2(model, params=params, max_batch=3,
                            max_seq_len=96, cache_block_size=16)
    rng = np.random.default_rng(1)
    prompt = np.asarray(rng.integers(0, model.cfg.vocab_size, 21), np.int32)
    lg = eng.put([7], [prompt])
    eng.fork(7, 8)
    nxt = np.asarray([int(np.argmax(lg[7]))], np.int32)
    eng.put([7], [nxt])                      # parent writes shared tail → COW
    eng.put([8], [nxt])
    eng._flush_batch([7, 8])
    parent = eng.tracer.last_requests[7]
    child = eng.tracer.last_requests[8]
    assert child["fields"]["forked_from"] == 7
    assert child["prompt_tokens"] == 21      # parent's seen tokens at fork
    assert parent["fields"]["cow_copies"] >= 1
    # the child's decode round covers it: no _other-only attribution
    assert any(not k.endswith("_other") for k in child["spans"])


def test_fault_run_instants_match_spans_one_to_one(tiny, tmp_path):
    from deepspeed_tpu.resilience.retry import retry_call
    model, params = tiny
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    eng = _v2(model, params)
    eng.tracer.attach()                      # mirror from the first fault on
    # the raise@1 aborts the injector's rule loop mid-traversal, so the
    # stall rule only counts the retry-put (1) and the decode put (2)
    configure_faults("generate_dispatch/v2_put:raise@1;"
                     "generate_dispatch/v2_put:stall=0.01@2")
    try:
        out = retry_call(
            lambda: eng.put([7], [np.asarray(PROMPTS[0], np.int32)]),
            what="test_put", retries=3, base_delay=0.001)
        eng.put([7], [[int(np.argmax(out[7]))]])
        eng.flush(7)
    finally:
        clear_faults()
    assert 7 in eng.tracer.last_requests     # fault absorbed, not dropped
    events = _events(path)
    fired = sorted(e["kind"] for e in events
                   if e["kind"] in ("fault", "retry", "watchdog",
                                    "serve_mode_degraded"))
    assert fired == ["fault", "fault", "retry"]
    mirrored = sorted(i["kind"] for i in eng.tracer.instants
                      if i["kind"] != "recompile")
    assert mirrored == fired                 # 1:1, nothing lost or invented
