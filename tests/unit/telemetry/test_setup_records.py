"""Set-up says where its seconds go: what the `jax.monitoring` listeners keep
of a compile (`trace`, `lower`, `backend_compile` records and what the
persistent cache said), what a `compile` span sums of them, and the `init`
and `import` spans of the three engines.

The records of a jitted toy first, in this process. What needs a fresh
process (the package's one `import` span; a persistent cache that misses,
then hits) runs one small script twice in a child. Then each engine is built
once at toy shapes on one CPU device and the spans it left are read.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry import (TelemetryHub, compile_records,
                                     compile_span, compile_totals,
                                     get_span_store, init_phase, init_span,
                                     union_seconds)
from deepspeed_tpu.telemetry.hub import get_hub, set_hub
from deepspeed_tpu.utils import groups

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
ALL = ("trace", "lower", "backend_compile")


@pytest.fixture()
def store():
    s = get_span_store()
    s.clear()
    yield s
    s.clear()


def _new(t0, kinds=ALL):
    return [r for r in compile_records(kinds) if r["t"] >= t0]


# ---------------------------------------------------------------- records
def test_a_jitted_call_leaves_a_record_of_each_kind_inside_it():
    def toy_setup_fn(x):
        return jnp.tanh(x) * 3 + 1

    t0 = time.perf_counter()
    jax.jit(toy_setup_fn)(jnp.arange(7.0)).block_until_ready()
    t1 = time.perf_counter()
    mine = [r for r in _new(t0) if "toy_setup_fn" in (r["fun_name"] or "")]
    assert sorted(r["kind"] for r in mine) == sorted(ALL)
    for r in mine:
        # `seconds` is JAX's, on `time.time`; `t` is ours, on `perf_counter`
        assert t0 - 1e-3 <= r["t"] - r["seconds"] and r["t"] <= t1
        assert r["seconds"] > 0 and r["program"] is None
    by = {r["kind"]: r for r in mine}
    assert by["trace"]["t"] <= by["lower"]["t"] <= by["backend_compile"]["t"]
    assert by["backend_compile"]["cache"] in ("hit", "miss", "uncached")
    assert "cache" not in by["trace"] and "cache" not in by["lower"]


def test_called_bare_it_returns_the_backend_compiles_alone():
    jax.jit(lambda x: x - 5)(jnp.arange(3)).block_until_ready()
    bare = compile_records()
    assert bare and all(r["kind"] == "backend_compile" for r in bare)
    assert bare == compile_records(("backend_compile",))
    every = compile_records(ALL)
    assert {r["kind"] for r in every} == set(ALL)
    assert [r["t"] for r in every] == sorted(r["t"] for r in every)


def test_a_trace_inside_a_trace_does_not_carry_the_union_past_the_wall():
    @jax.jit
    def inner_setup_fn(x):
        return jnp.sin(x) + 1

    def outer_setup_fn(x):
        return inner_setup_fn(x) * inner_setup_fn(x + 1)

    t0 = time.perf_counter()
    jax.jit(outer_setup_fn)(jnp.arange(5.0)).block_until_ready()
    wall = time.perf_counter() - t0
    traces = _new(t0, ("trace",))
    inner = next(r for r in traces if r["fun_name"] == "inner_setup_fn")
    outer = next(r for r in traces if r["fun_name"] == "outer_setup_fn")
    assert outer["t"] - outer["seconds"] <= inner["t"] - inner["seconds"]
    assert inner["t"] <= outer["t"]               # each its own interval
    assert union_seconds(traces, t0) <= wall
    assert union_seconds(traces, t0) < sum(r["seconds"] for r in traces)
    assert union_seconds(_new(t0), t0) <= wall    # every kind together


def test_union_counts_nested_and_overlapping_intervals_once():
    recs = [{"t": 10.0, "seconds": 4.0}, {"t": 9.0, "seconds": 1.0},
            {"t": 12.0, "seconds": 3.0}, {"t": 20.0, "seconds": 2.0}]
    assert union_seconds(recs) == pytest.approx(6.0 + 2.0)
    assert union_seconds(recs, since=11.0) == pytest.approx(1.0 + 2.0)
    assert union_seconds([]) == 0.0


def test_totals_are_the_hub_counters():
    set_hub(TelemetryHub(enabled=False))
    assert compile_totals() == (0, 0.0)
    jax.jit(lambda x: x * 7 - 2)(jnp.arange(4)).block_until_ready()
    n, s = compile_totals()
    assert n >= 1 and s > 0
    assert (n, s) == (get_hub().counters["compiles_total"],
                      get_hub().counters["compile_seconds_total"])


def test_a_compile_span_says_what_its_first_dispatch_was_made_of(
        store, tmp_path):
    path = tmp_path / "t.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    try:
        with compile_span("toy:made_of", "v1"):
            jax.jit(lambda x: jnp.cos(x) * 2)(jnp.arange(6.0)
                                              ).block_until_ready()
        (s,) = store.spans()
        f, dur = s["fields"], s["t1"] - s["t0"]
        assert f["backend_compiles"] >= 1 and f["backend_compile_s"] > 0
        assert f["trace_s"] > 0 and f["lower_s"] > 0
        assert f["trace_s"] + f["lower_s"] + f["backend_compile_s"] <= dur
        assert f["cache_hits"] + f["cache_misses"] <= f["backend_compiles"]
        assert f["cache_retrieval_s"] >= 0
        (ev,) = [json.loads(l) for l in open(path)]
        assert ev["kind"] == "compile"
        assert {k: ev[k] for k in f} == f         # the event carries them too
    finally:
        set_hub(TelemetryHub(enabled=False))


# ----------------------------------------------- what needs a fresh process
CHILD = """
import json, sys, time
import deepspeed_tpu
from deepspeed_tpu.telemetry import compile_records, get_span_store
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def cached_setup_fn(x):
    return jnp.tanh(x @ x.T).sum()

jax.jit(cached_setup_fn)(jnp.ones((8, 8))).block_until_ready()
(rec,) = [r for r in compile_records()
          if "cached_setup_fn" in (r["fun_name"] or "")]
spans = get_span_store().spans()
print(json.dumps({"rec": rec, "spans": spans, "now": time.perf_counter()}))
"""


@pytest.fixture(scope="module")
def fresh_twice(tmp_path_factory):
    """The child's report, run twice against one new cache directory."""
    cache = tmp_path_factory.mktemp("jaxcache")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", CHILD, str(cache)], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(json.loads(p.stdout.splitlines()[-1]))
    return out


def test_the_first_fresh_compile_misses_the_cache_and_the_second_hits(
        fresh_twice):
    first, second = (run["rec"] for run in fresh_twice)
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit"
    assert second["retrieval_s"] >= 0 and "saved_s" in second
    assert first["kind"] == second["kind"] == "backend_compile"


def test_importing_the_package_leaves_one_import_span(fresh_twice):
    for run in fresh_twice:
        (s,) = [s for s in run["spans"] if s["name"] == "import"]
        assert s["depth"] == 0 and s["parent"] is None and s["engine"] is None
        assert 0 < s["t1"] - s["t0"] and s["t1"] <= run["now"]
        assert [x["name"] for x in run["spans"]] == ["import"]


# ------------------------------------------------------- engine construction
def test_the_rounds_of_a_long_run_do_not_push_set_up_out_of_the_store():
    from deepspeed_tpu.telemetry.spans import SpanStore
    s = SpanStore(cap=3)
    s.add({"name": "init", "t0": 0.0, "t1": 1.0}, setup=True)
    for i in range(5):
        s.add({"name": "decode", "t0": 2.0 + i, "t1": 3.0 + i})
    s.add({"name": "compile", "t0": 6.2, "t1": 6.4}, setup=True)
    assert [(r["name"], r["t1"]) for r in s.spans()] == [
        ("init", 1.0), ("decode", 5.0), ("decode", 6.0), ("compile", 6.4),
        ("decode", 7.0)]                          # oldest first, by their end
    assert len(s) == 5 and s.spans(t1=2.0) == [s.spans()[0]]
    s.clear()
    assert len(s) == 0 and s.spans() == []


def test_parts_tile_their_init_and_an_engine_inside_one_hangs_under_it(store):
    @init_span("outer")
    def build_outer():
        init_phase("plan")
        part = init_phase("place_params", rows=3)
        part["async"] = True

        @init_span("inner")
        def build_inner():
            init_phase("plan")
        build_inner()
        init_phase("build_programs")

    build_outer()
    assert init_phase("plan") == {}               # outside: nothing recorded
    by = {(s["engine"], s["name"]): s for s in store.spans()}
    assert len(by) == len(store) == 6
    outer, inner = by["outer", "init"], by["inner", "init"]
    kids = [by["outer", n] for n in ("plan", "place_params", "build_programs")]
    assert all(k["parent"] == outer["id"] and k["depth"] == 1 for k in kids)
    assert kids[0]["t0"] == outer["t0"] and kids[-1]["t1"] == outer["t1"]
    assert all(a["t1"] == b["t0"] for a, b in zip(kids, kids[1:]))
    assert kids[1]["fields"] == {"rows": 3, "async": True}
    assert outer["depth"] == 0 and outer["parent"] is None
    # the engine built inside `place_params` is that part's child
    assert inner["parent"] == kids[1]["id"] and inner["depth"] == 2
    assert by["inner", "plan"]["parent"] == inner["id"]
    assert (by["inner", "plan"]["t0"], by["inner", "plan"]["t1"]) == (
        inner["t0"], inner["t1"])


def test_an_init_that_raises_still_closes_its_spans(store):
    @init_span("broken")
    def build():
        init_phase("plan")
        raise ValueError("no")

    with pytest.raises(ValueError):
        build()
    assert sorted(s["name"] for s in store.spans()) == ["init", "plan"]
    assert init_phase("plan") == {}               # nothing is left open


@pytest.fixture(scope="module")
def tiny():
    set_hub(TelemetryHub(enabled=False))
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    return (cfg,) + tuple(materialize_params(cfg))


def _build_v1(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    return deepspeed_tpu.init_inference(model, params=params, dtype="fp32")


def _build_v2(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    return InferenceEngineV2(model, params=params, max_batch=4, max_seq_len=64,
                             split_fuse_chunk=8, cache_block_size=16,
                             kv_layout="paged", prefix_sharing=False)


def _build_train(tiny):
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            return jnp.mean((nn.Dense(8)(nn.relu(nn.Dense(16)(x))) - y) ** 2)

    groups.reset_topology()
    topology = groups.initialize(
        groups.MeshTopology(devices=jax.devices()[:1]))
    model = MLP()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)),
                        jnp.zeros((2, 8)))["params"]
    try:
        return deepspeed_tpu.initialize(
            model=model, model_parameters=params, topology=topology,
            loss_fn=lambda p, b, r: model.apply({"params": p}, b["x"], b["y"]),
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 1, "steps_per_print": 0,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})[0]
    finally:
        groups.reset_topology()


ENGINES = {
    "v1": (_build_v1, ["plan", "place_params", "build_programs"]),
    "v2": (_build_v2, ["plan", "place_params", "alloc_cache",
                       "build_programs"]),
    "train": (_build_train, ["plan", "place_params", "init_optimizer"]),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_each_engine_leaves_one_init_span_that_its_parts_tile(
        engine, tiny, store):
    build, parts = ENGINES[engine]
    t0 = time.perf_counter()
    build(tiny)
    t1 = time.perf_counter()
    spans = store.spans()
    (init,) = [s for s in spans if s["name"] == "init"]
    assert init["engine"] == engine and init["depth"] == 0
    assert init["parent"] is None and t0 <= init["t0"] <= init["t1"] <= t1
    kids = [s for s in spans if s["parent"] == init["id"]]
    assert [k["name"] for k in kids] == parts
    assert all(k["engine"] == engine and k["depth"] == 1 for k in kids)
    assert kids[0]["t0"] == init["t0"] and kids[-1]["t1"] == init["t1"]
    assert all(a["t1"] == b["t0"] for a, b in zip(kids, kids[1:]))
    assert sum(k["t1"] - k["t0"] for k in kids) == pytest.approx(
        init["t1"] - init["t0"], abs=1e-9)
    # the parts that start device work say whether it was still running
    said = {k["name"] for k in kids if "async" in k["fields"]}
    assert said == set(parts) & {"place_params", "alloc_cache",
                                 "init_optimizer"}
    assert all(isinstance(k["fields"]["async"], bool)
               for k in kids if k["name"] in said)


def test_a_state_built_after_construction_is_no_part_of_it(tiny, store):
    """`dont_materialize`: `initialize_state` then runs outside `__init__`,
    and leaves no span (its `init_phase` calls record nothing)."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    import flax.linen as nn

    groups.reset_topology()
    topology = groups.initialize(
        groups.MeshTopology(devices=jax.devices()[:1]))
    model = nn.Dense(4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)))["params"]
    try:
        engine = DeepSpeedEngine(
            model=model, topology=topology, dont_materialize=True,
            loss_fn=lambda p, b, r: jnp.mean(
                model.apply({"params": p}, b["x"]) ** 2),
            config=DeepSpeedConfig(
                {"train_micro_batch_size_per_gpu": 2, "steps_per_print": 0,
                 "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}},
                world_size=topology.world_size))
        names = [s["name"] for s in store.spans()]
        assert names == ["plan", "init"]
        engine.initialize_state(params)
        assert [s["name"] for s in store.spans()] == names
        assert engine.state is not None
    finally:
        groups.reset_topology()
