"""The program map (`telemetry/program_map.py`): what the three engines keep
at a program's first dispatch, what the map says of their programs, what it
costs an engine that is never asked, and the join of device events to it.

One scripted run a module at toy shapes on the CPU: a train step of
llama-tiny with the chunked loss, two v1 generate keys, a v2 prefill and
decode round. The scopes are read off the CPU compile's text, which carries
the same `op_name` metadata the chip's does."""

import gc
import json
import re
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry import TelemetryHub, compile_records
from deepspeed_tpu.telemetry import program_map as program_map_fn
from deepspeed_tpu.telemetry.hub import set_hub
from deepspeed_tpu.telemetry.program_map import (_CAP, _KEPT, by_scope, keep,
                                                  row_matches, seconds_where)
from deepspeed_tpu.tools.tpucomms import hlo
from deepspeed_tpu.utils import groups

META = re.compile(r",?\s*metadata=\{(?:[^{}\"]|\"[^\"]*\")*\}")


def _names(row):
    return set(hlo.scope_names(row["scope"]))


def _counts():
    """Lowerings and backend compiles heard so far."""
    return len(compile_records(("lower",))), len(compile_records())


def _train_engine(cfg, model, params):
    groups.reset_topology()
    topology = groups.initialize(
        groups.MeshTopology(devices=jax.devices()[:1]))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, topology=topology,
        loss_fn=llama.llama_loss_fn(model),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2, "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    return engine


@pytest.fixture(scope="module")
def run():
    """The three engines driven once, NOTHING asked of the map; then the
    map. Returns what was counted on the way."""
    set_hub(TelemetryHub(enabled=False))
    telemetry.forget_programs()
    cfg = llama.llama_config("llama-tiny", dtype=jnp.float32,
                             loss_chunk_size=8)
    model, params = llama.materialize_params(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, (2, 8))
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (4, 16)
                                       ).astype(np.int32)}
    out = {}

    groups.reset_topology()
    v1 = deepspeed_tpu.init_inference(model, params=params, dtype="fp32")
    v1.generate(ids, max_new_tokens=4)
    v1.generate(ids, max_new_tokens=4, temperature=0.7, top_k=5, seed=3)
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=4, max_seq_len=64,
                           split_fuse_chunk=8, cache_block_size=16,
                           kv_layout="paged", prefix_sharing=False)
    tok = int(v2.put([1], [ids[0][:5].astype(np.int32)],
                     argmax_only=True)[1])
    v2.put([1], [[tok]], argmax_only=True)
    engine = _train_engine(cfg, model, params)
    out["first_loss"] = float(engine.train_batch(batch=batch))

    # every program has had its first dispatch: from here on an engine that
    # is never asked lowers and compiles nothing
    before = _counts()
    v1.generate(ids, max_new_tokens=4)
    v2.put([1], [[tok]], argmax_only=True)
    engine.train_batch(batch=batch)
    out["unasked"] = (before, _counts())
    out["kept"] = dict(_KEPT)
    out["built_before_asking"] = [e["built"] for e in _KEPT.values()]

    before = _counts()
    out["maps"] = program_map_fn()
    out["asked"] = (before, _counts())
    out["cfg"], out["model"], out["params"], out["batch"] = \
        cfg, model, params, batch
    return out


def test_an_engine_never_asked_lowers_and_compiles_nothing(run):
    before, after = run["unasked"]
    assert after == before
    assert run["built_before_asking"] and \
        all(b is None for b in run["built_before_asking"])


def test_asking_gets_the_executable_that_ran(run):
    """JAX's own caches answer the map's `lower` + `compile` of every
    program with what the dispatch made: no backend compile is heard, and
    the map says `memory`."""
    before, after = run["asked"]
    assert after[1] == before[1]
    assert {d["cache"] for d in run["maps"].values()} == {"memory"}
    assert not any(d.get("stale") for d in run["maps"].values())
    # built once and kept; the tracing may go
    assert program_map_fn() == run["maps"]
    assert all(e["traced"] is None for e in _KEPT.values())


def test_one_module_name_a_program(run):
    maps = run["maps"]
    assert sorted(maps) == sorted([
        "jit_ds_v1_generate_b2_s8_n4", "jit_ds_v1_generate_b2_s8_n4_t0_7_k5",
        "jit_ds_v2_prefill_32", "jit_ds_v2_decode",
        "jit_ds_train_train_batch"])
    assert maps["jit_ds_v1_generate_b2_s8_n4_t0_7_k5"]["program"] == \
        "v1:generate:b2_s8_n4:t0.7_k5"
    assert maps["jit_ds_train_train_batch"]["detector"] == "train_batch"
    assert maps["jit_ds_v1_generate_b2_s8_n4"]["detector"].startswith(
        "generate:(2, 8, 4,")
    for module, doc in maps.items():
        assert module == "jit_" + telemetry.jit_name(
            ("v2:" if module.startswith("jit_ds_v2") else "")
            + doc["program"])
        assert doc["rows"] and all(r["module"] == module
                                   for r in doc["rows"])
    assert program_map_fn("v1:generate:b2_s8_n4").keys() == \
        {"jit_ds_v1_generate_b2_s8_n4"}


@pytest.mark.parametrize("module,allowed", [
    ("jit_ds_train_train_batch", {"layers", "chunked_ce"}),
    ("jit_ds_v1_generate_b2_s8_n4", {"layers", "head"}),
    ("jit_ds_v2_decode", {"layers", "head"}),
    ("jit_ds_v2_prefill_32", {"layers", "head"})])
def test_every_product_lies_in_a_named_scope(run, module, allowed):
    rows = run["maps"][module]["rows"]
    dots = [r for r in rows if "dot" in r["holds"]]
    assert dots
    for r in dots:
        assert _names(r) & allowed, r
        assert "optimizer" not in _names(r)
    assert not [r for r in rows if "optimizer" in _names(r)
                and "dot" in r["holds"]]


def test_train_step_scopes_and_phases(run):
    rows = run["maps"]["jit_ds_train_train_batch"]["rows"]
    for scope in ("micro", "grad_accumulate", "optimizer", "chunked_ce",
                  "layers"):
        assert any(scope in _names(r) for r in rows), scope
    dots = [r for r in rows if "dot" in r["holds"]]
    assert {r["phase"] for r in dots} == {"fwd", "bwd"}
    assert all("micro" in _names(r) for r in dots)
    # the optimizer is outside the micro-batches' scan, the layers inside
    assert all(r["loop"] is None for r in rows if "optimizer" in _names(r)
               and not r.get("inferred"))
    assert any(r["loop"] for r in dots)


def test_prefill_and_decode_partition_a_v1_programs_products(run):
    rows = run["maps"]["jit_ds_v1_generate_b2_s8_n4"]["rows"]
    dots = [r for r in rows if "dot" in r["holds"]]
    under = [("prefill" in _names(r), "decode" in _names(r)) for r in dots]
    assert all(p != d for p, d in under)
    assert sum(p for p, _ in under) == sum(d for _, d in under) > 0
    # the decode side is the scan's body
    assert all(r["loop"] for r in dots if "decode" in _names(r))
    assert any("sample" in _names(r) for r in rows)
    assert any("kv_write" in _names(r) for r in rows)


def test_v2_programs_name_the_caches_path(run):
    prefill = run["maps"]["jit_ds_v2_prefill_32"]["rows"]
    decode = run["maps"]["jit_ds_v2_decode"]["rows"]
    assert any("row_view" in _names(r) for r in prefill)
    assert any("kv_write" in _names(r) for r in prefill)
    assert any("kv_stage" in _names(r) for r in decode)
    assert any(row_matches(r, scope="layers", holds="^dot$") for r in decode)
    assert any(row_matches(r, scope="head", holds="^dot$") for r in decode)


def test_scopes_change_metadata_only(run, monkeypatch):
    """The optimised train step with every `with jax.named_scope(...)` of
    the repo made a no-op (`micro`, `grad_accumulate`, `chunked_ce`, `head`;
    a decorator was applied at import and stays) is, `metadata=` taken out,
    the same text; and its first loss the same number."""
    import contextlib
    kept = run["kept"]["jit_ds_train_train_batch"]
    with_scopes = _KEPT["jit_ds_train_train_batch"]["built"]
    assert with_scopes is not None
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    engine = _train_engine(run["cfg"], run["model"], run["params"])
    loss = float(engine.train_batch(batch=run["batch"]))
    entry = _KEPT["jit_ds_train_train_batch"]
    assert entry is not kept and entry["built"] is None
    with entry["context"]:
        bare = entry["traced"].lower().compile().as_text()
    monkeypatch.undo()
    engine = _train_engine(run["cfg"], run["model"], run["params"])
    assert float(engine.train_batch(batch=run["batch"])) == loss \
        == run["first_loss"]
    entry = _KEPT["jit_ds_train_train_batch"]
    with entry["context"]:
        named = entry["traced"].lower().compile().as_text()

    def body(text):
        return META.sub("", text[re.search(r"^(%|ENTRY )", text,
                                           re.M).start():])
    assert "/micro/" in named and "/grad_accumulate/" in named
    assert "/micro/" not in bare and "/grad_accumulate/" not in bare
    assert body(named) == body(bare)


def test_the_registry_is_bounded_and_holds_no_engine():
    telemetry.forget_programs()

    class Engine:
        def __init__(self):
            self.w = jnp.ones((4, 4))

        def step(self, x):
            return jnp.tanh(x @ self.w).sum()

    alive = []
    for i in range(_CAP + 3):
        e = Engine()
        fn = lambda x, e=e: e.step(x)   # noqa: E731
        fn.__name__ = f"ds_toy_{i}"
        jitted = jax.jit(fn)
        keep(f"toy:{i}", jitted.trace(jnp.ones((2, 4))))
        alive.append(weakref.ref(e))
        del e, fn, jitted
    gc.collect()
    assert len(_KEPT) == _CAP
    assert "jit_ds_toy_0" not in _KEPT and f"jit_ds_toy_{_CAP + 2}" in _KEPT
    assert not any(ref() is not None for ref in alive)
    # what is kept still gives its rows
    doc = program_map_fn(f"toy:{_CAP + 2}")[f"jit_ds_toy_{_CAP + 2}"]
    assert any("dot" in r["holds"] for r in doc["rows"])
    telemetry.forget_programs()


def test_a_stale_text_gives_no_rows(monkeypatch):
    """A program the persistent cache kept at its first dispatch and does
    not know when the map asks was compiled from something else: the map
    says so in a field and gives no row of it."""
    import sys
    from deepspeed_tpu.telemetry import tracing
    # the package's attribute of this name is the function
    pm = sys.modules["deepspeed_tpu.telemetry.program_map"]
    telemetry.forget_programs()

    def toy(x):
        return (x * 2).sum()
    toy.__name__ = "ds_stale_toy"
    keep("toy:stale", jax.jit(toy).trace(jnp.ones((4,))))
    pm.note_first_dispatch("toy:stale", "hit")
    monkeypatch.setattr(pm, "_cache_said", lambda records: "miss")
    doc = program_map_fn()["jit_ds_stale_toy"]
    assert doc["stale"] is True and doc["rows"] == []
    assert (doc["first_cache"], doc["cache"]) == ("hit", "miss")
    telemetry.forget_programs()
    assert tracing._worst_cache([]) == "uncached"


def test_a_text_that_cannot_be_had_is_an_error_field_not_a_raise():
    """The map is an observer: a program whose lowering fails when the map
    asks (its tracing outlived what it closed over, say) carries `error`
    and no rows, and the other programs give theirs."""
    telemetry.forget_programs()

    class Broken:
        fun_name = "ds_broken"

        def lower(self):
            raise RuntimeError("no backend left to compile for")

    def toy(x):
        return x + 1
    toy.__name__ = "ds_sound_toy"
    keep("toy:broken", Broken())
    keep("toy:sound", jax.jit(toy).trace(jnp.ones((2,))))
    maps = program_map_fn()
    assert maps["jit_ds_broken"]["rows"] == []
    assert maps["jit_ds_broken"]["error"].startswith("RuntimeError: no backend")
    assert maps["jit_ds_sound_toy"]["rows"]
    telemetry.forget_programs()


def test_the_train_engine_hands_out_its_own_programs(run):
    telemetry.forget_programs()
    engine = _train_engine(run["cfg"], run["model"], run["params"])
    engine.train_batch(batch=run["batch"])

    def toy(x):
        return x * 3
    toy.__name__ = "ds_not_the_engines"
    keep("v9:other", jax.jit(toy).trace(jnp.ones((2,))))
    assert list(engine.program_map()) == ["jit_ds_train_train_batch"]
    assert list(engine.program_map("train_batch")) == \
        ["jit_ds_train_train_batch"]
    assert engine.program_map("micro") == {}
    telemetry.forget_programs()


# ------------------------------------------------------------------ the join

ROWS = [
    {"instr": "while.1", "module": "jit_a", "opcode": "while",
     "scope": "decode/while", "phase": None, "loop": None, "holds": []},
    {"instr": "fusion.2", "module": "jit_a", "opcode": "fusion",
     "scope": "decode/while/body/layers/q_proj/dot_general", "phase": None,
     "loop": "body", "holds": ["dot"]},
    {"instr": "fusion.3", "module": "jit_a", "opcode": "fusion",
     "scope": "decode/while/body/head/dot_general", "phase": None,
     "loop": "body", "holds": ["all-reduce[data,model]", "dot"]},
    {"instr": "fusion.2", "module": "jit_b", "opcode": "fusion",
     "scope": "optimizer/sub", "phase": None, "loop": None, "holds": []},
]
MAPS = {"jit_a": {"program": "a", "module": "jit_a",
                  "rows": [r for r in ROWS if r["module"] == "jit_a"]},
        "jit_b": {"program": "b", "module": "jit_b",
                  "rows": [r for r in ROWS if r["module"] == "jit_b"]}}


def _seconds(joined):
    return {(r["module"], r["instr"]): round(s * 1e9)
            for r, s in joined["rows"]}


def test_by_scope_nesting_is_self_time():
    ops = [["%while.1 = (s32[]) while(...)", 100, 1000],
           ["%fusion.2", 200, 300], ["%fusion.3", 600, 100]]
    joined = by_scope(ops, [["jit_a(77)", 0, 2000]], maps=MAPS)
    assert _seconds(joined) == {("jit_a", "while.1"): 600,
                                ("jit_a", "fusion.2"): 300,
                                ("jit_a", "fusion.3"): 100}
    assert joined["unmatched"] == {}
    assert round(joined["busy_s"] * 1e9) == 1000
    assert round(seconds_where(joined, scope="decode") * 1e9) == 1000
    assert round(seconds_where(joined, scope="layers",
                               holds="^dot$") * 1e9) == 300
    assert round(seconds_where(joined, holds=r"^all-reduce\[",
                               axes="data") * 1e9) == 100
    assert seconds_where(joined, holds=r"^all-reduce\[",
                         axes=["data", "expert"]) == 0
    assert round(seconds_where(joined, scope="decode",
                               not_scope="head") * 1e9) == 900
    assert round(seconds_where(joined, any_scope=["head", "q_proj"],
                               not_instr=r"^fusion\.3") * 1e9) == 300
    # a window cuts the events, as the benchmark's self times are cut
    cut = by_scope(ops, [["jit_a(77)", 0, 2000]], window=(0, 400),
                   maps=MAPS)
    assert _seconds(cut) == {("jit_a", "while.1"): 100,
                             ("jit_a", "fusion.2"): 200}


def test_by_scope_keeps_what_it_cannot_name():
    ops = [["%fusion.2", 10, 5],            # before any module event
           ["%fusion.9", 120, 10],          # a name the map lacks
           ["%fusion.2", 140, 10],
           ["%copy.1", 5000, 7]]            # a module the map lacks
    mods = [["jit_a(1)", 100, 100], ["jit_other(2)", 4000, 2000]]
    joined = by_scope(ops, mods, maps=MAPS)
    assert _seconds(joined) == {("jit_a", "fusion.2"): 10}
    assert {k: round(v * 1e9) for k, v in joined["unmatched"].items()} == {
        "(no module)/fusion.2": 5, "jit_a/fusion.9": 10,
        "jit_other/copy.1": 7}
    assert round(joined["busy_s"] * 1e9) == 32


def test_by_scope_tells_two_interleaved_modules_apart():
    """`fusion.2` is an instruction of both programs: each event goes to the
    module that was running."""
    mods = [["jit_a(1)", 0, 100], ["jit_b(2)", 100, 100],
            ["jit_a(1)", 200, 100], ["jit_b(2)", 300, 100]]
    ops = [["%fusion.2", 10, 20], ["%fusion.2", 110, 30],
           ["%fusion.2", 210, 20], ["%fusion.2", 310, 30]]
    joined = by_scope(ops, mods, maps=MAPS)
    assert _seconds(joined) == {("jit_a", "fusion.2"): 40,
                                ("jit_b", "fusion.2"): 60}
    assert round(seconds_where(joined, scope="optimizer") * 1e9) == 60


# ---------------------------------------------------------- the operator's form


def test_trace_capture_writes_the_map_and_the_cli_reads_it(tmp_path, capsys):
    telemetry.forget_programs()

    def toy(x):
        with jax.named_scope("head"):
            return (x @ x.T).sum()
    toy.__name__ = "ds_cli_toy"
    jitted = jax.jit(toy)
    x = jnp.ones((8, 8))
    keep("toy:cli", jitted.trace(x))
    logdir = str(tmp_path / "trace")
    with telemetry.trace_capture(logdir):
        jitted(x).block_until_ready()
    with open(tmp_path / "trace" / "program_map.json") as f:
        doc = json.load(f)
    assert doc["jit_ds_cli_toy"]["program"] == "toy:cli"
    assert any("head" in hlo.scope_names(r["scope"])
               for r in doc["jit_ds_cli_toy"]["rows"])
    from deepspeed_tpu.telemetry.__main__ import main
    assert main(["--by-scope", logdir]) == 0
    said = capsys.readouterr().out
    assert "toy:cli (jit_ds_cli_toy" in said and "unmatched:" in said
    with pytest.raises(SystemExit):
        main([])
    telemetry.forget_programs()
