"""Every program an engine compiles is registered under its stable name.

Two records carry a program's name: the `compile` span its first dispatch
leaves in the span store (`perfbench/readers/program.py` reads set-up by
program from it) and the `RecompileDetector`, which watches that name for
a changed signature from then on. What must hold for each program of the
three engines, at toy shapes on one CPU device:

- the first dispatch leaves exactly ONE `compile` span whose `program` is
  the name below, with `phase` `first_dispatch`;
- the detector observed the program under the name it registers today;
- a second dispatch with the same shapes adds neither a span nor a miss;
- a single-device dequant name carries no `@` suffix (a serve mode, an
  int8 cache and a mesh each add one, and tell another program).

Each engine is built and driven once per module: every case reads what
that one scripted run recorded.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.engine_v2 import chunk_row_widths
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry import TelemetryHub, get_span_store, jit_name
from deepspeed_tpu.telemetry.program_map import _KEPT
from deepspeed_tpu.telemetry.hub import set_hub
from deepspeed_tpu.utils import groups

MAX_BATCH, CHUNK, BUCKET = 20, 8, 32
WIDTHS = chunk_row_widths(MAX_BATCH)          # (16, 20)
V1_SHAPE = (2, 8, 4)                          # rows, prompt, new tokens
GREEDY = V1_SHAPE + (0.0, 0, 1.0, None, 0)
SAMPLED = V1_SHAPE + (0.7, 5, 1.0, None, 0)
WAVE = 3                                      # generate(4): one wave of 3


def _compile_spans():
    return [(s["fields"]["program"], s["fields"]["phase"], s["engine"])
            for s in get_span_store().spans() if s["name"] == "compile"]


def _record(detector, passes):
    """Run each pass of `passes` once and keep, after each, the compile
    spans it left, the detector's programs with their signature counts,
    and its misses."""
    out = []
    for run in passes:
        get_span_store().clear()
        run()
        out.append({"spans": _compile_spans(),
                    "seen": {p: len(s) for p, s in detector._seen.items()},
                    "misses": detector.misses})
    get_span_store().clear()
    return out


@pytest.fixture(scope="module")
def tiny():
    set_hub(TelemetryHub(enabled=False))
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    return (cfg,) + tuple(materialize_params(cfg))


@pytest.fixture(scope="module")
def train_run():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            return jnp.mean((nn.Dense(8)(nn.relu(nn.Dense(16)(x))) - y) ** 2)

    set_hub(TelemetryHub(enabled=False))
    groups.reset_topology()
    topology = groups.initialize(
        groups.MeshTopology(devices=jax.devices()[:1]))
    model = MLP()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)),
                        jnp.zeros((2, 8)))["params"]
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, topology=topology,
        loss_fn=lambda p, b, r: model.apply({"params": p}, b["x"], b["y"]),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2, "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    rng = np.random.default_rng(0)
    micro = {k: rng.standard_normal((2, 8)).astype(np.float32) for k in "xy"}
    fused = {k: rng.standard_normal((4, 8)).astype(np.float32) for k in "xy"}

    def one_pass():
        for _ in range(2):               # two micro steps to the boundary
            engine.backward(engine.forward(micro))
        engine.step()
        engine.train_batch(batch=fused)

    try:
        return _record(engine.recompiles, [one_pass, one_pass])
    finally:
        groups.reset_topology()


@pytest.fixture(scope="module")
def v1_run(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference(model, params=params, dtype="fp32")
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, V1_SHAPE[:2])

    def one_pass():
        eng.generate(ids, max_new_tokens=V1_SHAPE[2])
        eng.generate(ids, max_new_tokens=V1_SHAPE[2], temperature=0.7,
                     top_k=5, seed=3)

    return _record(eng.recompiles, [one_pass, one_pass])


@pytest.fixture(scope="module")
def v2_run(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    eng = InferenceEngineV2(model, params=params, max_batch=MAX_BATCH,
                            max_seq_len=64, split_fuse_chunk=CHUNK,
                            cache_block_size=16, kv_layout="paged",
                            prefix_sharing=False)
    rng = np.random.default_rng(2)
    short = rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
    long_ = rng.integers(1, cfg.vocab_size, 2 * CHUNK + 3).astype(np.int32)
    uids = iter(range(1, 1000))

    def one_pass():
        a, b = next(uids), next(uids)
        tok = int(eng.put([a], [short], argmax_only=True)[a])    # prefill
        tok = int(eng.put([a], [[tok]], argmax_only=True)[a])    # decode
        # a long prompt beside a decoding row: the first chunk round warms
        # `chunk_batch` and `fused_batch` at every width
        eng.put([a, b], [[tok], long_], argmax_only=True)
        while any(s.pending
                  for s in eng.state_manager.tracked_sequences.values()):
            eng.put([], [], argmax_only=True)
        eng._flush_batch([a, b])
        eng.generate([list(short)], max_new_tokens=WAVE + 1)     # a wave

    return _record(eng.recompiles, [one_pass, one_pass])


# (case id, the run's fixture, the detector's name, the span's name, engine)
CASES = [
    ("train-micro", "train_run", "micro", "train:micro", "train"),
    ("train-step", "train_run", "step", "train:step", "train"),
    ("train-train_batch", "train_run", "train_batch", "train:train_batch",
     "train"),
    ("v1-greedy", "v1_run", f"generate:{GREEDY}",
     "v1:generate:b2_s8_n4", "v1"),
    ("v1-sampled", "v1_run", f"generate:{SAMPLED}",
     "v1:generate:b2_s8_n4:t0.7_k5", "v1"),
] + [(f"v2-{name}", "v2_run", name, name, "v2") for name in (
    [f"prefill:{BUCKET}", "decode", f"chunk_batch:{CHUNK}"]
    + [f"fused_batch:{CHUNK}:{w}" for w in WIDTHS]
    + [f"decode_scan:{WAVE}:None"])]


@pytest.mark.parametrize("run,detector_name,span_name,engine",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_a_program_is_registered_once_under_its_stable_name(
        request, run, detector_name, span_name, engine):
    first, second = request.getfixturevalue(run)
    # the two sampling keys of v1 are two programs of one shape: each
    # leaves its own span, the sampled one's name saying how it samples
    shared = sum(1 for c in CASES if c[1] == run and c[3] == span_name)
    assert first["spans"].count((span_name, "first_dispatch", engine)) \
        == shared
    assert [s for s in first["spans"] if s[0] == span_name
            and s[1] != "first_dispatch"] == []
    assert first["seen"].get(detector_name) == 1, sorted(first["seen"])
    assert "@" not in detector_name and "@" not in span_name
    # the jitted function, and so the module a device trace shows, is
    # named after the span: the program map is kept under that name
    kept = {(e["program"], e["detector"]): m for m, e in _KEPT.items()}
    assert kept[(span_name, detector_name)] == "jit_" + jit_name(
        ("v2:" if engine == "v2" else "") + span_name)
    # the second dispatch: no span, no new signature, no miss
    assert second["spans"] == []
    assert second["seen"][detector_name] == 1
    assert second["misses"] == first["misses"] == 0


@pytest.mark.parametrize("run", ["train_run", "v1_run", "v2_run"])
def test_no_program_compiles_outside_the_cases(request, run):
    """Every `compile` span and every detector name of the scripted run is
    one of the cases above: an engine that grew a program would have to
    name it here."""
    first, _ = request.getfixturevalue(run)
    mine = [c for c in CASES if c[1] == run]
    assert sorted({s[0] for s in first["spans"]}) == \
        sorted({c[3] for c in mine})
    assert sorted(first["seen"]) == sorted(c[2] for c in mine)
