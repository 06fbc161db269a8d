"""The AUTO-input-layout placement of both engines (on by default on a TPU,
where nothing in this suite used to run it): forced on here, on the CPU
mesh. The chip found that re-placing params under the compiled program's
own spelling of their sharding renamed every leaf's placement, and the
pinned v2 serving programs then saw their cache come back under the other
name — a signature miss on every one of them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

import deepspeed_tpu
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.qwen2 import materialize_params, qwen2_config
from deepspeed_tpu.utils import groups


def _model():
    cfg = qwen2_config("qwen2-tiny", dtype=jnp.float32)
    return materialize_params(cfg)


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 256, size=(n,)).tolist() for n in (8, 24, 8, 24)]


def _v1(auto, tp):
    model, params = _model()
    eng = deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                       auto_layouts=auto,
                                       tensor_parallel={"tp_size": tp})
    ids = np.asarray([p for p in _prompts() if len(p) == 8])
    return eng, eng.generate(ids, max_new_tokens=8).tolist()


def _v2(auto, tp):
    model, params = _model()
    eng = InferenceEngineV2(
        model, config=DeepSpeedInferenceConfig(
            dtype="fp32", auto_layouts=auto,
            tensor_parallel={"tp_size": tp}),
        params=params, max_batch=2, max_seq_len=64, cache_block_size=16,
        split_fuse_chunk=16, kv_layout="paged")
    if auto:  # a signature drift shows on the second serve
        eng.generate(_prompts(), max_new_tokens=8)
    return eng, eng.generate(_prompts(), max_new_tokens=8)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("build", [_v1, _v2], ids=["v1", "v2"])
def test_auto_layout_placement_keeps_shardings_and_tokens(build, tp):
    """tp=2: the chip's four-chip run found the layout program compiled for
    replicated inputs (it was lowered on avals without shardings), which
    then refused the tensor-parallel tree."""
    eng, tokens = build(True, tp)
    assert eng._layouts_pinned
    for leaf in jax.tree_util.tree_leaves(eng.params):
        assert isinstance(leaf.sharding, NamedSharding)
    if tp == 2:
        return  # it served; token parity and misses are tp=1's to pin
    # (v2 at tp=2 reports misses with or without AUTO layouts: the detector
    # tells PartitionSpec(None, 'model') from the same spec with trailing
    # Nones — not this file's subject)
    assert eng.recompiles.pinned_misses == 0
    groups.reset_topology()
    _, plain = build(False, tp)
    assert tokens == plain
