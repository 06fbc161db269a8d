"""A model whose layers keep caches of different KINDS through v1 `generate`
(Phi-4-mini-flash: rings of a window's slots, one shared full-length slab,
Mamba-1 state): the engine asks the model for its cache, reports K/V bytes
by kind and the state apart, counts the prefill's positions inside the
program, and refuses the streamed serve modes by name. A model of one kind
of layer reports what it reported."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                   kv_cache_bytes,
                                                   recurrent_state_bytes)
from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                              RecurrentState)
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, materialize_params
from deepspeed_tpu.telemetry import TelemetryHub
from deepspeed_tpu.telemetry.hub import get_hub, set_hub

CFG = Phi4FlashConfig(vocab_size=128, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=8, num_attention_heads=8,
                      num_key_value_heads=4, sliding_window=8,
                      dtype=jnp.float32)


@pytest.fixture
def hub(tmp_path):
    path = tmp_path / "serving.jsonl"
    yield set_hub(TelemetryHub(enabled=True, jsonl_path=str(path))), path
    set_hub(TelemetryHub(enabled=False))


def engine(**kw):
    model, params = materialize_params(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                        **kw), model, params


def test_generate_is_the_greedy_walk_of_the_plain_forward(hub):
    eng, model, params = engine()
    assert eng.serve_mode == "dequant"
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 11), 1, 128))
    out = eng.generate(ids, max_new_tokens=9)     # past the window of 8
    assert out.shape == (3, 20) and np.array_equal(out[:, :11], ids)
    logits = np.asarray(jax.jit(lambda p, ids: model.apply({"params": p}, ids))(
        params, jnp.asarray(out)))         # one compile, not one an op
    for t in range(11, 20):
        row = logits[:, t - 1]
        assert np.all(row[np.arange(3), out[:, t]] >= row.max(-1) - 1e-5)

    _, path = hub
    event = [json.loads(l) for l in path.read_text().splitlines()
             if json.loads(l)["kind"] == "serving"][-1]
    kinds = CFG.kv_bytes_by_kind(3, 128, jnp.float32)
    # two rings of 8 slots and one slab of 128, 2 groups x 16 wide, K and V
    assert kinds == {"window_kv_bytes": 2 * 8 * 2 * 3 * 2 * 16 * 4,
                     "shared_kv_bytes": 128 * 2 * 3 * 2 * 16 * 4}
    assert {k: event[k] for k in kinds} == kinds
    assert event["kv_bytes"] == sum(kinds.values())
    assert event["state_bytes"] == CFG.recurrent_state_bytes(3, jnp.float32) \
        == 3 * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    # the prefill took in 3 x 11 positions; one a row walked the cross decoder
    assert (event["prompt_positions"], event["cross_prefill_positions"]) \
        == (33, 3)
    gauges, counters = get_hub().gauges, get_hub().counters
    assert gauges["serving_v1/window_kv_bytes"] == kinds["window_kv_bytes"]
    assert gauges["serving_v1/shared_kv_bytes"] == kinds["shared_kv_bytes"]
    assert counters["serving_v1/cross_prefill_positions"] == 3


@pytest.mark.parametrize("mode", ["layer_scan", "capacity"])
def test_the_streamed_modes_refuse_the_tree_by_name(mode):
    with pytest.raises(ValueError, match="Phi4FlashForCausalLM keeps its own"):
        engine(serve_mode=mode)


def test_bytes_by_kind_are_the_model_s_to_count():
    assert kv_cache_bytes(CFG, 2, 64, jnp.bfloat16) == \
        sum(kv_bytes_by_kind(CFG, 2, 64, jnp.bfloat16).values())
    assert recurrent_state_bytes(CFG, 2, jnp.bfloat16) > 0
    from deepspeed_tpu.models.qwen2 import qwen2_config
    dense = qwen2_config("qwen2-tiny")
    assert kv_bytes_by_kind(dense, 2, 64, jnp.bfloat16) == {}


def cache_of(rows=4):
    return HybridCache(
        kv=KVCache.create_stacked(1, rows, 32, 2, 16, dtype=jnp.float32),
        window=KVCache.create_stacked(2, rows, 8, 2, 16, dtype=jnp.float32,
                                      ring=True),
        state=RecurrentState.create(3, rows, (16, 128), 4, 128,
                                    dtype=jnp.float32))


def test_a_ring_lands_a_token_at_its_position_modulo_the_window():
    cache = cache_of().advance(19)                # cursors at 19: slot 3
    new = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 2, 16))
    ring = cache.window.land(new, 2 * new)
    np.testing.assert_array_equal(np.asarray(ring.k.stack[:, :, :, 3]), new)
    np.testing.assert_array_equal(np.asarray(ring.v.stack[:, :, :, 3]), 2 * new)
    assert float(jnp.abs(ring.k.stack).sum()) == \
        pytest.approx(float(jnp.abs(new).sum()), rel=1e-6)
    slab = cache.kv.land(new[:1], new[:1])        # the slab: at the cursor
    np.testing.assert_array_equal(np.asarray(slab.k.stack[:, :, :, 19]),
                                  new[:1])


def test_rows_of_every_kind_are_cut_and_put_back():
    cache = jax.tree_util.tree_map(
        lambda t: jnp.arange(t.size, dtype=t.dtype).reshape(t.shape),
        cache_of())
    part = cache.rows(jnp.int32(1), 2)
    assert part.window.ring and part.window.k.stack.shape == (2, 2, 2, 8, 16)
    assert part.kv.k.stack.shape == (1, 2, 2, 32, 16)
    assert part.state.ssm.shape == (3, 2, 16, 128)
    np.testing.assert_array_equal(np.asarray(part.index),
                                  np.asarray(cache.index[1:3]))
    np.testing.assert_array_equal(np.asarray(part.window.v.stack),
                                  np.asarray(cache.window.v.stack[:, 1:3]))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, part)
    back = cache.with_rows(zeros, jnp.int32(1))
    assert float(back.state.conv[:, 1:3].sum()) == 0
    np.testing.assert_array_equal(np.asarray(back.kv.k.stack[:, 3]),
                                  np.asarray(cache.kv.k.stack[:, 3]))
    again = back.with_rows(part, jnp.int32(1))
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_cache_without_a_window_advances_as_before():
    cache = HybridCache(
        kv=KVCache.create_stacked(2, 3, 16, 2, 8),
        state=RecurrentState.create(1, 3, (4, 8, 16), 4, 32))
    assert cache.window is None
    moved = cache.advance(5)
    assert moved.window is None and int(moved.index[0]) == 5
    assert cache.rows(0, 2).window is None
