"""The index-key kind of `HybridCache` (one key a token a layer BESIDE K and
V, `models/keye_sparse.py`): through `rows` / `with_rows` / `advance` /
`advance_row` / `land` as every other kind, equal cursors; through v1
`generate` (the engine asks the model for its cache and its counters); its
bytes on the `serving` event and as a gauge, beside K and V's and inside
`kv_bytes`; the streamed serve modes refuse the tree by name."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                   kv_cache_bytes)
from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                              LatentCache)
from deepspeed_tpu.models.keye_sparse import (KeyeSparseConfig,
                                              materialize_params)
from deepspeed_tpu.telemetry import TelemetryHub, get_hub
from deepspeed_tpu.telemetry.hub import set_hub

CFG = KeyeSparseConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    indexer_num_heads=4, indexer_head_dim=8, index_topk=8, num_experts=4,
    router_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
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


def filled(cache):
    return jax.tree_util.tree_map(
        lambda t: jax.random.normal(jax.random.PRNGKey(t.ndim),
                                    t.shape).astype(t.dtype), cache)


def test_the_kind_lies_beside_k_and_v_and_moves_with_them():
    model, _ = materialize_params(CFG, jax.random.PRNGKey(0))
    cache = model.make_cache(4, 16, dtype=jnp.float32)
    assert isinstance(cache, HybridCache) and isinstance(cache.kv, KVCache)
    assert isinstance(cache.index_keys, LatentCache) and cache.state is None
    # an 8-value key a token a layer, stored a whole lane row
    assert cache.index_keys.c.stack.shape == (2, 4, 1, 16, 128)
    assert cache.index_keys.max_len == cache.kv.max_len == cache.max_len == 16
    # one cursor a sequence, every kind's equal
    moved = cache.advance(3).advance_row(jnp.int32(2), 5)
    for kind in (moved.kv, moved.index_keys):
        assert np.array_equal(np.asarray(kind.index), [3, 3, 8, 3])
    assert np.array_equal(np.asarray(moved.index), [3, 3, 8, 3])
    # rows are cut and put back through the one tree_map
    cache = filled(cache).advance(0)
    part = cache.rows(jnp.int32(2), 2)
    assert part.index_keys.c.stack.shape == (2, 2, 1, 16, 128)
    assert part.kv.k.stack.shape == (2, 2, 2, 16, 16)
    back = jax.tree_util.tree_map(jnp.zeros_like, cache).with_rows(
        part, jnp.int32(2))
    assert jnp.array_equal(back.index_keys.c.stack[:, 2:],
                           cache.index_keys.c.stack[:, 2:])
    assert not back.index_keys.c.stack[:, :2].any()


def test_a_step_s_index_keys_land_once_at_the_cursors():
    keys = LatentCache.create(2, 3, 16, 8, dtype=jnp.float32).replace(
        index=jnp.asarray([0, 5, 16], jnp.int32))        # the last is parked
    new = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
    landed = keys.land(new)
    assert jnp.array_equal(landed.c.stack[:, 0, 0, 0], new[:, 0])
    assert jnp.array_equal(landed.c.stack[:, 1, 0, 5], new[:, 1])
    assert float(jnp.abs(landed.c.stack).sum()) == pytest.approx(
        float(jnp.abs(new[:, :2]).sum()))               # the parked row's: dropped


def test_generate_is_the_greedy_walk_of_the_plain_forward(hub):
    eng, model, params = engine()
    assert eng.serve_mode == "dequant"
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 11), 1, 128))
    out = eng.generate(ids, max_new_tokens=6)
    assert out.shape == (3, 17) and np.array_equal(out[:, :11], ids)
    # every generated token is the argmax of the uncached forward over its
    # own prefix (positions 9 .. 16 choose 8 of their cached tokens), or ties
    # with it to float32 rounding
    logits = np.asarray(jax.jit(lambda p, ids: model.apply({"params": p}, ids))(
        params, jnp.asarray(out)))
    for t in range(11, 17):
        row = logits[:, t - 1]
        assert np.all(row[np.arange(3), out[:, t]] >= row.max(-1) - 1e-5)

    _, path = hub
    event = [json.loads(l) for l in path.read_text().splitlines()
             if json.loads(l)["kind"] == "serving"][-1]
    max_len = 128                                    # 11 + 6, rounded up
    index = 2 * 3 * max_len * 128 * 4        # a key a token, a whole lane row
    assert event["index_kv_bytes"] == index
    assert event["kv_bytes"] == 2 * 2 * 3 * max_len * 2 * 16 * 4 + index
    assert event["state_bytes"] == 0
    # two layers; a prefill of 11 and 5 decode steps; 3 rows; a query at
    # position t sees t + 1 positions and keeps at most 8
    seen = [t + 1 for t in range(16)]
    assert event["kv_positions_live"] == 2 * 3 * sum(seen)
    assert event["kv_positions_selected"] == 2 * 3 * sum(min(n, 8) for n in seen)
    assert event["assignments"] == 2 * 3 * 16 * 2
    assert 0 < event["held_assignments"] < event["assignments"]
    # a benchmark reads the same off a hub that writes no stream
    assert get_hub().gauges["serving_v1/index_kv_bytes"] == index
    assert get_hub().counters["serving_v1/kv_positions_selected"] == \
        event["kv_positions_selected"]


def test_the_accounting_adds_the_kind_to_k_and_v():
    assert kv_bytes_by_kind(CFG, 2, 128, jnp.bfloat16) == {
        "index_kv_bytes": 2 * 2 * 128 * 128 * 2}
    assert kv_cache_bytes(CFG, 2, 128, jnp.bfloat16) == \
        2 * 2 * 2 * 128 * 2 * 16 * 2 + 2 * 2 * 128 * 128 * 2
    # an int8 K and V would still hold the keys in the compute type beside it
    assert kv_cache_bytes(CFG, 2, 128, jnp.bfloat16, kv_dtype="int8") == \
        2 * 2 * 2 * 128 * 2 * (16 + 4) + 2 * 2 * 128 * 128 * 2


@pytest.mark.parametrize("mode", ["layer_scan", "capacity"])
def test_the_streamed_modes_refuse_the_tree_by_name(mode):
    with pytest.raises(ValueError, match="KeyeSparseForCausalLM"):
        engine(serve_mode=mode)


def test_a_call_s_counters_are_summed_past_int32():
    """The cell's own sums: a prefill forward sows 128 chunks a layer of up
    to 2,048 x 32,768 positions each, 5e10 a batch, and the engine's sum is
    exact (limbs of 16 bits, each leaf split before it is summed)."""
    from deepspeed_tpu.inference.engine import _LIMB, _wide_add
    leaves = [jnp.full((128,), 2048 * 32768 - 7 * i, jnp.int32)
              for i in range(12)]
    want = sum(128 * (2048 * 32768 - 7 * i) for i in range(12))
    assert want > 2 ** 36

    @jax.jit
    def summed(leaves):
        total = (jnp.zeros((), jnp.int32),) * 2
        for _ in range(5):                       # a prefill and four steps
            total = _wide_add(total, leaves)
        return total
    high, low = (int(t) for t in summed(leaves))
    assert 0 <= low < 1 << _LIMB
    assert (high << _LIMB) + low == 5 * want
