"""A model of mixed layers through v1 `generate`: the engine asks the model
for its cache (K and V of the attention layers, the recurrent layers' state
beside them), counts what the model's layers count inside the program, and
reports K/V bytes and state bytes apart. Every other model's program and
accounting stay what they were."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.capacity_scan import (kv_cache_bytes,
                                                   recurrent_state_bytes)
from deepspeed_tpu.inference.kv_cache import HybridCache, KVCache
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                             materialize_params)
from deepspeed_tpu.telemetry import TelemetryHub
from deepspeed_tpu.telemetry.hub import set_hub

CFG = NemotronHConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=4,
    hybrid_override_pattern="ME*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=4,
    router_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, dtype=jnp.float32)


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
    out = eng.generate(ids, max_new_tokens=6)
    assert out.shape == (3, 17) and np.array_equal(out[:, :11], ids)
    # every generated token is the argmax of the uncached forward over its
    # own prefix, or ties with it to float32 rounding
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(out)))
    for t in range(11, 17):
        row = logits[:, t - 1]
        assert np.all(row[np.arange(3), out[:, t]] >= row.max(-1) - 1e-5)

    _, path = hub
    event = [json.loads(l) for l in path.read_text().splitlines()
             if json.loads(l)["kind"] == "serving"][-1]
    max_len = 128                                    # 11 + 6, rounded up
    # K and V of the ONE attention layer, not of the four layers
    assert event["kv_bytes"] == 2 * 1 * 3 * max_len * 2 * 16 * 4
    assert event["state_bytes"] == CFG.recurrent_state_bytes(3, jnp.float32) \
        == 1 * 3 * (4 * 8 * 16 * 4 + 3 * (32 + 2 * 2 * 16) * 4)
    # two expert layers, a prefill of 11 and 5 decode steps of 1, 3 rows, k 2
    assert event["assignments"] == 2 * 3 * (11 + 5) * 2
    assert 0 < event["held_assignments"] < event["assignments"]
    # a benchmark reads the same off a hub that writes no stream
    from deepspeed_tpu.telemetry import get_hub
    assert get_hub().gauges["serving_v1/state_bytes"] == event["state_bytes"]
    assert get_hub().counters["serving_v1/assignments"] == event["assignments"]


def test_the_accounting_counts_each_kind_apart():
    assert kv_cache_bytes(CFG, 2, 128, jnp.bfloat16) == 2 * 1 * 2 * 128 * 2 * 16 * 2
    assert recurrent_state_bytes(CFG, 2, jnp.bfloat16) == \
        CFG.recurrent_state_bytes(2, jnp.bfloat16) > 0
    from deepspeed_tpu.models.llama import LlamaConfig
    dense = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_hidden_layers=3, num_attention_heads=4,
                        num_key_value_heads=2)
    assert recurrent_state_bytes(dense, 2, jnp.bfloat16) == 0
    assert kv_cache_bytes(dense, 2, 128, jnp.bfloat16) == 2 * 3 * 2 * 128 * 2 * 8 * 2


@pytest.mark.parametrize("mode", ["layer_scan", "capacity"])
def test_the_streamed_modes_refuse_the_tree_by_name(mode):
    with pytest.raises(ValueError, match="NemotronHForCausalLM"):
        engine(serve_mode=mode)


def test_rows_of_a_hybrid_cache_round_trip():
    model, _ = materialize_params(CFG, jax.random.PRNGKey(0))
    cache = model.make_cache(4, 16, dtype=jnp.float32)
    assert isinstance(cache, HybridCache) and isinstance(cache.kv, KVCache)
    cache = jax.tree_util.tree_map(
        lambda t: jax.random.normal(jax.random.PRNGKey(2), t.shape).astype(t.dtype),
        cache)
    part = cache.rows(jnp.int32(2), 2)
    assert part.state.ssm.shape[1] == 2 and part.kv.index.shape == (2,)
    zero = jax.tree_util.tree_map(jnp.zeros_like, cache)
    back = zero.with_rows(part, jnp.int32(2))
    assert jnp.array_equal(back.state.ssm[:, 2:], cache.state.ssm[:, 2:])
    assert jnp.array_equal(back.kv.k[:, 2:], cache.kv.k[:, 2:])
    assert not back.state.conv[:, :2].any() and int(cache.max_len) == 16
