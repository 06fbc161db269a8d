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
    logits = np.asarray(jax.jit(lambda p, ids: model.apply({"params": p}, ids))(
        params, jnp.asarray(out)))         # one compile, not one an op
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
    # what the other held-expert families count too (PR 66): the held
    # experts a pass reads, of those held a layer a pass (a prefill and 5
    # decode steps), and the grouped GEMM's second row tiles an expert
    assert event["experts_held"] == 2 * (1 + 5) * CFG.n_routed_experts
    assert 0 < event["experts_touched"] <= event["experts_held"]
    assert 0 <= event["weight_tile_revisits"] <= event["held_assignments"]
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
    assert jnp.array_equal(back.kv.k.stack[:, 2:], cache.kv.k.stack[:, 2:])
    assert not back.state.conv[:, :2].any() and int(cache.max_len) == 16


# ------------------------------------------- the stacked K/V of a hybrid cache


def _per_layer_view_modules():
    """`Attention` and `Layers` as they were before PR 42: an attention layer
    cuts its K/V out of the per-layer-view stack (`kv.k[slot]`), scatters
    into the slice and writes it back. The reference the stacked view must
    equal."""
    import flax.linen as nn
    from deepspeed_tpu.inference.kv_cache import decode_mask, update_layer
    from deepspeed_tpu.models import hybrid, nemotron_h
    from deepspeed_tpu.ops.attention import cached_attention

    class PerLayerAttention(nemotron_h.Attention):
        @nn.compact
        def __call__(self, h, kv=None, slot=None):
            cfg = self.cfg
            hd, nh, nkv = cfg.head_dim, cfg.num_attention_heads, \
                cfg.num_key_value_heads
            b, s, _ = h.shape
            dense = nemotron_h._dense
            q = dense(nh * hd, ("embed", "heads"), cfg.dtype, "q_proj")(h)
            k = dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "k_proj")(h)
            v = dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "v_proj")(h)
            q, k, v = (q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd),
                       v.reshape(b, s, nkv, hd))
            index = kv.index
            k_l, v_l = update_layer(kv.k[slot], kv.v[slot], k, v, index)
            kv = kv.replace(
                k=jax.lax.dynamic_update_index_in_dim(kv.k, k_l, slot, 0),
                v=jax.lax.dynamic_update_index_in_dim(kv.v, v_l, slot, 0))
            pos = index[:, None] + jnp.arange(s)[None, :]
            ctx = cached_attention(q, k_l, v_l, index,
                                   decode_mask(pos, k_l.shape[1]),
                                   impl=cfg.attn_impl)
            out = dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                        "o_proj")(ctx.reshape(b, s, nh * hd))
            return out, kv

    class PerLayerLayers(nemotron_h.Layers):
        @nn.compact
        def __call__(self, h, cache=None):
            cfg = self.cfg
            state, kv = cache.state, cache.kv
            pattern = cfg.hybrid_override_pattern
            for i, kind in enumerate(pattern):
                slot = pattern[:i].count(kind)
                x = nemotron_h.RMSNorm(cfg.norm_eps, cfg.dtype,
                                       name=f"layer_{i}_norm")(h)
                if kind == "M":
                    out, state = nemotron_h.MambaMixer(
                        cfg, name=f"layer_{i}")(x, state, slot)
                elif kind == "*":
                    out, kv = PerLayerAttention(cfg, name=f"layer_{i}")(
                        x, kv, slot)
                else:
                    out = hybrid.held_experts(
                        cfg, f"layer_{i}", held=cfg.n_routed_experts,
                        activation="relu2", score_fn="sigmoid",
                        shared=cfg.moe_shared_expert_intermediate_size)(
                            x, train=False)
                h = h + out
            return h, cache.replace(state=state, kv=kv)

    return PerLayerLayers


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_decode_steps_equal_the_per_layer_view_of_the_attention_layers(
        monkeypatch, kernels):
    """Prefill and 64 decode steps through a `HybridCache` whose K/V lie in
    the stacked view (two attention layers, by slot; a decode step stages
    both tokens and lands them with one write) against the per-layer view:
    the logits of every step within 1e-5, rows at different cursors."""
    import dataclasses
    from deepspeed_tpu.models import nemotron_h
    cfg = dataclasses.replace(CFG, hybrid_override_pattern="M*E*",
                              num_attention_heads=8)
    if kernels:
        import deepspeed_tpu.ops.attention as attention
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    model, params = materialize_params(cfg, jax.random.PRNGKey(0))
    steps, rows = 64, 3
    rng = np.random.default_rng(8)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, 5)))
    forced = jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, steps)))
    new = model.make_cache(rows, 128, dtype=jnp.float32)
    assert new.kv.stacked and new.kv.k.stack.shape == (2, rows, 2, 128, 16)
    fill = jnp.asarray(rng.standard_normal((2,) + new.kv.k.stack.shape),
                       jnp.float32)
    index = jnp.asarray([0, 9, 30], jnp.int32)
    from deepspeed_tpu.inference.kv_cache import DenseLayer
    new = new.replace(kv=KVCache(k=DenseLayer(fill[0]), v=DenseLayer(fill[1]),
                                 index=index))
    old = new.replace(kv=KVCache(k=jnp.swapaxes(fill[0], 2, 3),
                                 v=jnp.swapaxes(fill[1], 2, 3), index=index))

    def walk(cache):
        @jax.jit
        def run(params, cache, ids, forced):
            logits, cache = model.apply({"params": params}, ids, cache=cache)

            def step(cache, tok):
                out, cache = model.apply({"params": params}, tok[:, None],
                                         cache=cache)
                return cache, out[:, 0]
            cache, outs = jax.lax.scan(step, cache, forced.T)
            return logits[:, -1], outs, cache
        return run(params, cache, ids, forced)

    got = walk(new)
    monkeypatch.setattr(nemotron_h, "Layers", _per_layer_view_modules())
    want = walk(old)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=0)
    np.testing.assert_array_equal(np.asarray(got[2].index),
                                  np.asarray(index) + 5 + steps)
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(got[2].kv.k.stack, 2, 3)),
        np.asarray(want[2].kv.k), atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(got[2].state.ssm),
                                  np.asarray(want[2].state.ssm))
