"""The stacked dense KV cache of a v1 program, addressed by layer and
written one token a row a step (PR 42): the twin of `test_kv_pool_in_place.py`.

All on the CPU (Pallas kernels in interpret mode):

- KERNELS: `decode_attention` on the stacked cache with layer `l` equals its
  per-layer call on the slice, bit for bit; with the step's token STAGED it
  equals the token written then attended, bit for bit; the writer `kv_write_dense` leaves
  the stacks as the XLA scatter of `KVCache.land` does, bit for bit.
- WRITES: a prefill's rows written into the stack (`update_layer` on
  `DenseLayer` views) equal the per-layer scatter at any cursor, past
  `M - S` and parked ones too; the per-layer view (bare arrays,
  `QuantizedKVLayer`) is untouched.
- PROGRAMS: prefill and 64 decode steps of the llama model over the stacked
  cache return the logits of the model over the per-layer view (the old
  scan, which `llama.py` keeps for a cache in that view), rows at
  different cursors, on the XLA path and with the kernels on; v1
  `generate` returns the same tokens as the engine whose model has no say
  in its cache. The benchmark's `correct` judges only the first token, the
  prefill: these hold the decode path.
- STRUCTURE: the dense cached scan scans over no stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.kv_cache import (DenseLayer, KVCache,
                                              QuantizedKVLayer, update_layer)
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        materialize_params)
from deepspeed_tpu.ops.attention import cached_attention
from deepspeed_tpu.ops.pallas import decode_attention as da

L, B, HKV, M, D, BLK = 3, 5, 2, 32, 16, 8

# Each kernel under ONE `jax.jit` for the module: a bare call compiles the
# interpreted kernel anew every time, and the kernel tests below call one up
# to seven times at one shape.
decode_attention = jax.jit(da.decode_attention, static_argnames=("block_k",))
kv_write_dense = jax.jit(da.kv_write_dense)


def _stacks(rng, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.standard_normal((L, B, HKV, M, D)), dtype)
                 for _ in range(2))


def _per_layer(stack):
    """(L, B, Hkv, M, D) -> the per-layer view's (L, B, M, Hkv, D)."""
    return jnp.swapaxes(stack, 2, 3)


# a cursor inside a block, on a block's edge, one slot before the end, the
# first slot, and a row parked at M
CURSORS = np.asarray([5, BLK, M - 1, 0, M], np.int32)


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("n_rep", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_decode_kernel_reads_the_layer_it_is_given(dtype, n_rep):
    rng = np.random.default_rng(0)
    k, v = _stacks(rng, dtype)
    q = jnp.asarray(rng.standard_normal((B, 1, HKV * n_rep, D)), dtype)
    lengths = jnp.asarray(CURSORS + 1)
    outs = []
    for l in range(L):
        got = decode_attention(q, k, v, lengths, layer=jnp.int32(l),
                               block_k=BLK)
        want = decode_attention(q, _per_layer(k)[l], _per_layer(v)[l],
                                lengths, block_k=BLK)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        outs.append(np.asarray(got, np.float32))
    assert not np.array_equal(outs[0], outs[1])  # the layers do differ


@pytest.mark.parametrize("n_rep", [8, 16])
def test_staged_token_equals_token_written_then_attended(n_rep):
    rng = np.random.default_rng(1)
    k, v = _stacks(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, HKV * n_rep, D)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, L, B, HKV, D)), jnp.float32)
    index = jnp.asarray(CURSORS)
    k_w, v_w = kv_write_dense(k, v, new[0], new[1], index)
    for l in range(L):
        got = decode_attention(q, k, v, index + 1, layer=jnp.int32(l),
                               block_k=BLK, k_new=new[0, l], v_new=new[1, l])
        want = decode_attention(q, k_w, v_w, index + 1, layer=jnp.int32(l),
                                block_k=BLK)
        # the staged key takes the written key's place in its tile: the same
        # arithmetic, and a parked row's token is dropped by both
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    bare = decode_attention(q, k, v, index + 1, layer=jnp.int32(0),
                            block_k=BLK)
    assert not np.array_equal(np.asarray(got)[:-1], np.asarray(bare)[:-1])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_writer_equals_the_scatter_and_drops_a_parked_row(dtype):
    rng = np.random.default_rng(2)
    k, v = _stacks(rng, dtype)
    new = jnp.asarray(rng.standard_normal((2, L, B, HKV, D)), dtype)
    cache = KVCache(k=DenseLayer(k), v=DenseLayer(v),
                    index=jnp.asarray(CURSORS))
    want = cache.land(new[0], new[1])         # off the chip: the XLA scatter
    got = kv_write_dense(k, v, new[0], new[1], cache.index)
    for g, w, old in ((got[0], want.k.stack, k), (got[1], want.v.stack, v)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
        g = np.asarray(g, np.float32)
        # one slot a live row a layer changed, and nothing of the parked row
        changed = (g != np.asarray(old, np.float32)).any(axis=(2, 4))
        assert changed.sum() == L * (B - 1) and not changed[:, -1].any()
        for b, c in enumerate(CURSORS[:-1]):
            assert changed[:, b, c].all()


def test_cached_attention_folds_the_staged_token_on_the_xla_path():
    """Off the chip a staged view attends through the masked XLA path, the
    staged token over its cursor's slot: as the token written then attended."""
    from deepspeed_tpu.inference.kv_cache import decode_mask
    rng = np.random.default_rng(3)
    k, v = _stacks(rng)
    q = jnp.asarray(rng.standard_normal((B - 1, 1, HKV * 4, D)), jnp.float32)
    k, v = k[:, :B - 1], v[:, :B - 1]
    index = jnp.asarray(CURSORS[:-1])
    new = jnp.asarray(rng.standard_normal((2, B - 1, 1, HKV, D)), jnp.float32)
    mask = decode_mask(index[:, None], M)
    views = (DenseLayer(k, jnp.int32(1), staged=True),
             DenseLayer(v, jnp.int32(1), staged=True))
    staged = update_layer(*views, new[0], new[1], index)
    assert staged[0].stack is k and staged[0].stage.shape == (B - 1, HKV, D)
    got = cached_attention(q, *staged, index, mask)
    k_l, v_l = update_layer(_per_layer(k)[1], _per_layer(v)[1], new[0], new[1],
                            index)
    want = cached_attention(q, k_l, v_l, index, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


# ------------------------------------------------------------------- writes


@pytest.mark.parametrize("s", [1, 4, BLK + 3])
def test_rows_written_into_the_stack_equal_the_per_layer_scatter(s):
    """Any cursor by contract: mid-row, one that pushes tokens past M (those
    drop, the rest land), a parked row (all drop)."""
    rng = np.random.default_rng(4)
    k, v = _stacks(rng)
    new = jnp.asarray(rng.standard_normal((2, B, s, HKV, D)), jnp.float32)
    index = jnp.asarray([0, 7, M - s, M - s + 2 if s > 2 else M - 1, M],
                        jnp.int32)
    views = (DenseLayer(k, jnp.int32(2)), DenseLayer(v, jnp.int32(2)))
    got = jax.jit(update_layer)(*views, new[0], new[1], index)
    want = update_layer(_per_layer(k)[2], _per_layer(v)[2], new[0], new[1],
                        index)
    for g, w, old in zip(got, want, (k, v)):
        np.testing.assert_array_equal(np.asarray(_per_layer(g.stack)[2]),
                                      np.asarray(w))
        for l in (0, 1):  # the other layers are not touched
            np.testing.assert_array_equal(np.asarray(g.stack[l]),
                                          np.asarray(old[l]))
    assert not np.array_equal(np.asarray(got[0].stack[2]), np.asarray(k[2]))


def test_the_per_layer_view_is_what_it_was():
    """Bare arrays and `QuantizedKVLayer` keep the (B, M, Hkv, D) order and
    the scatter: values written where the parent wrote them."""
    rng = np.random.default_rng(5)
    k = jnp.asarray(rng.standard_normal((B, M, HKV, D)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((B, 2, HKV, D)), jnp.float32)
    index = jnp.asarray([0, 7, M - 2, M - 1, M], jnp.int32)
    got, _ = update_layer(k, k, new, new, index)
    want = np.asarray(k).copy()
    for b, c in enumerate(np.asarray(index)):
        for t in range(2):
            if c + t < M:
                want[b, c + t] = np.asarray(new)[b, t]
    np.testing.assert_array_equal(np.asarray(got), want)
    q8 = QuantizedKVLayer(data=jnp.zeros((B, M, HKV, D), jnp.int8),
                          scales=jnp.ones((B, M, HKV), jnp.float32))
    got8, _ = update_layer(q8, q8, new, new, index)
    assert isinstance(got8, QuantizedKVLayer) and got8.data.shape == q8.shape
    assert np.asarray(got8.data[1, 7]).any() and not np.asarray(
        got8.data[4]).any()
    # `create` is the per-layer view, `create_stacked` the kernel's order
    assert KVCache.create(L, B, M, HKV, D).k.shape == (L, B, M, HKV, D)
    stacked = KVCache.create_stacked(L, B, M, HKV, D)
    assert stacked.k.stack.shape == (L, B, HKV, M, D)
    assert stacked.stacked and stacked.max_len == M
    assert not KVCache.create(L, B, M, HKV, D).stacked


# ----------------------------------------------------------------- programs


class PerLayerViewDense(LlamaForCausalLM):
    """`LlamaForCausalLM` with no say in its cache: the v1 engine hands it
    the per-layer view, and `llama.py` scans over `(cache.k, cache.v)` as it
    did before PR 42. The reference the layer-indexed scan must equal."""
    make_cache = None


def _cfg(dtype):
    return LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=96,
                       num_hidden_layers=3, num_attention_heads=8,
                       num_key_value_heads=2, max_position_embeddings=256,
                       dtype=dtype)


PARAMS = {}


def _params(dtype):
    if dtype not in PARAMS:
        PARAMS[dtype] = materialize_params(
            _cfg(dtype), param_dtype=jnp.dtype(dtype))[1]
    return PARAMS[dtype]


STEPS, PROMPT, ROWS, CAP = 64, 6, 4, 128
START = np.asarray([0, 3, 11, 40], np.int32)  # rows at different cursors


def _walk(model, params, cache, ids, forced):
    """Prefill `ids` then `STEPS` decode steps fed `forced` (the same tokens
    to both models, so one near-tie cannot fork the comparison): the
    logits of every step."""
    @jax.jit
    def run(params, cache, ids, forced):
        logits, cache = model.apply({"params": params}, ids, cache=cache)

        def step(cache, tok):
            out, cache = model.apply({"params": params}, tok[:, None],
                                     cache=cache)
            return cache, out[:, 0]
        cache, steps = jax.lax.scan(step, cache, forced.T)
        return logits[:, -1], steps, cache
    return run(params, cache, ids, forced)


def _caches(cfg, rng):
    """The same random cache in both views, the rows' cursors at `START`."""
    per_layer = jnp.asarray(rng.standard_normal(
        (2, cfg.num_hidden_layers, ROWS, CAP, cfg.num_key_value_heads,
         cfg.head_dim)), cfg.dtype)
    index = jnp.asarray(START)
    old = KVCache(k=per_layer[0], v=per_layer[1], index=index)
    new = KVCache(k=DenseLayer(jnp.swapaxes(per_layer[0], 2, 3)),
                  v=DenseLayer(jnp.swapaxes(per_layer[1], 2, 3)), index=index)
    return new, old


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["f32", "bf16"])
def test_decode_steps_equal_the_per_layer_view_scan(monkeypatch, dtype, tol,
                                                    kernels):
    if kernels:  # the chip's path, interpreted: the kernel by layer with
        # the staged token, and the Pallas writer
        import deepspeed_tpu.ops.attention as attention
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    cfg, rng = _cfg(dtype), np.random.default_rng(6)
    new, old = _caches(cfg, rng)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (ROWS, PROMPT)))
    forced = jnp.asarray(rng.integers(0, cfg.vocab_size, (ROWS, STEPS)))
    model = LlamaForCausalLM(cfg)
    got = _walk(model, _params(dtype), new, ids, forced)
    want = _walk(model, _params(dtype), old, ids, forced)
    for g, w in zip(got[:2], want[:2]):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, atol=tol * np.abs(w).max(), rtol=0)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(got[1]).argmax(-1),
                                      np.asarray(want[1]).argmax(-1))
    # the whole cache: every token where the per-layer view has it
    assert np.array_equal(np.asarray(got[2].index), START + PROMPT + STEPS)
    for g, w in ((got[2].k, want[2].k), (got[2].v, want[2].v)):
        np.testing.assert_allclose(
            np.asarray(_per_layer(g.stack), np.float32),
            np.asarray(w, np.float32), atol=tol, rtol=0)
    if kernels:
        jaxpr = str(jax.make_jaxpr(lambda p, c, t: model.apply(
            {"params": p}, t, cache=c))(_params(dtype), new, forced[:, :1]))
        assert "kv_write_dense" in jaxpr and "self_attn_dense_decode" in jaxpr
        assert "scatter" not in jaxpr


def test_v1_generate_returns_the_per_layer_engines_tokens():
    from deepspeed_tpu.utils import groups
    cfg = _cfg(jnp.float32)
    ids = np.asarray(np.random.default_rng(7).integers(1, cfg.vocab_size,
                                                       (3, 9)))
    outs = []
    for cls in (LlamaForCausalLM, PerLayerViewDense):
        groups.reset_topology()
        eng = deepspeed_tpu.init_inference(cls(cfg), params=_params(jnp.float32),
                                           dtype="fp32")
        outs.append(np.asarray(eng.generate(ids, max_new_tokens=STEPS + 1)))
    assert outs[0].shape == (3, 9 + STEPS + 1)
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("cls,kernels,counted", [
    (LlamaForCausalLM, True, True),
    (LlamaForCausalLM, False, False),    # the XLA path: no kernel, no count
    (PerLayerViewDense, True, False),    # a per-layer model: not the stack
], ids=["stacked-kernel", "stacked-xla", "per-layer"])
def test_serving_event_counts_what_the_decode_kernel_fetches(
        monkeypatch, tmp_path, cls, kernels, counted):
    """`dense_kv_slots_live` / `dense_kv_slots_fetched` /
    `dense_decode_grid_steps` (PR 49): from the kernel's own plan, on the
    host, only where the stacked dense cache meets the kernel."""
    import json

    import deepspeed_tpu.ops.attention as attention
    from deepspeed_tpu.ops.pallas.decode_attention import decode_plan
    from deepspeed_tpu.telemetry import TelemetryHub, get_hub, set_hub
    from deepspeed_tpu.utils import groups
    monkeypatch.setattr(attention, "_use_pallas", lambda: kernels)
    cfg = _cfg(jnp.float32)
    rows, prompt, new = 3, 9, 5
    ids = np.asarray(np.random.default_rng(8).integers(1, cfg.vocab_size,
                                                       (rows, prompt)))
    path = tmp_path / "serving.jsonl"
    set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    try:
        groups.reset_topology()
        eng = deepspeed_tpu.init_inference(cls(cfg), params=_params(jnp.float32),
                                           dtype="fp32")
        eng.generate(ids, max_new_tokens=new)
        event = [json.loads(l) for l in path.read_text().splitlines()
                 if json.loads(l)["kind"] == "serving"][-1]
        names = ("dense_kv_slots_live", "dense_kv_slots_fetched",
                 "dense_decode_grid_steps")
        if not counted:
            assert not any(n in event for n in names)
            assert not any(f"serving_v1/{n}" in get_hub().gauges for n in names)
            return
        layers, max_len = cfg.num_hidden_layers, 128   # 9 + 5, rounded up
        rb, blk_k = decode_plan(rows, cfg.num_key_value_heads, max_len,
                                cfg.head_dim, 4)
        assert (rb, blk_k) == (1, 128)   # three rows: a group each
        # four decode steps attend 10, 11, 12 and 13 tokens a row (the
        # step's staged token among them), each inside the first block
        assert event["dense_kv_slots_live"] == layers * rows * (10 + 11 + 12 + 13)
        assert event["dense_kv_slots_fetched"] == layers * 4 * rows * blk_k
        assert event["dense_decode_grid_steps"] == layers * 4 * rows * 1
        for n in names:
            assert get_hub().gauges[f"serving_v1/{n}"] == event[n]
    finally:
        set_hub(TelemetryHub(enabled=False))


def test_an_int8_cache_keeps_the_per_layer_view():
    model = LlamaForCausalLM(_cfg(jnp.float32))
    assert model.make_cache(2, 128).stacked
    q8 = model.make_cache(2, 128, quantized=True)
    assert q8.quantized and not q8.stacked


# ---------------------------------------------------------------- structure


def _scans(jaxpr):
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    return [e for _, e in primitive_eqns(jaxpr, ["scan"])]


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "prefill"])
def test_dense_scan_scans_over_no_stack(s):
    cfg = _cfg(jnp.float32)
    model = LlamaForCausalLM(cfg)
    cache = model.make_cache(2, 128)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: model.apply({"params": p}, i, cache=c))(
        _params(jnp.float32), jnp.zeros((2, s), jnp.int32), cache)
    stack = tuple(cache.k.stack.shape)
    (eqn,) = [e for e in _scans(jaxpr)
              if e.params["length"] == cfg.num_hidden_layers]
    nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in eqn.invars]
    consts, carry, xs = shapes[:nc], shapes[nc:nc + nk], shapes[nc + nk:]
    ys = [tuple(v.aval.shape) for v in eqn.outvars[nk:]]
    assert stack not in xs and stack not in ys
    # prefill carries the stacks and writes them; decode closes over them
    # and stages (the scan's outputs are the step's new tokens)
    assert (carry.count(stack), consts.count(stack)) == (
        (0, 2) if s == 1 else (2, 0))
    if s == 1:
        assert ys.count((cfg.num_hidden_layers, 2, cfg.num_key_value_heads,
                         cfg.head_dim)) == 2
