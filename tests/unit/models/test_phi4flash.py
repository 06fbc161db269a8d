"""Phi-4-mini-flash through its three passes against the float32 reference
(`perfbench/configs/phi4flash_reference.py`), at a small size on seeded
weights, LOGITS not tokens: the plain forward; a prefill and then decoding
through the caches (rings, the shared slab, the Mamba-1 state) with a prompt
shorter than the window, a generation that crosses it and one well past it;
and the prefill that walks only each row's last position through the cross
decoder against the all-position walk."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import phi4flash
from deepspeed_tpu.models.phi4flash import (Phi4FlashConfig, lambda_init,
                                            materialize_params)
from perfbench.manifest import Manifest

SIZES = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
             num_hidden_layers=8, num_attention_heads=8,
             num_key_value_heads=4, sliding_window=8, layer_norm_eps=1e-5)
CFG = Phi4FlashConfig(**SIZES, dtype=jnp.float32)
REF = Manifest().module("configs", "phi4flash_reference")
ROWS, LENGTH = 3, 30


def moved(params):
    """The seeded tree with its small parameters moved off their initial
    values (biases 0, norm weights 1, D 1): a term the program dropped, or
    took from the wrong layer of a stack, would otherwise not show."""
    def bump(path, x):
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(
            jax.tree_util.keystr(path).encode()) % 2 ** 31)
        small = x.size < 5000
        return x + 0.1 * jax.random.normal(key, x.shape, x.dtype) if small \
            else x
    return jax.tree_util.tree_map_with_path(bump, params)


@pytest.fixture(scope="module")
def served():
    model, params = materialize_params(CFG, jax.random.PRNGKey(0))
    params = moved(params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (ROWS, LENGTH), 1, 128)
    want = REF._head(REF.hidden_states(params, ids, SIZES), params)
    return model, params, ids, np.asarray(want)


def test_layer_kinds_of_the_published_depth():
    cfg = Phi4FlashConfig()
    assert (cfg.num_mamba_layers, cfg.front_pairs, cfg.back_pairs) == (9, 8, 7)
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    assert (cfg.pair_groups, cfg.pair_width) == (10, 128)
    assert cfg.ssm_state_shape == (16, 5120)
    # 0.8 - 0.6 exp(-0.3 i): 0.2 at the first layer, 0.8 far down
    assert float(lambda_init(0)) == pytest.approx(0.2)
    assert float(lambda_init(31)) == pytest.approx(0.8 - 0.6 * np.exp(-9.3))


@pytest.mark.parametrize("bad", [dict(num_hidden_layers=6),
                                 dict(num_hidden_layers=10),
                                 dict(mb_per_layer=4),
                                 dict(tie_word_embeddings=False)])
def test_a_walk_the_program_has_not_is_refused(bad):
    with pytest.raises(ValueError, match="phi4flash"):
        Phi4FlashConfig(**{**SIZES, **bad})


def test_the_plain_forward_is_the_reference_s(served):
    model, params, ids, want = served
    got = model.apply({"params": params}, ids)
    # float32 both: the orders of summation differ, nothing else
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_loss_is_the_reference_s(served):
    model, params, ids, _ = served
    loss = phi4flash.phi4flash_loss_fn(model)(params, {"input_ids": ids}, None)
    assert float(loss) == pytest.approx(
        float(REF.mean_loss(params, ids, SIZES)), rel=1e-5)


# the window is 8: a prompt inside it whose generation crosses it, one that
# fills it exactly, one past it by a part of a window (the ring is written
# rolled), and one more than two windows long
@pytest.mark.parametrize("prompt", [5, 8, 13, 20])
def test_prefill_then_decode_through_the_caches(served, prompt):
    model, params, ids, want = served
    cache = model.make_cache(ROWS, 32, dtype=jnp.float32)
    logits, cache = model.apply({"params": params}, ids[:, :prompt],
                                cache=cache)
    assert logits.shape == (ROWS, 1, 128)         # the last position's alone
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt - 1],
                               atol=2e-5)
    step = jax.jit(lambda tok, cache: model.apply({"params": params}, tok,
                                                  cache=cache))
    for t in range(prompt, LENGTH):
        logits, cache = step(ids[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t],
                                   atol=2e-5, err_msg=f"position {t}")
    assert np.array_equal(np.asarray(cache.index), [LENGTH] * ROWS)
    assert np.array_equal(np.asarray(cache.window.index), cache.index)


@pytest.mark.parametrize("tokens", [10 ** 6, 2 * 13, 13])
def test_the_one_position_cross_prefill_is_the_all_position_walk(
        served, tokens, monkeypatch):
    """Layers 18-31 of the published depth (here 6-7) and the head see only
    each row's last position in a prefill, whether the rows walk the first
    half together or a few at a time; and the cache it leaves is the same."""
    model, params, ids, want = served
    ids = jnp.concatenate([ids, ids[:1]])         # 4 rows: groups of 4, 2, 1
    want = np.concatenate([want, want[:1]])
    monkeypatch.setattr(phi4flash, "PREFILL_TOKENS", tokens)
    (logits, cache), counted = model.apply(
        {"params": params}, ids[:, :13], mutable=["counters"],
        cache=model.make_cache(4, 32, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 12],
                               atol=2e-5)
    assert {k: int(v) for k, v in counted["counters"].items()} == {
        "prompt_positions": 4 * 13, "cross_prefill_positions": 4}
    logits, _ = model.apply({"params": params}, ids[:, 13:14], cache=cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 13],
                               atol=2e-5)


def test_the_cache_by_kind(served):
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    # two rings of 8 slots, ONE slab of 128 that the cross layers read, three
    # Mamba-1 states (16 x 128, float32) and convolution tails
    assert cache.window.ring and not cache.kv.ring
    assert cache.window.k.stack.shape == (2, 2, 2, 8, 16)
    assert cache.kv.k.stack.shape == (1, 2, 2, 128, 16)
    assert cache.state.ssm.shape == (3, 2, 16, 128)
    assert cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (3, 2, 3, 128)
    held = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (cache.kv.k, cache.kv.v, cache.window.k, cache.window.v)))
    assert held == sum(CFG.kv_bytes_by_kind(2, 128, jnp.bfloat16).values())
    assert cache.state.ssm.nbytes + cache.state.conv.nbytes == \
        CFG.recurrent_state_bytes(2, jnp.bfloat16)
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)


def test_the_state_is_kept_in_float32_between_tokens(served):
    """On seeded weights a Mamba layer's output is mostly its `D` skip and
    the state's precision does not reach the logits (PERF.md, PR 45: the
    chip's comparison cannot tell a bf16 state). With the state's inputs
    scaled up so that it carries the layer, a 25-step walk through the caches
    holds the reference to 1e-6 (read: 3e-7), and misses it by 4e-6 with
    the state rounded to bfloat16 between steps."""
    model, params, ids, _ = served

    def louder(path, x):
        name = jax.tree_util.keystr(path[-2:])
        return x * 8.0 if "x_proj" in name else \
            x + 2.0 if name.endswith("['dt_proj']['bias']") else x
    params = jax.tree_util.tree_map_with_path(louder, params)
    want = np.asarray(REF._head(REF.hidden_states(params, ids, SIZES), params))

    def walk(round_state):
        cache = model.make_cache(ROWS, 32, dtype=jnp.float32)
        _, cache = model.apply({"params": params}, ids[:, :5], cache=cache)
        step = jax.jit(lambda tok, cache: model.apply(
            {"params": params}, tok, cache=cache))
        worst = 0.0
        for t in range(5, LENGTH):
            if round_state:
                cache = cache.replace(state=cache.state.replace(
                    ssm=jax.lax.reduce_precision(cache.state.ssm, 8, 7)))
            logits, cache = step(ids[:, t:t + 1], cache)
            worst = max(worst, float(np.abs(np.asarray(logits[:, 0])
                                            - want[:, t]).max()))
        return worst

    kept, rounded = walk(False), walk(True)
    assert kept < 1e-6 and rounded > 2e-6     # read: 3.0e-7 and 4.1e-6
