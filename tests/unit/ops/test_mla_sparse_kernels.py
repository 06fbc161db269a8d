"""The kernels that read a LATENT cache under a learned choice
(`ops/pallas/mla_sparse.py`) against their plain `jax.numpy` forms, in
interpret mode (the chip's compiler sees them in `test_chip_compile.py`, the
chip in `chip_smoke.py`): the decode step over the chosen rows in both forms
of its read (the slab under the bias, the chosen rows gathered), the step's
token staged, a row shorter than `topk`, ties in the choice; a prefill chunk
at q/k 24 and v 16 whose keys and values are expanded a block of slots at a
time; the choice itself at the indexer's sizes of this family (`index_n_heads`
heads of a whole lane row); and YaRN in `ops.attention.rope_cos_sin` against
the published formula written out. Each kernel under ONE module-level
`jax.jit` a shape."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention as ops
from deepspeed_tpu.ops.pallas import mla_sparse as ms
from deepspeed_tpu.ops.pallas import sparse_select as ss

F32 = jnp.float32
L, B, M, RANK, DR, H, DN, DV, HI, DI = 2, 3, 256, 32, 8, 4, 16, 16, 4, 128
LAYER, SCALE = 1, 0.3


def normal(key, shape):
    return jax.random.normal(key, shape, F32)


@pytest.fixture(scope="module")
def slabs():
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    return normal(k[0], (L, B, 1, M, RANK + DR)), normal(k[1], (L, B, 1, M, DI))


@functools.partial(jax.jit, static_argnames=("topk",))
def select(q, w, stack, lengths, new, topk):
    return ss.sparse_index_select(q, w, stack, LAYER, lengths, topk, new)


@functools.partial(jax.jit, static_argnames=("topk",))
def select_plain(q, w, stack, lengths, new, topk):
    return ss.sparse_index_select_reference(q, w, stack, LAYER, lengths, topk,
                                            new)


@jax.jit
def decode_slab(q_lat, q_rope, stack, lengths, bias, new):
    return ms.mla_sparse_decode(q_lat, q_rope, stack, LAYER, lengths, bias,
                                SCALE, new)


@functools.partial(jax.jit, static_argnames=("topk",))
def decode_gathered(q_lat, q_rope, stack, lengths, bias, kept, new, topk):
    return ms.mla_sparse_decode_gathered(q_lat, q_rope, stack, LAYER, lengths,
                                         bias, kept, topk, SCALE, new)


@jax.jit
def decode_plain(q_lat, q_rope, stack, lengths, bias, new):
    return ms.mla_sparse_decode_reference(q_lat, q_rope, stack, LAYER, lengths,
                                          bias, SCALE, new)


# lengths: past `topk` (the choice drops rows), under it (every live row is
# kept), one token (the staged one alone), the slab full
@pytest.mark.parametrize("lengths,topk", [([200, 17, 256], 24), ([5, 1, 9], 24),
                                          ([256, 130, 64], 128)],
                         ids=["drops", "short", "full"])
def test_the_decode_step_reads_the_chosen_rows_in_both_forms(slabs, lengths,
                                                             topk):
    lat, keys = slabs
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_i, w = normal(k[0], (B, HI, DI)), normal(k[1], (B, HI))
    new_i, new = normal(k[2], (B, DI)), normal(k[3], (B, RANK + DR))
    q_lat, q_rope = normal(k[4], (B, H, RANK)), normal(k[5], (B, H, DR))
    bias, kept = select(q_i, w, keys, lengths, new_i, topk)
    bias_p, kept_p = select_plain(q_i, w, keys, lengths, new_i, topk)
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(bias_p))
    np.testing.assert_array_equal(np.asarray(kept),
                                  np.minimum(np.asarray(lengths), topk))
    want = decode_plain(q_lat, q_rope, lat, lengths, bias, new)
    np.testing.assert_allclose(
        decode_slab(q_lat, q_rope, lat, lengths, bias, new), want, atol=2e-6)
    np.testing.assert_allclose(
        decode_gathered(q_lat, q_rope, lat, lengths, bias, kept, new, topk),
        want, atol=2e-6)
    # the staged token is in the result: without it the step reads the
    # slot's stale row
    if int(lengths[0]) <= topk:
        stale = decode_plain(q_lat, q_rope, lat, lengths, bias,
                             lat[LAYER, :, 0, 0])
        assert float(jnp.abs(stale - want).max()) > 1e-3


def test_the_gathered_slots_are_the_bias_s_zeros_in_order():
    bias = jnp.where(jnp.asarray([[1, 0, 0, 1, 1, 0], [0, 0, 0, 0, 1, 0]],
                                 bool), 0.0, ss.NEG_INF)
    np.testing.assert_array_equal(np.asarray(ms.chosen_slots(bias, 4)),
                                  [[0, 3, 4, 6], [4, 6, 6, 6]])


def test_ties_go_to_the_lower_slot_in_the_kernel_s_choice(slabs):
    """Index keys that are equal tie exactly; of the slots at the threshold
    the lowest are kept, `jax.lax.top_k`'s set, and the gathered read
    follows the same set."""
    lat, keys = slabs
    keys = keys.at[LAYER, 0, 0, :64].set(keys[LAYER, 0, 0, 0])     # 64 equal
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    lengths = jnp.asarray([64, 40, 50], jnp.int32)
    q_i, w = normal(k[0], (B, HI, DI)), normal(k[1], (B, HI))
    new_i = keys[LAYER, :, 0, 0]                # row 0's staged key ties too
    bias, kept = select(q_i, w, keys, lengths, new_i, 24)
    np.testing.assert_array_equal(np.asarray(bias[0] == 0.0),
                                  np.arange(M) < 24)
    bias_p, _ = select_plain(q_i, w, keys, lengths, new_i, 24)
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(bias_p))
    assert list(np.asarray(kept)) == [24, 24, 24]


@functools.partial(jax.jit, static_argnames=("topk",))
def prefill(q_nope, q_rope, w_kvb, q_i, w, lat, keys, row, start, topk):
    bias, kept = ss.sparse_prefill_choice(q_i, w, keys, LAYER, row, start,
                                          topk)
    return ms.mla_sparse_prefill(q_nope, q_rope, w_kvb, bias, lat, LAYER, row,
                                 start, SCALE), bias, kept


@jax.jit
def prefill_plain(q_nope, q_rope, w_kvb, bias, lat, row, start):
    return ms.mla_sparse_prefill_reference(q_nope, q_rope, w_kvb, bias, lat,
                                           LAYER, row, start, SCALE)


# a chunk at the row's start (queries 0 .. 127: the first keep every
# position) and one further on (every query drops rows); the expansion walks
# 1 and 2 live blocks of 128 slots, in two groups of 2 heads
@pytest.mark.parametrize("start", [0, 128])
def test_a_prefill_chunk_attends_its_choice_in_the_expanded_form(
        slabs, start, monkeypatch):
    lat, keys = slabs
    monkeypatch.setattr(ms, "EXPAND_HEADS", 2)
    monkeypatch.setattr(ms, "EXPAND_BLOCK", 128)
    monkeypatch.setattr(ms, "PREFILL_QUERIES", 64)
    monkeypatch.setattr(ms, "PREFILL_BLOCK", 64)
    c, row, topk = 128, 1, 24
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    q_nope, q_rope = normal(k[0], (c, H, DN)), normal(k[1], (c, H, DR))
    w_kvb = normal(k[2], (RANK, H, DN + DV)) * 0.2
    q_i, w = normal(k[3], (c, HI, DI)), normal(k[4], (c, HI))
    # slots past the chunk's end hold what an earlier batch left: never read
    lat = lat.at[:, :, :, start + c:].set(1e4)
    got, bias, kept = prefill(q_nope, q_rope, w_kvb, q_i, w, lat, keys, row,
                              start, topk)
    np.testing.assert_array_equal(
        np.asarray(kept), np.minimum(start + np.arange(c) + 1, topk))
    live = np.arange(M)[None, :] <= (start + np.arange(c))[:, None]
    chosen = ss.chosen(ss.index_scores(q_i, w, keys[LAYER, row, 0]),
                       jnp.asarray(live), topk)
    np.testing.assert_array_equal(np.asarray(bias) == 0.0, np.asarray(chosen))
    want = prefill_plain(q_nope, q_rope, w_kvb,
                         jnp.where(chosen, 0.0, ss.NEG_INF),
                         lat.at[:, :, :, start + c:].set(0.0), row, start)
    np.testing.assert_allclose(got, want, atol=5e-6)


# ------------------------------------------------------------------- YaRN


def _yarn_by_hand(dim, theta, factor, original, beta_fast, beta_slow):
    """The published `find_correction_range` / `linear_ramp_factor`, a pair
    at a time in Python floats."""
    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        freq = theta ** (-2 * i / dim)
        out.append(freq / factor * ramp + freq * (1 - ramp))
    return np.asarray(out), low, high


def test_yarn_is_the_published_ramp_and_the_default_is_untouched():
    from deepspeed_tpu.models.deepseek_sparse import YarnScaling
    rs = YarnScaling()          # DeepSeek-V3.2's: 40 x 4,096, beta 32 and 1
    want, low, high = _yarn_by_hand(64, 10000.0, 40, 4096, 32, 1)
    # of the 32 pairs the first 11 keep their frequency, the last 9 are
    # divided by 40, a ramp between
    assert (low, high) == (10, 23)
    got = np.asarray(ops.yarn_inv_freq(64, 10000.0, rs))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert ops.yarn_mscale(40.0, 1.0) == pytest.approx(0.1 * math.log(40) + 1)
    assert ops.yarn_mscale(1.0, 1.0) == 1.0
    positions = jnp.arange(50)[None]
    cos, sin = ops.rope_cos_sin(positions, 64, 10000.0, F32, rs)
    np.testing.assert_allclose(cos[0], np.cos(np.arange(50)[:, None] * want),
                               atol=1e-5)
    # mscale != mscale_all_dim scales the tables by their ratio
    cos2, _ = ops.rope_cos_sin(positions, 64, 10000.0, F32,
                               YarnScaling(mscale=2.0))
    np.testing.assert_allclose(
        cos2, cos * (0.2 * math.log(40) + 1) / (0.1 * math.log(40) + 1),
        atol=1e-5)
    # no `rope_scaling`: the tables every other family is served from, and
    # the program that makes them, are what they were
    base = ops.rope_cos_sin(positions, 64, 10000.0, F32)
    np.testing.assert_allclose(base[0][0], np.cos(np.arange(50)[:, None]
                                                  * plain), atol=1e-5)
    text = jax.jit(lambda p: ops.rope_cos_sin(p, 64, 10000.0, F32)).lower(
        positions).as_text()
    assert "divide" in text and text == jax.jit(
        lambda p: ops.rope_cos_sin(p, 64, 10000.0, F32, None)).lower(
            positions).as_text()
