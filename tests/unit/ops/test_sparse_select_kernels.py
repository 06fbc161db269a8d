"""The learned-selection kernels (`ops/pallas/sparse_select.py`) against
their plain `jax.numpy` forms, in interpret mode (the chip's compiler sees
them in `test_chip_compile.py`, the chip in `chip_smoke.py`): the choice by
bisection over the scores' bits is the set `jax.lax.top_k` gives, ties
included; the decode step's choice and its attention under the choice with
the step's token staged; a prefill chunk's choice and attention against a
row's slabs. Each kernel under ONE module-level `jax.jit` a shape.

Scores that must tie, or must not depend on the order of a sum, are made of
small integers: every product and sum is then exact in float32 whatever the
order, and kernel and plain form see the SAME scores."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import sparse_select as ss

F32 = jnp.float32
L, B, HKV, NREP, D, HI, DI = 2, 3, 2, 2, 16, 4, 8
H = HKV * NREP
LAYER = 1


def integers(key, shape, lo=-3, hi=4, dtype=F32):
    return jax.random.randint(key, shape, lo, hi).astype(dtype)


def normal(key, shape, dtype=F32):
    return jax.random.normal(key, shape, F32).astype(dtype)


@functools.partial(jax.jit, static_argnames=("topk",))
def select(q, w, stack, lengths, new, topk):
    return ss.sparse_index_select(q, w, stack, LAYER, lengths, topk, new)


@functools.partial(jax.jit, static_argnames=("topk",))
def select_plain(q, w, stack, lengths, new, topk):
    return ss.sparse_index_select_reference(q, w, stack, LAYER, lengths, topk,
                                            new)


@jax.jit
def decode(q, k, v, lengths, bias, kn, vn):
    return ss.sparse_attn_decode(q, k, v, LAYER, lengths, bias, D ** -0.5,
                                 kn, vn)


@functools.partial(jax.jit, static_argnames=("topk",))
def prefill(q, qi, w, k, v, keys, row, start, topk):
    return ss.sparse_attn_prefill(q, qi, w, k, v, keys, LAYER, row, start,
                                  topk, D ** -0.5)


@functools.partial(jax.jit, static_argnames=("topk",))
def prefill_plain(q, qi, w, k, v, keys, row, start, topk):
    return ss.sparse_attn_prefill_reference(q, qi, w, k, v, keys, LAYER, row,
                                            start, topk, D ** -0.5)


def kept(bias):
    return np.asarray(bias) == 0.0


# ------------------------------------------------------------- the choice


@functools.partial(jax.jit, static_argnames=("topk",))
def bisected(scores, live, topk):
    """`_choose` and `_write_bias` alone, over scores handed in whole: what
    every kernel runs after its scores."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, m = scores.shape
    tw = ss.block_of(m, 32)

    def kernel(s_ref, n_ref, bias_ref, kept_ref, key_scr, cut_scr):
        slot = jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
        key_scr[...] = jnp.where(slot < n_ref[...], ss.sort_key(s_ref[...]),
                                 ss.INT_MIN)
        thr = ss._choose(key_scr, cut_scr, kept_ref, m // tw, tw,
                         jnp.minimum(n_ref[...], topk))
        ss._write_bias(bias_ref, key_scr, cut_scr, thr, m // tw, tw, m // tw)

    return pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct((rows, m), F32),
                           jax.ShapeDtypeStruct((rows, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, m), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        interpret=True)(scores, live[:, None])


@pytest.mark.parametrize("topk", [1, 5, 16, 64, 200])
def test_the_bisected_choice_is_top_k_s(topk):
    """float32 scores of every kind in one batch of rows: normal draws, a
    row of few distinct values (ties at the threshold, more than are owed:
    the lower slots are kept), zeros of both signs, infinities, a row
    shorter than `topk` (every live slot is kept)."""
    m = 128
    key = jax.random.PRNGKey(topk)
    rows = [jax.random.normal(key, (m,)) * 1e3,
            jax.random.randint(key, (m,), -2, 3).astype(F32),
            jnp.where(jnp.arange(m) % 3 == 0, -0.0, 0.0),
            jnp.where(jnp.arange(m) % 5 == 0, jnp.inf, -jnp.inf),
            jax.random.normal(key, (m,)) * 1e-30,
            jnp.full((m,), 7.0)]
    scores = jnp.stack(rows).astype(F32)
    live = jnp.asarray([m, m, 100, m, 3, 77], jnp.int32)
    want = ss.chosen(scores, jnp.arange(m)[None, :] < live[:, None], topk)
    bias, count = bisected(scores, live, topk)
    got = kept(bias)
    np.testing.assert_array_equal(got, np.asarray(want))
    # the choice's own count is of the zeros written, ties cut or not
    assert list(got.sum(-1)) == list(count[:, 0]) == \
        [min(int(n), topk) for n in live]


# ------------------------------------------------------------------ decode


def _decode_operands(key, m, exact):
    ks = jax.random.split(key, 9)
    draw = integers if exact else normal
    return dict(
        q_i=draw(ks[0], (B, HI, DI)), w=draw(ks[1], (B, HI)),
        keys=draw(ks[2], (L, B, 1, m, DI)), new_i=draw(ks[3], (B, DI)),
        q=normal(ks[4], (B, H, D)), k=normal(ks[5], (L, B, HKV, m, D)),
        v=normal(ks[6], (L, B, HKV, m, D)), kn=normal(ks[7], (B, HKV, D)),
        vn=normal(ks[8], (B, HKV, D)))


@pytest.mark.parametrize("m,topk,lengths", [
    (64, 8, (64, 20, 1)), (256, 16, (200, 256, 9)), (256, 300, (256, 7, 130))],
    ids=["one_block", "blocks", "topk_over_the_row"])
def test_decode_select_is_top_k_of_the_same_scores(m, topk, lengths):
    """Integer operands: the scores are exact and tie in plenty; the kernel's
    set is `jax.lax.top_k`'s, the staged key scored in its slot."""
    t = _decode_operands(jax.random.PRNGKey(m + topk), m, exact=True)
    lengths = jnp.asarray(lengths, jnp.int32)
    got, count = select(t["q_i"], t["w"], t["keys"], lengths, t["new_i"],
                        topk)
    want, count_plain = select_plain(t["q_i"], t["w"], t["keys"], lengths,
                                     t["new_i"], topk)
    np.testing.assert_array_equal(kept(got), kept(want))
    # the kernel's count is of the zeros it wrote
    assert list(kept(got).sum(-1)) == list(count) == list(count_plain) == \
        [min(int(n), topk) for n in lengths]
    # the staged key takes part: with another the choice is another (where
    # there is a choice: a row under `topk` keeps every slot)
    other, _ = select(t["q_i"], t["w"], t["keys"], lengths,
                      t["new_i"] + 50.0, topk)
    assert np.array_equal(kept(other), kept(got)) == (topk >= m)
    # and nothing past a row's length is ever kept
    assert not (kept(got) & (np.arange(m)[None] >= np.asarray(lengths)[:, None])).any()


def test_decode_select_of_seeded_scores():
    """Normal operands in bfloat16, as served: the scores' sums differ by
    rounding between kernel and plain form, so a boundary pair may swap;
    all but a few of the kept slots are the same."""
    t = _decode_operands(jax.random.PRNGKey(5), 256, exact=False)
    stack = t["keys"].astype(jnp.bfloat16)
    lengths = jnp.asarray([256, 100, 31], jnp.int32)
    got = kept(select(t["q_i"], t["w"], stack, lengths, t["new_i"], 16)[0])
    want = kept(select_plain(t["q_i"], t["w"], stack, lengths, t["new_i"],
                             16)[0])
    assert list(got.sum(-1)) == [16, 16, 16]
    assert (got != want).sum() <= 4


@pytest.mark.parametrize("m,lengths", [(64, (64, 20, 1)),
                                       (256, (200, 256, 9))])
def test_decode_attention_under_the_choice(m, lengths):
    t = _decode_operands(jax.random.PRNGKey(m), m, exact=True)
    lengths = jnp.asarray(lengths, jnp.int32)
    bias, _ = select_plain(t["q_i"], t["w"], t["keys"], lengths, t["new_i"],
                           8)
    got = decode(t["q"], t["k"], t["v"], lengths, bias, t["kn"], t["vn"])
    want = ss.sparse_attn_decode_reference(
        t["q"], t["k"], t["v"], LAYER, lengths, bias, D ** -0.5, t["kn"],
        t["vn"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    # it is attention over the kept slots alone, the staged token in its slot
    b = 1
    n = int(lengths[b])
    k = t["k"][LAYER, b].at[:, n - 1].set(t["kn"][b])
    v = t["v"][LAYER, b].at[:, n - 1].set(t["vn"][b])
    at = np.flatnonzero(kept(bias)[b])
    for h in range(H):
        p = jax.nn.softmax(k[h // NREP, at] @ t["q"][b, h] * D ** -0.5)
        np.testing.assert_allclose(got[b, h], p @ v[h // NREP, at], atol=2e-6)


# ----------------------------------------------------------------- prefill


def _prefill_operands(key, c, m, exact):
    ks = jax.random.split(key, 6)
    draw = integers if exact else normal
    return (normal(ks[0], (c, H, D)), draw(ks[1], (c, HI, DI)),
            draw(ks[2], (c, HI)), normal(ks[3], (L, B, HKV, m, D)),
            normal(ks[4], (L, B, HKV, m, D)), draw(ks[5], (L, B, 1, m, DI)))


@pytest.mark.parametrize("c,m,start,topk", [
    (16, 64, 0, 8), (16, 64, 48, 8), (32, 256, 96, 24), (8, 256, 5, 300)],
    ids=["from_empty", "to_the_row_s_end", "blocks", "topk_over_the_row"])
def test_prefill_chunk_against_the_row_s_slabs(c, m, start, topk):
    """A chunk of row 2's queries at positions `start ..`: each query's
    choice among the slots up to its own (integer index operands: exact
    scores, ties), and attention over it."""
    ops = _prefill_operands(jax.random.PRNGKey(c + start), c, m, exact=True)
    got, count = prefill(*ops, 2, start, topk)
    want, count_plain = prefill_plain(*ops, 2, start, topk)
    np.testing.assert_allclose(got, want, atol=3e-6)
    assert list(count) == list(count_plain) == [
        min(start + i + 1, topk) for i in range(c)]
    # a query sees nothing past its own position: moving later slots' keys
    # and values changes no earlier query
    q, qi, w, k, v, keys = ops
    edge = start + c // 2
    moved, _ = prefill(q, qi, w, k.at[:, :, :, edge:].add(1.0),
                       v.at[:, :, :, edge:].add(1.0),
                       keys.at[:, :, :, edge:].add(1.0), 2, start, topk)
    np.testing.assert_array_equal(moved[:c // 2], got[:c // 2])
    assert not np.allclose(moved[c // 2:], got[c // 2:])
