"""The paged decode kernel on the stacked KV pool, on the CPU in interpret
mode (PRs 29, 31, 46; the pool's programs: `test_kv_pool_in_place.py`):

- with layer `l` it equals its per-layer call on `pool[l]`, every `l`;
- ROWS THAT HOLD NOTHING (PR 31): a row whose cursor stands at capacity runs
  no step of the kernel; the rows beside it come out bit for bit as they do
  without it;
- it walks a row's live blocks and no table entry beside them (PR 46).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.inference.kv_pool_kernels import (BS, D, HKV, L, LIVE_ROWS,
                                                  NB, PARKED_ROWS, T, WINDOW,
                                                  paged_decode_attention,
                                                  poisoned, random_pools)


@pytest.mark.parametrize("n_rep", [1, 8])
@pytest.mark.parametrize("staged", [False, True])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_reads_the_layer_it_is_given(quantized, staged, n_rep):
    rng = np.random.default_rng(0)
    b, h = 4, HKV * n_rep
    k, v, ks, vs = random_pools(rng, quantized)
    q = jnp.asarray(rng.standard_normal((b, 1, h, D)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, NB, (b, T)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, T * BS + 1, (b,)), jnp.int32)
    new = jnp.asarray(rng.standard_normal((2, b, HKV, D)), jnp.bfloat16)
    kw = dict(k_new=new[0], v_new=new[1]) if staged else {}
    outs = []
    for l in range(L):
        got = paged_decode_attention(
            q, k, v, tables, lengths, layer=jnp.int32(l), k_scales=ks,
            v_scales=vs, **kw)
        want = paged_decode_attention(
            q, k[l], v[l], tables, lengths,
            k_scales=None if ks is None else ks[l],
            v_scales=None if vs is None else vs[l], **kw)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        outs.append(np.asarray(got, np.float32))
    assert not np.array_equal(outs[0], outs[1])  # the layers do differ


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("staged", [False, True], ids=["unstaged", "staged"])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "stack_of_1"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_skips_a_row_parked_at_capacity(quantized, stacked,
                                                      staged, windowed):
    """Fails at the parent of PR 31 for the parked rows only: it ran every
    block of a parked row, on block 0."""
    rng = np.random.default_rng(31)
    n_rep = 4
    k, v, tables, pools = poisoned(rng, quantized, stacked)
    q = jnp.asarray(rng.standard_normal((6, 1, HKV * n_rep, D)), jnp.bfloat16)
    cap = T * BS
    # as `cached_attention` passes them, cursor + 1: the engine parks at
    # `cap`; live rows mid-block, on a block's edge, and at the last slot
    lengths = np.asarray([cap + 1, 3, cap + 1, BS + 1, cap, cap + 7], np.int32)
    new = jnp.asarray(rng.standard_normal((2, 6, HKV, D)), jnp.bfloat16)
    kw = dict(window=WINDOW if windowed else None, **pools)

    def run(rows):
        staged_kw = dict(k_new=new[0, rows], v_new=new[1, rows]) \
            if staged else {}
        return np.asarray(paged_decode_attention(
            q[rows], k, v, tables[rows], jnp.asarray(lengths[rows]),
            **staged_kw, **kw), np.float32)

    got = run(np.arange(6))
    np.testing.assert_array_equal(got[LIVE_ROWS], run(np.asarray(LIVE_ROWS)))
    assert np.isfinite(got).all() and np.abs(got[LIVE_ROWS]).min() > 0
    want = np.zeros_like(got[PARKED_ROWS])
    if staged:  # the staged token alone: its value, for every head of a group
        want = np.repeat(np.asarray(new[1, PARKED_ROWS], np.float32),
                         n_rep, axis=1)[:, None]
    np.testing.assert_array_equal(got[PARKED_ROWS], want)


def _blocks_with_a_column(pool_len, qpos, window):
    """The logical blocks of a row that hold a column the query attends:
    below the pool length and, with a window, above `qpos - window`."""
    cols = np.arange(T * BS)
    keep = cols < pool_len
    if window is not None:
        keep &= cols > qpos - window
    return sorted(set((cols[keep] // BS).tolist()))


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("staged", [False, True], ids=["unstaged", "staged"])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "stack_of_1"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_reads_only_the_blocks_that_hold_tokens(
        quantized, stacked, staged, windowed):
    """PR 46: the kernel walks a row's live blocks and no table entry
    beside them. EVERY pool block that no live (row, block) pair names is
    NaN (an int8 pool: NaN scales), every table entry past a row's last
    live block is -1 and those below a window's band name a NaN block; the
    batch comes out finite, and each row as it does served alone (which
    also holds the hand-over of a row's first block from the row before it,
    whatever rows lie between)."""
    rng = np.random.default_rng(46)
    n_rep, nb, cap = 4, 24, T * BS
    window = WINDOW if windowed else None
    # as `cached_attention` passes them, cursor + 1; parked rows first,
    # between and last, live rows from one token to the last slot
    lengths = np.asarray([cap + 1, 1, 2, cap + 1, BS, BS + 1, BS + 2,
                          cap + 1, cap + 1, 2 * BS + 3, cap, cap + 9],
                         np.int32)
    b = len(lengths)
    k, v, ks, vs = random_pools(rng, quantized, nb=nb)
    free = list(1 + rng.permutation(nb - 1))
    tables = np.full((b, T), -1, np.int32)
    named = []
    for r, length in enumerate(lengths):
        if length > cap:
            continue
        live = _blocks_with_a_column(length - 1 if staged else length,
                                     length - 1, window)
        if live:
            tables[r, :live[-1]] = 0          # below the band: a NaN block
        for j in live:
            tables[r, j] = free.pop()
            named.append(tables[r, j])
    dead = np.setdiff1d(np.arange(nb), named)
    assert len(named) >= 8 and 0 in dead
    nan = float("nan")
    if quantized:
        ks, vs = ks.at[:, :, dead].set(nan), vs.at[:, :, dead].set(nan)
    else:
        k, v = k.at[:, :, dead].set(nan), v.at[:, :, dead].set(nan)
    if stacked:
        pools = dict(k_scales=ks, v_scales=vs, layer=jnp.int32(1))
    else:
        k, v = k[1], v[1]
        pools = dict(k_scales=None if ks is None else ks[1],
                     v_scales=None if vs is None else vs[1])
    q = jnp.asarray(rng.standard_normal((b, 1, HKV * n_rep, D)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((2, b, HKV, D)), jnp.bfloat16)

    def run(rows):
        staged_kw = dict(k_new=new[0, rows], v_new=new[1, rows]) \
            if staged else {}
        return np.asarray(paged_decode_attention(
            q[rows], k, v, jnp.asarray(tables[rows]),
            jnp.asarray(lengths[rows]), window=window, **staged_kw, **pools),
            np.float32)

    got = run(np.arange(b))
    assert np.isfinite(got).all()
    live_rows = np.flatnonzero(lengths <= cap)
    # alone: every live row, and one parked row of each length (where a
    # parked row sits in the BATCH, first, between and last, is `got`'s)
    parked_alone = [int(np.flatnonzero(lengths == n)[0])
                    for n in (cap + 1, cap + 9)]
    for r in [*live_rows, *parked_alone]:
        np.testing.assert_array_equal(got[r:r + 1], run(np.asarray([r])))
    # every parked row: nothing, or its staged token alone (as in
    # `test_decode_kernel_skips_a_row_parked_at_capacity`)
    parked_rows = np.flatnonzero(lengths > cap)
    want = np.zeros_like(got[parked_rows])
    if staged:
        want = np.repeat(np.asarray(new[1, parked_rows], np.float32),
                         n_rep, axis=1)[:, None]
    np.testing.assert_array_equal(got[parked_rows], want)
    assert np.abs(got[live_rows]).max(axis=(1, 2, 3)).min() > 0


@pytest.mark.parametrize("staged", [False, True], ids=["unstaged", "staged"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_at_every_edge_of_a_block_in_one_batch(quantized,
                                                             staged):
    """Pool lengths 0, 1, BS - 1, BS, BS + 1, T*BS - 1 and a parked row in
    ONE batch, against the plain float32 softmax over the tokens each row
    holds (an unstaged row of length 0 holds none and reads zeros)."""
    rng = np.random.default_rng(47)
    n_rep, cap = 4, T * BS
    pool_len = np.asarray([0, 1, BS - 1, BS, BS + 1, cap - 1, 0], np.int32)
    parked = np.asarray([False] * 6 + [True])
    lengths = np.where(parked, cap + 1, pool_len + (1 if staged else 0))
    b, h = len(lengths), HKV * n_rep
    k, v, ks, vs = random_pools(rng, quantized)
    tables = rng.integers(0, NB, (b, T)).astype(np.int32)
    tables[parked] = -1
    q = jnp.asarray(rng.standard_normal((b, 1, h, D)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((2, b, HKV, D)), jnp.bfloat16)
    kw = dict(k_new=new[0], v_new=new[1]) if staged else {}
    got = np.asarray(paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
        layer=jnp.int32(2), k_scales=ks, v_scales=vs, **kw), np.float32)

    def tokens(pool, scales):   # (L, Hkv, NB, BS, D) -> (B, T*BS, Hkv, D)
        x = np.asarray(pool[2], np.float32)
        if scales is not None:
            x = x * np.asarray(scales[2], np.float32)[..., None]
        rows = x[:, np.maximum(tables, 0)]          # (Hkv, B, T, BS, D)
        return np.moveaxis(rows, 0, 3).reshape(b, cap, HKV, D)

    kd, vd = tokens(k, ks), tokens(v, vs)
    qf = np.asarray(q, np.float32)[:, 0].reshape(b, HKV, n_rep, D)
    for r in range(b):
        kr, vr = kd[r, :pool_len[r]], vd[r, :pool_len[r]]
        if staged:
            kr = np.concatenate([kr, np.asarray(new[0, r], np.float32)[None]])
            vr = np.concatenate([vr, np.asarray(new[1, r], np.float32)[None]])
        if not len(kr):
            np.testing.assert_array_equal(got[r], 0.0)
            continue
        s = np.einsum("grd,tgd->grt", qf[r], kr) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("grt,tgd->grd", p / p.sum(-1, keepdims=True), vr)
        np.testing.assert_allclose(got[r, 0], want.reshape(h, D), atol=3e-2,
                                   rtol=3e-2)
