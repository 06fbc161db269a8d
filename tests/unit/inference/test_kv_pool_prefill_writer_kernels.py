"""The paged prefill kernel and the Pallas writer on the stacked KV pool, on
the CPU in interpret mode (PRs 29, 31, 36; the pool's programs:
`test_kv_pool_in_place.py`):

- the prefill kernel with layer `l` equals its per-layer call on `pool[l]`;
  a row parked at capacity runs no step of it, and rows of ONE sequence at
  consecutive cursors are the one longer chunk they are;
- the writer `paged_kv_write` leaves the pools as the XLA scatters of
  `kv_cache.py` do, bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (PagedKVCache, PagedLayer,
                                              _update_paged_layer,
                                              quantize_kv_tokens)
from tests.unit.inference.kv_pool_kernels import (BS, D, HKV, L, LIVE_ROWS,
                                                  NB, PARKED_ROWS, T, WINDOW,
                                                  paged_kv_write,
                                                  paged_prefill_attention,
                                                  poisoned, random_pools)


@pytest.mark.parametrize("n_rep", [1, 8])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_prefill_kernel_reads_the_layer_it_is_given(quantized, n_rep):
    rng = np.random.default_rng(1)
    b, s, h = 3, 4, HKV * n_rep
    k, v, ks, vs = random_pools(rng, quantized)
    q = jnp.asarray(rng.standard_normal((b, s, h, D)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, NB, (b, T)), jnp.int32)
    starts = jnp.asarray(rng.integers(0, T * BS - s + 1, (b,)), jnp.int32)
    outs = []
    for l in range(L):
        got = paged_prefill_attention(
            q, k, v, tables, starts, layer=jnp.int32(l), k_scales=ks,
            v_scales=vs)
        want = paged_prefill_attention(
            q, k[l], v[l], tables, starts,
            k_scales=None if ks is None else ks[l],
            v_scales=None if vs is None else vs[l])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        outs.append(np.asarray(got, np.float32))
    assert not np.array_equal(outs[0], outs[1])


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "stack_of_1"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_prefill_kernel_skips_a_row_parked_at_capacity(quantized, stacked,
                                                       windowed):
    rng = np.random.default_rng(32)
    n_rep, s = 4, 4
    k, v, tables, pools = poisoned(rng, quantized, stacked)
    q = jnp.asarray(rng.standard_normal((6, s, HKV * n_rep, D)), jnp.bfloat16)
    cap = T * BS
    # live rows: a first chunk, one across a block's edge, one that ends on
    # the last slot (start + valid == capacity)
    starts = np.asarray([cap, 0, cap, BS - 2, cap - s, cap + 3], np.int32)
    kw = dict(window=WINDOW if windowed else None, block_q=2, **pools)

    def run(rows):
        return np.asarray(paged_prefill_attention(
            q[rows], k, v, tables[rows], jnp.asarray(starts[rows]), **kw),
            np.float32)

    got = run(np.arange(6))
    np.testing.assert_array_equal(got[LIVE_ROWS], run(np.asarray(LIVE_ROWS)))
    assert np.isfinite(got).all() and np.abs(got[LIVE_ROWS]).min() > 0
    np.testing.assert_array_equal(got[PARKED_ROWS], 0.0)


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "stack_of_1"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_prefill_kernel_rows_of_one_sequence_beside_parked_rows(
        quantized, stacked, windowed):
    """A filled chunk round (PR 36): the live rows are ONE sequence's, three
    chunks at consecutive cursors through one table (the middle one across
    a block's edge), parked rows before, between and after them. Each comes
    out as it does with the parked rows gone, and the three as the one
    chunk of three times the length that they are."""
    rng = np.random.default_rng(36)
    n_rep, s = 4, 4
    k, v, tables, pools = poisoned(rng, quantized, stacked)
    tables = tables.at[np.asarray(LIVE_ROWS)].set(tables[LIVE_ROWS[0]])
    q = jnp.asarray(rng.standard_normal((6, s, HKV * n_rep, D)), jnp.bfloat16)
    cap, first = T * BS, BS - s - 2
    starts = np.asarray([cap, first, cap, first + s, first + 2 * s, cap + 3],
                        np.int32)
    kw = dict(window=WINDOW if windowed else None, block_q=2, **pools)

    def run(rows):
        return np.asarray(paged_prefill_attention(
            q[rows], k, v, tables[rows], jnp.asarray(starts[rows]), **kw),
            np.float32)

    got = run(np.arange(6))
    np.testing.assert_array_equal(got[LIVE_ROWS], run(np.asarray(LIVE_ROWS)))
    assert np.isfinite(got).all() and np.abs(got[LIVE_ROWS]).min() > 0
    np.testing.assert_array_equal(got[PARKED_ROWS], 0.0)
    live = np.asarray(LIVE_ROWS)
    one = np.asarray(paged_prefill_attention(
        q[live].reshape(1, 3 * s, HKV * n_rep, D), k, v, tables[live[:1]],
        jnp.asarray(starts[live[:1]]), **kw), np.float32)
    np.testing.assert_array_equal(got[LIVE_ROWS].reshape(one.shape), one)


def _owned_tables(rng, b):
    """Each row owns a prefix of its table, of distinct blocks; the rest
    is unowned (-1)."""
    tables = np.full((b, T), -1, np.int32)
    free = list(rng.permutation(NB))
    for i in range(b):
        for j in range(int(rng.integers(0, T + 1))):
            if free:
                tables[i, j] = free.pop()
    return jnp.asarray(tables)


@pytest.mark.parametrize("s", [1, 5, BS, 2 * BS + 3])
@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("one_sequence", [False, True],
                         ids=["rows_apart", "rows_of_one_sequence"])
def test_writer_kernel_is_the_scatter(one_sequence, dtype, quantized, s):
    """`paged_kv_write` against `_update_paged_layer`: parked rows, unowned
    entries, cursors anywhere, a piece that spans blocks, a whole block.
    `rows_of_one_sequence` (a filled chunk round, PR 36): rows 2, 4 and 3
    write through ONE table at consecutive cursors, so two grid steps
    read, modify and write the same block one after the other."""
    changed = 0
    for seed in range(3):
        rng = np.random.default_rng(seed)
        b, layer = 5, 1
        k, v, ks, vs = random_pools(rng, quantized, dtype)
        tables = _owned_tables(rng, b)
        starts = rng.integers(0, T * BS + 2, (b,)).astype(np.int32)
        starts[0] = T * BS                                  # parked
        starts[1] = (starts[1] // BS) * BS                  # block-aligned
        if one_sequence:
            tables = tables.at[jnp.asarray([2, 3, 4])].set(
                jnp.asarray(rng.permutation(NB)[:T], jnp.int32))
            starts[2] = seed                      # 0, 1, 2: then on from it
            starts[[4, 3]] = starts[2] + s, starts[2] + 2 * s
        starts = jnp.asarray(starts)
        kn = jnp.asarray(rng.standard_normal((b, s, HKV, D)), dtype)
        vn = jnp.asarray(rng.standard_normal((b, s, HKV, D)), dtype)
        want = [_update_paged_layer(
            PagedLayer(pool=p, tables=tables, scales=sc,
                       layer=jnp.int32(layer)), new, starts)
            for p, sc, new in ((k, ks, kn), (v, vs, vn))]
        extra = {}
        if quantized:
            (kn, kns), (vn, vns) = quantize_kv_tokens(kn), quantize_kv_tokens(vn)
            extra = dict(k_scales=ks, v_scales=vs, k_new_scales=kns[None],
                         v_new_scales=vns[None])
        got = paged_kv_write(k, v, kn[None], vn[None], tables, starts, layer,
                             **extra)
        for g, w in zip(got[:2], want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w.pool, np.float32))
        if quantized:
            for g, w in zip(got[2:], want):
                np.testing.assert_array_equal(np.asarray(g),
                                              np.asarray(w.scales))
        changed += int((np.asarray(got[0], np.float32)
                        != np.asarray(k, np.float32)).sum())
    assert changed  # something was written


def test_writer_kernel_lands_a_stage_in_every_layer():
    """The `apply_stage` form: one token a row, all layers in one call,
    position `index - 1`; a row with nothing before it (index 0) drops."""
    rng = np.random.default_rng(7)
    b = 4
    k, v, _, _ = random_pools(rng, False)
    tables = _owned_tables(rng, b)
    index = jnp.asarray([0, 3, BS + 1, T * BS], jnp.int32)
    stage = jnp.asarray(rng.standard_normal((2, L, b, HKV, D)), jnp.bfloat16)
    cache = PagedKVCache(
        k=PagedLayer(pool=k, tables=jnp.broadcast_to(tables, (L, b, T)),
                     stage=stage[0]),
        v=PagedLayer(pool=v, tables=jnp.broadcast_to(tables, (L, b, T)),
                     stage=stage[1]),
        index=index)
    want = cache.apply_stage()       # off the chip: the batched XLA scatter
    got = paged_kv_write(k, v, stage[0][:, :, None], stage[1][:, :, None],
                         tables, index - 1, 0)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want.k.pool, np.float32))
    np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                  np.asarray(want.v.pool, np.float32))
