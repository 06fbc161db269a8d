"""The stacked KV pool, addressed by layer and written in place (PR 29).

Three layers of proof, all on the CPU (Pallas kernels in interpret mode):

- KERNELS: both paged attention kernels on the stacked pool with layer `l`
  equal their per-layer call on `pool[l]`, every `l`; the Pallas writer
  `paged_kv_write` leaves the pools as the XLA scatters of `kv_cache.py` do,
  bit for bit.
- ROWS THAT HOLD NOTHING (PR 31): a row whose cursor stands at capacity runs
  no step of either attention kernel; the rows beside it come out bit for
  bit as they do without it.
- PROGRAMS: `prefill`, `decode`, `chunk_batch` and `fused_batch` of a v2
  engine return the same logits and the same WHOLE cache, bit for bit, as
  the engine whose model keeps the per-layer-view scan this PR replaced
  (`PerLayerViewLlama`: the old form, built from `update_layer` on views).
- STRUCTURE: the paged cached scan scans over no pool; a dense `KVCache` in
  the per-layer view is still scanned over as `(cache.k, cache.v)` and holds
  no layer operand (the stacked view, which `generate-batch` runs since PR
  42, has its own file: `test_dense_cache_in_place.py`).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (KVCache, PagedKVCache,
                                              PagedLayer, _update_paged_layer,
                                              decode_mask, quantize_kv_tokens)
from deepspeed_tpu.models.llama import (LlamaBlock, LlamaConfig,
                                        LlamaForCausalLM, RMSNorm,
                                        materialize_params)
from deepspeed_tpu.ops.attention import rope_cos_sin
from deepspeed_tpu.ops.pallas.paged_attention import (paged_decode_attention,
                                                      paged_kv_write,
                                                      paged_prefill_attention)

L, HKV, NB, BS, D, T = 3, 2, 10, 8, 16, 3


def _pools(rng, quantized, dtype=jnp.bfloat16, nb=NB):
    k = jnp.asarray(rng.standard_normal((L, HKV, nb, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((L, HKV, nb, BS, D)), dtype)
    if not quantized:
        return k, v, None, None
    (k, ks), (v, vs) = quantize_kv_tokens(k), quantize_kv_tokens(v)
    return k, v, ks, vs


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("n_rep", [1, 8])
@pytest.mark.parametrize("staged", [False, True])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_reads_the_layer_it_is_given(quantized, staged, n_rep):
    rng = np.random.default_rng(0)
    b, h = 4, HKV * n_rep
    k, v, ks, vs = _pools(rng, quantized)
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


@pytest.mark.parametrize("n_rep", [1, 8])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_prefill_kernel_reads_the_layer_it_is_given(quantized, n_rep):
    rng = np.random.default_rng(1)
    b, s, h = 3, 4, HKV * n_rep
    k, v, ks, vs = _pools(rng, quantized)
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


# a batch with parked rows first, between and last; live rows own blocks
# 1.. of the pool, block 0 is NaN
PARKED_ROWS, LIVE_ROWS, WINDOW = [0, 2, 5], [1, 3, 4], 5


def _poisoned(rng, quantized, stacked):
    """Pools whose block 0 is NaN in every layer (an int8 pool holds no NaN:
    its block 0 has NaN scales), tables of which the live rows own blocks
    1.. and the parked rows nothing (-1: a read through it clips to block
    0) or, row 2, what a request left behind; and what selects the layer."""
    k, v, ks, vs = _pools(rng, quantized)
    nan = float("nan")
    if quantized:
        ks, vs = ks.at[:, :, 0].set(nan), vs.at[:, :, 0].set(nan)
    else:
        k, v = k.at[:, :, 0].set(nan), v.at[:, :, 0].set(nan)
    tables = np.full((6, T), -1, np.int32)
    tables[LIVE_ROWS] = 1 + rng.permutation(NB - 1)[:3 * T].reshape(3, T)
    tables[2] = [0, 4, 0]
    if stacked:
        pools = dict(k_scales=ks, v_scales=vs, layer=jnp.int32(1))
    else:
        k, v = k[1], v[1]
        pools = dict(k_scales=None if ks is None else ks[1],
                     v_scales=None if vs is None else vs[1])
    return k, v, jnp.asarray(tables), pools


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
    k, v, tables, pools = _poisoned(rng, quantized, stacked)
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
    k, v, ks, vs = _pools(rng, quantized, nb=nb)
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
    for r in range(b):
        np.testing.assert_array_equal(got[r:r + 1], run(np.asarray([r])))
    live_rows = np.flatnonzero(lengths <= cap)
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
    k, v, ks, vs = _pools(rng, quantized)
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


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "stack_of_1"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_prefill_kernel_skips_a_row_parked_at_capacity(quantized, stacked,
                                                       windowed):
    rng = np.random.default_rng(32)
    n_rep, s = 4, 4
    k, v, tables, pools = _poisoned(rng, quantized, stacked)
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
    k, v, tables, pools = _poisoned(rng, quantized, stacked)
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
        k, v, ks, vs = _pools(rng, quantized, dtype)
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
    k, v, _, _ = _pools(rng, False)
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


# ----------------------------------------------------------------- programs


class PerLayerViewLlama(LlamaForCausalLM):
    """`LlamaForCausalLM` with the cached scan as it was before PR 29: the
    block scan scans over `(cache.k, cache.v)`, each layer gets a
    `PagedLayer` VIEW of its own pool (`layer=None`) and `update_layer`
    writes that view. The reference the layer-indexed scan must equal."""

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, cache=None):
        if cache is None:  # the engine's shape probe: the parent's own path
            return super().__call__(input_ids, labels, positions)
        cfg = self.cfg
        embed = self.param("embed_tokens", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        h = jnp.take(embed.astype(cfg.dtype), input_ids, axis=0)
        s = input_ids.shape[1]
        index = cache.index
        pos = index[:, None] + jnp.arange(s)[None, :]
        cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta, cfg.dtype)
        mask = decode_mask(pos, cache.max_len, window=cfg.sliding_window)
        scan = nn.scan(
            LlamaBlock, variable_axes={"params": 0},
            split_rngs={"params": True}, in_axes=(nn.broadcast, 0),
            out_axes=0, length=cfg.num_hidden_layers,
            metadata_params={nn.meta.PARTITION_NAME: "layers"})
        h, (k_new, v_new) = scan(cfg, name="layers")(
            h, (cos, sin, index, mask), (cache.k, cache.v))
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(h)
        return self._lm_head(h, embed), cache.replace(
            k=k_new, v=v_new, index=index + s)


CFG = LlamaConfig(vocab_size=96, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=3, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128,
                  dtype=jnp.float32)
MAX_BATCH, MAX_SEQ, BLOCK = 4, 48, 8


@pytest.fixture(scope="module")
def params():
    return materialize_params(CFG)[1]


def _engines(params, **kw):
    """(layer-indexed engine, per-layer-view engine), caches filled alike:
    random pools, every row owning distinct blocks, one table entry
    unowned."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    pair = []
    for cls in (LlamaForCausalLM, PerLayerViewLlama):
        groups.reset_topology()
        eng = InferenceEngineV2(cls(CFG), params=params, max_batch=MAX_BATCH,
                                max_seq_len=MAX_SEQ, cache_block_size=BLOCK,
                                **kw)
        rng = np.random.default_rng(11)
        c = eng.cache
        t = c.k.tables.shape[-1]
        tables = rng.permutation(c.num_blocks)[:MAX_BATCH * t].reshape(
            MAX_BATCH, t).astype(np.int32)
        tables[2, t - 1] = -1
        c = c.with_tables(jnp.asarray(tables))

        def fill(layer):
            pool = rng.standard_normal(layer.pool.shape)
            if layer.scales is None:
                return layer.replace(pool=jnp.asarray(pool, layer.pool.dtype))
            q, sc = quantize_kv_tokens(jnp.asarray(pool, jnp.float32))
            return layer.replace(pool=q, scales=sc)
        c = c.replace(k=fill(c.k), v=fill(c.v))
        eng.cache = jax.device_put(c, eng._cache_pin)
        pair.append(eng)
    return pair


def _same(got, want):
    ga, wa = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(ga) == len(wa)
    for g, w in zip(ga, wa):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _prefill(eng, rng):
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 16)), jnp.int32)
    return eng._prefill_fn(16)(eng.params, eng.cache, ids, jnp.int32(1),
                               jnp.int32(11))


def _decode(eng, rng):
    cap = eng.cache.max_len
    eng.cache = eng.cache.replace(index=jnp.asarray(   # row 3 parked
        [5, BLOCK, 2 * BLOCK + 3, cap], jnp.int32))
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (MAX_BATCH, 1)),
                       jnp.int32)
    active = jnp.asarray([True, True, True, False])
    return eng._decode_fn()(eng.params, eng.cache, toks, active)


def _chunk_rows(eng, rng, width):
    chunk, cap = eng.split_fuse_chunk, eng.cache.max_len
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, (width, chunk)),
                      jnp.int32)
    # a cursor mid-block, a block-aligned one, a parked row (the rest)
    slots = np.full((width,), MAX_BATCH, np.int32)
    starts = np.full((width,), cap, np.int32)
    valids = np.zeros((width,), np.int32)
    slots[:2], starts[:2], valids[:2] = (0, 2), (3, BLOCK), (chunk, chunk - 1)
    return ids, jnp.asarray(slots), jnp.asarray(starts), jnp.asarray(valids)


def _chunk_batch(eng, rng):
    return eng._chunk_batch_fn()(eng.params, eng.cache,
                                 *_chunk_rows(eng, rng, MAX_BATCH))


def _fused_batch(eng, rng):
    cap = eng.cache.max_len
    eng.cache = eng.cache.replace(index=jnp.asarray(
        [cap, 7, cap, 2 * BLOCK], jnp.int32))     # rows 1 and 3 decode
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (MAX_BATCH, 1)),
                       jnp.int32)
    active = jnp.asarray([False, True, False, True])
    from deepspeed_tpu.inference.v2.engine_v2 import chunk_row_widths
    width = chunk_row_widths(eng.max_batch)[0]
    return eng._fused_batch_fn(width)(eng.params, eng.cache, toks, active,
                                      *_chunk_rows(eng, rng, width))


PROGRAMS = {"prefill": _prefill, "decode": _decode,
            "chunk_batch": _chunk_batch, "fused_batch": _fused_batch}
ENGINES = {"f32": {}, "int8": {"kv_cache_dtype": "int8"},
           "chunk_is_block": {"split_fuse_chunk": BLOCK}}


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("engine", list(ENGINES))
def test_program_equals_the_per_layer_view_scan(params, engine, program):
    """Logits and the whole returned cache, bit for bit. `chunk_is_block`
    runs both scatters of `_update_paged_layer`'s `lax.cond`: the rows'
    cursors are not all aligned in `chunk_batch` (token scatter) and the
    prefill's are (block scatter)."""
    new, old = _engines(params, **ENGINES[engine])
    got = PROGRAMS[program](new, np.random.default_rng(5))
    want = PROGRAMS[program](old, np.random.default_rng(5))
    _same(got, want)
    cache = got[0]
    assert not np.array_equal(np.asarray(cache.k.pool[0]),
                              np.asarray(cache.k.pool[1]))


@pytest.mark.parametrize("program", ["prefill", "fused_batch"])
@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_program_equals_with_the_kernels_on(params, monkeypatch, engine,
                                            program):
    """The chip's path, interpreted: both paged kernels and the Pallas
    writer (chunk rows and `apply_stage`) inside the programs, on the
    stacked pool by layer index against per-layer views."""
    import deepspeed_tpu.ops.attention as attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    new, old = _engines(params, **ENGINES[engine])
    got = PROGRAMS[program](new, np.random.default_rng(5))
    want = PROGRAMS[program](old, np.random.default_rng(5))
    _same(got, want)
    jaxpr = str(jax.make_jaxpr(
        lambda c: c.apply_stage())(new.cache))
    assert "kv_write_paged" in jaxpr and "scatter" not in jaxpr


@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_decode_program_walks_no_grid_axis_of_the_tables_length(
        params, monkeypatch, engine):
    """PR 46: the `decode` program's `self_attn_paged_decode` call, once a
    layer inside the layer scan, has a grid of the batch's rows alone: no
    axis of the block table's length T, nor of rows x T."""
    import deepspeed_tpu.ops.attention as attention
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    eng, _ = _engines(params, **ENGINES[engine])
    t = eng.cache.k.tables.shape[-1]
    assert t > 1 and t != MAX_BATCH
    toks = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    active = jnp.ones((MAX_BATCH,), bool)
    jaxpr = jax.make_jaxpr(eng._decode_fn())(eng.params, eng.cache, toks,
                                             active)
    grids = [tuple(e.params["grid_mapping"].grid)
             for _, e in primitive_eqns(jaxpr.jaxpr, {"pallas_call"})
             if "self_attn_paged_decode" in str(
                 e.params.get("name") or e.params["name_and_src_info"])]
    assert grids == [(MAX_BATCH,)]


@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_put_rounds_with_shared_prefix_blocks(params, engine):
    """A served sequence of rounds (prefill, chunks beside decodes, two
    prompts sharing their first blocks, a fork) leaves the two engines
    with the same tokens' logits and the same cache."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    rng = np.random.default_rng(3)
    shared = list(map(int, rng.integers(0, CFG.vocab_size, 2 * BLOCK)))
    prompts = {1: shared + [5, 6, 7], 2: shared + [9, 8],
               3: list(map(int, rng.integers(0, CFG.vocab_size, 19)))}
    runs = []
    for cls in (LlamaForCausalLM, PerLayerViewLlama):
        groups.reset_topology()
        eng = InferenceEngineV2(cls(CFG), params=params, max_batch=MAX_BATCH,
                                max_seq_len=MAX_SEQ, cache_block_size=BLOCK,
                                split_fuse_chunk=BLOCK, prefix_sharing=True,
                                **ENGINES[engine])
        outs = [eng.put([1], [np.asarray(prompts[1])])]
        # the prompt's three chunks ran in that one round, three rows of
        # the four (PR 36); nothing is pending, and an empty put is empty
        assert list(outs[0]) == [1] and eng.put([], []) == {}
        outs.append(eng.put([2, 3], [np.asarray(prompts[2]),
                                     np.asarray(prompts[3])]))
        outs.append(eng.put([1], [[4]]))      # a decode beside their chunks
        outs.append(eng.put([], []))
        eng.fork(1, 4)
        for step in range(3):
            outs.append(eng.put([1, 2, 3, 4], [[step + 1]] * 4))
        assert eng.block_manager.prefix_hits >= 1
        runs.append((outs, eng.cache))
    (got, got_cache), (want, want_cache) = runs
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for uid in g:
            np.testing.assert_array_equal(np.asarray(g[uid]),
                                          np.asarray(w[uid]))
    _same(got_cache, want_cache)


def test_int8_chunks_keep_their_scales(params):
    """A prompt served into an int8 pool leaves its tokens' scales in the
    cache (the merge of a chunk program's rows dropped them before PR 29:
    the pool held int8 values under unit scales)."""
    new, _ = _engines(params, kv_cache_dtype="int8")
    before = np.asarray(new.cache.k.scales)
    cache, _ = _chunk_batch(new, np.random.default_rng(2))
    assert (np.asarray(cache.k.scales) != before).any()


# ---------------------------------------------------------------- structure


def _scans(jaxpr):
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    return [e for _, e in primitive_eqns(jaxpr, ["scan"])]


def _scanned(eqn):
    """Shapes of a scan's scanned inputs and outputs."""
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    return ([tuple(v.aval.shape) for v in eqn.invars[skip:]],
            [tuple(v.aval.shape) for v in
             eqn.outvars[eqn.params["num_carry"]:]])


@pytest.mark.parametrize("staged,s", [(True, 1), (False, 1), (True, 4)],
                         ids=["staged_decode", "unstaged_decode", "chunk"])
def test_paged_scan_scans_over_no_pool(params, staged, s):
    model = LlamaForCausalLM(CFG)
    cache = PagedKVCache.create(CFG.num_hidden_layers, 2, 32, 2, CFG.head_dim,
                                num_blocks=6, block_size=BLOCK,
                                dtype=jnp.float32, staged=staged)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: model.apply({"params": p}, i, cache=c))(
        params, jnp.zeros((2, s), jnp.int32), cache)
    pool = tuple(cache.k.pool.shape)
    scans = [e for e in _scans(jaxpr) if e.params["length"] == pool[0]]
    assert scans
    for eqn in scans:
        xs, ys = _scanned(eqn)
        assert pool not in xs and pool not in ys
        skip = eqn.params["num_consts"]
        carry = [tuple(v.aval.shape) for v in
                 eqn.invars[skip:skip + eqn.params["num_carry"]]]
        consts = [tuple(v.aval.shape) for v in eqn.invars[:skip]]
        # a pass that can write the pools carries them; staged decode
        # cannot, and closes over them
        writes = not (staged and s == 1)
        assert (carry.count(pool), consts.count(pool)) == (
            (2, 0) if writes else (0, 2))


def test_dense_scan_is_the_parents(params):
    """The per-layer view of the dense cache (`KVCache.create`: int8 caches,
    the v2 slot layout, the other families): it still scans over
    `(cache.k, cache.v)`, and nothing of the paged protocol (a layer
    index) has entered it."""
    model = LlamaForCausalLM(CFG)
    cache = KVCache.create(CFG.num_hidden_layers, 2, 32, 2, CFG.head_dim,
                           dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: model.apply({"params": p}, i, cache=c))(
        params, jnp.zeros((2, 1), jnp.int32), cache)
    (eqn,) = [e for e in _scans(jaxpr)
              if e.params["length"] == CFG.num_hidden_layers]
    xs, ys = _scanned(eqn)
    assert xs.count(tuple(cache.k.shape)) == 2
    assert ys.count(tuple(cache.k.shape)) == 2
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    assert not [v for v in eqn.invars[skip:]
                if v.aval.shape == (CFG.num_hidden_layers,)
                and jnp.issubdtype(v.aval.dtype, jnp.integer)]
