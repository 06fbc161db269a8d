"""Decode-attention kernel golden tests (softmax_context slot): vs the
masked XLA reference used by the model decode path."""

import functools
import os

os.environ.setdefault("DS_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops.pallas.decode_attention as da
from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.decode_attention import (KV_TILE_BUDGET,
                                                       _interpret,
                                                       decode_plan,
                                                       kv_write_dense,
                                                       plan_traffic)

TOL = 1e-5 if _interpret() else 2e-2

# ONE `jax.jit` of the kernel for the module: a bare call compiles the
# interpreted kernel anew every time, and the cases below call it up to six
# times at one shape. A case that patches a constant the PLAN reads
# (`KV_TILE_BUDGET`) calls `da.decode_attention` itself: a trace made under
# another budget must not answer it.
decode_attention = jax.jit(da.decode_attention, static_argnames=("block_k",))


@jax.jit
def _ref(q, k_cache, v_cache, lengths):
    m = k_cache.shape[1]
    mask = jnp.arange(m)[None, None, :] < lengths[:, None, None]  # (B,1,M)
    return reference_attention(q, k_cache, v_cache, causal=False,
                               segment_mask=mask)


@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_decode_matches_masked_reference(hkv):
    b, m, h, d = 3, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, m, hkv, d))
    v = jax.random.normal(ks[2], (b, m, hkv, d))
    lengths = jnp.asarray([7, 130, 256], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=64)
    ref = _ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=TOL, atol=TOL)


def test_decode_unaffected_by_garbage_beyond_length():
    """Slots past the cursor hold garbage (stale writes); kernel must not
    read them into the result."""
    b, m, h, d = 2, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, m, h, d))
    v = jax.random.normal(ks[2], (b, m, h, d))
    lengths = jnp.asarray([40, 100], jnp.int32)
    out1 = decode_attention(q, k, v, lengths, block_k=32)
    k2 = k.at[:, 100:].set(1e4)  # poison the tail
    v2 = v.at[:, 100:].set(-1e4)
    out2 = decode_attention(q, k2, v2, lengths, block_k=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_decode_under_jit():
    b, m, h, d = 2, 128, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, m, 2, d))
    v = jax.random.normal(ks[2], (b, m, 2, d))
    lengths = jnp.asarray([64, 128], jnp.int32)
    out = jax.jit(lambda *a: decode_attention(*a, block_k=64))(q, k, v, lengths)
    ref = _ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=TOL, atol=TOL)


def test_cached_attention_auto_dispatch_predicate(monkeypatch):
    """The 'auto' path may bypass the elementwise mask ONLY for single-token
    GQA (n_rep>=4) prefix-mask decodes; windows stay on the XLA path and a
    forced kernel + window raises."""
    import deepspeed_tpu.ops.attention as A
    from deepspeed_tpu.inference.kv_cache import decode_mask

    monkeypatch.setattr(A, "_use_pallas", lambda: True)  # interpret-mode kernel
    rng = np.random.default_rng(0)
    B, M, HKV, NREP, D = 2, 64, 2, 4, 16
    H = NREP * HKV
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, M, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, M, HKV, D)), jnp.float32)
    index = jnp.asarray([10, 33], jnp.int32)
    positions = index[:, None]
    mask = decode_mask(positions, M)

    auto = A.cached_attention(q, k, v, index, mask, impl="auto")
    ref = A.cached_attention(q, k, v, index, mask, impl="reference")
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # banded mask: auto must honor it elementwise (XLA path)
    wmask = decode_mask(positions, M, window=8)
    auto_w = A.cached_attention(q, k, v, index, wmask, impl="auto", window=8)
    ref_w = A.cached_attention(q, k, v, index, wmask, impl="reference")
    np.testing.assert_allclose(np.asarray(auto_w), np.asarray(ref_w),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(auto_w) - np.asarray(ref)).max() > 1e-4

    with pytest.raises(NotImplementedError):
        A.cached_attention(q, k, v, index, wmask, impl="decode_pallas", window=8)

    # multi-token (prefill) sticks to the masked path even under auto
    q4 = jnp.asarray(rng.normal(size=(B, 4, H, D)), jnp.float32)
    m4 = decode_mask(jnp.stack([index + i for i in range(4)], 1), M)
    out = A.cached_attention(q4, k, v, index, m4, impl="auto")
    assert out.shape == (B, 4, H, D)


# ---------------------------------------------------------------------------
# A grid step carries a row GROUP's every KV head (PR 49). The kernel's
# contract, case by case, at blocks of BLK slots so that a row's length falls
# inside a block, on its edge and one past it.

M, BLK = 64, 16
# two groups of four rows (`MIN_STEPS` leaves eight rows of four blocks no
# more): a single token, a block's edge, the edge + 1 and the whole cache in
# one, and the same four the other way round
MIXED = np.asarray([1, BLK, BLK + 1, M, M, BLK + 1, BLK, 1], np.int32)


def _stack(rng, b, hkv, d, layers=2, dtype=jnp.float32, m=M):
    """K and V stacks (L, B, Hkv, M, D), q (B, 1, H, D) is the caller's."""
    return tuple(jnp.asarray(rng.standard_normal((layers, b, hkv, m, d)), dtype)
                 for _ in range(2))


def _per_layer(stack, layer):
    """Layer `layer` of (L, B, Hkv, M, D) as the per-layer (B, M, Hkv, D)."""
    return jnp.swapaxes(stack[layer], 1, 2)


def _query(rng, b, h, d, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal((b, 1, h, d)), dtype)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("hkv", [1, 2, 4])
@pytest.mark.parametrize("n_rep", [1, 8, 16])
def test_rows_of_one_group_are_each_masked_at_their_own_length(n_rep, hkv, d):
    rng = np.random.default_rng(10)
    k, v = _stack(rng, len(MIXED), hkv, d)
    q = _query(rng, len(MIXED), hkv * n_rep, d)
    assert decode_plan(len(MIXED), hkv, M, d, 4, BLK) == (4, BLK)
    got = decode_attention(q, k, v, jnp.asarray(MIXED), layer=jnp.int32(1),
                           block_k=BLK)
    want = _ref(q, _per_layer(k, 1), _per_layer(v, 1), jnp.asarray(MIXED))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)
    # garbage past a row's own length does not reach it, though the group
    # fetched those slots for its longest row
    past = jnp.arange(M)[None, None, :, None] >= MIXED[:, None, None, None]
    got2 = decode_attention(q, jnp.where(past[None], 1e4, k),
                            jnp.where(past[None], -1e4, v),
                            jnp.asarray(MIXED), layer=jnp.int32(1),
                            block_k=BLK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("hkv,n_rep", [(1, 1), (1, 16), (2, 8), (4, 8),
                                       (2, 16), (4, 1)])
def test_staged_equals_written_then_attended_bit_for_bit_under_jit(hkv, n_rep,
                                                                   d):
    """Mixed lengths in one group, an EMPTY row (cursor -1: nothing valid,
    nothing staged lands) and a PARKED row (cursor M: no slot, its token is
    dropped) beside live ones."""
    rng = np.random.default_rng(11)
    index = jnp.asarray([0, BLK - 1, BLK, M - 1, M, 5, -1, 2 * BLK], jnp.int32)
    b = index.shape[0]
    k, v = _stack(rng, b, hkv, d)
    q = _query(rng, b, hkv * n_rep, d)
    new = jnp.asarray(rng.standard_normal((2, 2, b, hkv, d)), jnp.float32)
    k_w, v_w = kv_write_dense(k, v, new[0], new[1], index)
    kw = dict(layer=jnp.int32(1), block_k=BLK)
    written = functools.partial(decode_attention, **kw)
    got = np.asarray(decode_attention(q, k, v, index + 1, k_new=new[0, 1],
                                      v_new=new[1, 1], **kw))
    want = np.asarray(written(q, k_w, v_w, index + 1))
    np.testing.assert_array_equal(got, want)
    # the live rows against the plain reference, the empty row zeros, the
    # parked row what the cache held (its token has no slot)
    ref = np.asarray(_ref(q, _per_layer(k_w, 1), _per_layer(v_w, 1),
                          index + 1))
    live = np.asarray(index) >= 0
    np.testing.assert_allclose(got[live], ref[live], rtol=TOL, atol=TOL)
    assert not got[6].any()
    bare = np.asarray(written(q, k, v, index + 1))
    np.testing.assert_array_equal(got[4], bare[4])
    assert not np.array_equal(got[:4], bare[:4])


@pytest.mark.parametrize("b,rb", [(5, 1), (6, 3), (7, 1), (12, 4), (4, 2),
                                  (16, 4)])
def test_a_batch_the_row_group_does_not_divide(monkeypatch, b, rb):
    """`rb` falls to a divisor of B (1 at worst) under the VMEM budget,
    here a budget of four rows' tiles, and under `MIN_STEPS`."""
    hkv, d = 2, 64
    monkeypatch.setattr(da, "KV_TILE_BUDGET", 4 * 4 * hkv * BLK * d * 4)
    assert decode_plan(b, hkv, M, d, 4, BLK) == (rb, BLK)
    rng = np.random.default_rng(12)
    k, v = _stack(rng, b, hkv, d, layers=1)
    q = _query(rng, b, hkv * 8, d)
    lengths = jnp.asarray(rng.integers(0, M + 1, b), jnp.int32)
    got = da.decode_attention(q, k, v, lengths, layer=jnp.int32(0),
                              block_k=BLK)
    want = np.array(_ref(q, _per_layer(k, 0), _per_layer(v, 0), lengths))
    want[np.asarray(lengths) == 0] = 0.0   # an empty row writes zeros
    np.testing.assert_allclose(np.asarray(got), want, rtol=TOL, atol=TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hkv,n_rep", [(1, 8), (2, 8), (4, 16), (2, 1)])
def test_the_stack_by_layer_equals_the_per_layer_array(hkv, n_rep, dtype):
    rng = np.random.default_rng(13)
    k, v = _stack(rng, len(MIXED), hkv, 64, layers=3, dtype=dtype)
    q = _query(rng, len(MIXED), hkv * n_rep, 64, dtype)
    outs = []
    for layer in range(3):
        got = decode_attention(q, k, v, jnp.asarray(MIXED),
                               layer=jnp.int32(layer), block_k=BLK)
        want = decode_attention(q, _per_layer(k, layer), _per_layer(v, layer),
                                jnp.asarray(MIXED), block_k=BLK)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        outs.append(np.asarray(got, np.float32))
    assert not np.array_equal(outs[0], outs[2])


@pytest.mark.parametrize("hkv,n_rep", [(1, 8), (2, 8), (4, 1), (2, 16)])
def test_int8_view_with_unit_scales_is_bit_equal(hkv, n_rep):
    """Integer values in int8's range are lossless under unit scales: the
    scales' path (float32 products, the scale riding the lanes) must then
    give the unquantized kernel's bits, each row at its own length."""
    rng = np.random.default_rng(14)
    b, d = len(MIXED), 64
    kc, vc = (jnp.asarray(rng.integers(-30, 30, (b, M, hkv, d)), jnp.float32)
              for _ in range(2))
    q = _query(rng, b, hkv * n_rep, d)
    ones = jnp.ones((b, M, hkv), jnp.float32)
    want = decode_attention(q, kc, vc, jnp.asarray(MIXED), block_k=BLK)
    got = decode_attention(q, kc.astype(jnp.int8).astype(jnp.float32),
                           vc.astype(jnp.int8).astype(jnp.float32),
                           jnp.asarray(MIXED), block_k=BLK,
                           k_scales=ones, v_scales=ones)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and real scales move it: the scales are read, a row at a time
    got2 = decode_attention(q, kc.astype(jnp.int8).astype(jnp.float32),
                            vc.astype(jnp.int8).astype(jnp.float32),
                            jnp.asarray(MIXED), block_k=BLK,
                            k_scales=ones.at[2].set(0.5), v_scales=ones)
    diff = (np.asarray(got2) != np.asarray(got)).any(axis=(1, 2, 3))
    assert list(diff) == [r == 2 for r in range(b)]


# the two cells that run the kernel: generate-batch (Qwen2.5-3B: 32 rows,
# n_rep 8) and generate-reason (Nemotron: 64 rows, n_rep 16), bf16; and a
# long cache of four blocks
CELL_SHAPES = [(32, 2, 1280, 128), (64, 2, 1024, 128), (32, 2, 4096, 128)]


@pytest.mark.parametrize("b,hkv,m,d", CELL_SHAPES)
def test_plan_at_the_cells_shapes(b, hkv, m, d):
    rb, blk_k = decode_plan(b, hkv, m, d, 2)
    nk = m // blk_k
    assert b % rb == 0 and m % blk_k == 0 and blk_k % 128 == 0
    # the double-buffered K and V tiles fit the stated budget
    assert 4 * rb * hkv * blk_k * d * 2 <= KV_TILE_BUDGET
    # whole blocks: nothing fetched that holds no token
    full = np.full(b, blk_k)
    live, fetched, steps = plan_traffic((rb, blk_k), full, m)
    assert live == fetched == b * blk_k
    assert steps == b // rb * nk and da.MIN_STEPS <= steps <= b * nk / 4
    if nk > 1:   # a token past a block's edge costs its group a block a row
        live, fetched, _ = plan_traffic((rb, blk_k), full + 1, m)
        assert live == b * (blk_k + 1) and fetched == b * 2 * blk_k
    # one long row makes its whole group fetch its blocks; a parked row
    # (length > M) counts M slots, as the kernel attends it
    lengths = np.full(b, 1)
    lengths[0] = m + 1
    live, fetched, _ = plan_traffic((rb, blk_k), lengths, m)
    assert live == m + b - 1
    assert fetched == rb * m + (b - rb) * blk_k >= live
    # an empty group still fetches its first block (the clamp's floor)
    assert plan_traffic((rb, blk_k), np.zeros(b), m)[:2] == (0, b * blk_k)
    # calls summed over a leading axis: a decode step each
    steps2 = plan_traffic((rb, blk_k), np.stack([full, lengths]), m)
    assert steps2 == (b * blk_k + live, b * blk_k + fetched, 2 * steps)


@pytest.mark.parametrize("cap,want", [(None, 640), (256, 256), (64, 64),
                                      (100, 80)])
def test_plan_blocks_divide_the_cache_in_whole_lane_tiles(cap, want):
    """M 1280: whole tiles of 128 lanes where a divisor allows, else the
    largest divisor under the cap."""
    assert decode_plan(32, 2, 1280, 128, 2, cap)[1] == want


def test_plan_shortens_the_block_where_one_rows_heads_pass_the_budget():
    """32 KV heads (a forced kernel at n_rep 1): a block of every head of
    ONE row is what the budget bounds, and a step carries one row."""
    rb, blk_k = decode_plan(16, 32, 2048, 128, 2)
    assert (rb, blk_k) == (1, 256)
    assert 4 * 32 * blk_k * 128 * 2 <= KV_TILE_BUDGET
