"""The two kernel forms a window layer reads through (PR 60): the dense
decode kernel over a RING (`decode_attention(slots=)`, traced as
`self_attn_ring_decode`) and the flash forward under a static window
(`flash_attention(window=)`, `self_attn_flash_fwd_band`), in interpret mode
against plain `jax.numpy`: the ring at every fill, the band at windows that
are and are not whole blocks; and that `window=None` is the program it was.

Tolerance 2e-6 on outputs of magnitude 1, float32 on both sides: the
kernels' online softmax against a whole-row softmax, nothing else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import KVCache
from deepspeed_tpu.ops import attention as ops
from deepspeed_tpu.ops.pallas import decode_attention as dense
from deepspeed_tpu.ops.pallas import flash_attention as flash

TOL = 2e-6
ROWS, HKV, N_REP, D, M, LAYERS = 5, 2, 4, 16, 8, 3
# a row's cursor (positions cached before this step's token): an empty ring,
# a part-full one, one this token fills exactly, one it wraps for the first
# time, one wrapped many times over
FILLS = [0, 3, M - 1, M, 3 * M + 5]


@pytest.fixture(scope="module")
def ring():
    """Rows at `FILLS`, each row's whole history of K and V, and the ring
    cache a prefill of that history leaves (layer 1 of 3 the one read)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    longest = max(FILLS) + 1
    k, v = (jax.random.normal(key, (ROWS, longest, HKV, D))
            for key in keys[:2])
    q = jax.random.normal(keys[2], (ROWS, 1, HKV * N_REP, D))
    index = jnp.asarray(FILLS, jnp.int32)
    cache = KVCache.create_stacked(LAYERS, ROWS, M, HKV, D, jnp.float32,
                                   ring=True)
    # a row at a time: each has its own length
    for row, n in enumerate(FILLS):
        one = KVCache.create_stacked(LAYERS, 1, M, HKV, D, jnp.float32,
                                     ring=True)
        if n:
            one = one.write_prefill(1, k[row:row + 1, :n], v[row:row + 1, :n])
        cache = cache.replace(
            k=cache.k.replace(stack=cache.k.stack.at[:, row].set(
                one.k.stack[:, 0])),
            v=cache.v.replace(stack=cache.v.stack.at[:, row].set(
                one.v.stack[:, 0])))
    cache = cache.replace(index=index)
    rows = jnp.arange(ROWS)
    return q, k, v, cache, (k[rows, index], v[rows, index])


def _by_position(q, k, v, index):
    """Each row's query against its LAST `M` positions up to `index`,
    position by position: no ring, no slot."""
    out = []
    for row, n in enumerate(FILLS):
        lo = max(0, n + 1 - M)
        out.append(ops.reference_attention(
            q[row:row + 1], k[row:row + 1, lo:n + 1], v[row:row + 1, lo:n + 1],
            causal=False))
    return jnp.concatenate(out)


def test_the_ring_reader_is_the_window_at_every_fill(ring):
    """The kernel under its ring name, given a COUNT and the staged token's
    SLOT, against the row's last 8 positions read by position; and the
    masked XLA path `cached_attention` takes off the chip says the same."""
    q, k, v, cache, (k_new, v_new) = ring
    want = _by_position(q, k, v, cache.index)
    got = dense.decode_attention(
        q, cache.k.stack, cache.v.stack, jnp.minimum(cache.index + 1, M),
        layer=jnp.int32(1), k_new=k_new, v_new=v_new, slots=cache.index % M)
    np.testing.assert_allclose(got, want, atol=TOL)
    views = tuple(c.replace(stage=new) for c, new in zip(
        cache.layer_views(1, staged=True), (k_new, v_new)))
    assert views[0].ring
    xla = ops.cached_attention(q, *views, cache.index, None)
    np.testing.assert_allclose(xla, want, atol=TOL)
    # the step's one write lands the token where the reader put it
    landed = cache.land(*(jnp.stack([jnp.zeros_like(n), n, jnp.zeros_like(n)])
                          for n in (k_new, v_new)))
    rows = jnp.arange(ROWS)
    assert np.array_equal(landed.k.stack[1, rows, :, cache.index % M], k_new)


def test_a_count_alone_does_not_place_the_staged_token(ring):
    """Why the slot is given APART from the count: once a ring has wrapped
    the count stays at 8 and the token's slot goes on turning. Read as a
    dense row (the token in the last live slot) the wrapped rows come out
    wrong, the others right."""
    q, k, v, cache, (k_new, v_new) = ring
    want = _by_position(q, k, v, cache.index)
    dense_read = dense.decode_attention(
        q, cache.k.stack, cache.v.stack, jnp.minimum(cache.index + 1, M),
        layer=jnp.int32(1), k_new=k_new, v_new=v_new)
    far = np.abs(np.asarray(dense_read - want)).max(axis=(1, 2, 3))
    assert np.all(far[:3] < TOL) and np.all(far[3:] > 1e-3), far


def test_a_ring_is_read_one_staged_token_a_row(ring):
    q, _, _, cache, (k_new, _) = ring
    views = cache.layer_views(1, staged=True)
    with pytest.raises(NotImplementedError, match="one staged token"):
        ops.cached_attention(q, *views, cache.index, None)   # nothing staged
    with pytest.raises(NotImplementedError, match="count of live slots|COUNT"):
        ops._decode_kernel_wanted("decode_pallas", 8, 4)


def test_both_names_are_one_kernel_body(ring):
    """`self_attn_dense_decode` for full-length rows, `self_attn_ring_decode`
    for a ring: the benchmark's metrics tell them apart by name."""
    q, _, _, cache, (k_new, v_new) = ring
    def text(**kw):
        return jax.jit(lambda q, k, v, n, kn, vn: dense.decode_attention(
            q, k, v, n, layer=jnp.int32(1), k_new=kn, v_new=vn, **kw)).lower(
            q, cache.k.stack, cache.v.stack, cache.index + 1, k_new,
            v_new).as_text(debug_info=True)
    assert dense.RING_NAME in text(slots=cache.index % M)
    plain = text()
    assert dense.DENSE_NAME in plain and dense.RING_NAME not in plain


# (positions, window, block): the window whole blocks, not whole blocks,
# smaller than a block, and nearly the sequence
BANDS = [(64, 32, 16), (64, 24, 16), (64, 5, 16), (48, 40, 16), (96, 32, 32)]


@pytest.fixture(scope="module")
def qkv():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(keys[0], (2, 96, 4, 16)),
            jax.random.normal(keys[1], (2, 96, 2, 16)),
            jax.random.normal(keys[2], (2, 96, 2, 16)))


@pytest.mark.parametrize("s,window,blk", BANDS,
                         ids=[f"s{s}_w{w}_b{b}" for s, w, b in BANDS])
def test_the_banded_forward_is_the_masked_reference(qkv, s, window, blk):
    q, k, v = (t[:, :s] for t in qkv)
    got = flash.flash_attention(q, k, v, window=window, block_q=blk,
                                block_k=blk)
    want = ops.reference_attention(q, k, v, window=window)
    np.testing.assert_allclose(got, want, atol=TOL)
    # what a prefill's window layers call, off the chip: XLA's band or the
    # masked reference, the same numbers
    np.testing.assert_allclose(ops.banded_prefill(q, k, v, window), want,
                               atol=TOL)


def test_a_query_block_visits_its_band_alone():
    """The grid's last axis is the widest band, not the sequence: at 8,192
    positions under a window of 2,048, five key blocks of 512 a query block
    where the causal grid has up to sixteen."""
    for s, window, blk, want in ((8192, 2048, 512, 5), (8192, 2048, 1024, 3),
                                 (64, 24, 16, 3), (64, 5, 16, 2)):
        nq = s // blk
        nb = max((i * blk + blk - 1) // blk - max(i * blk - window + 1, 0)
                 // blk + 1 for i in range(nq))
        assert nb == want
        for i in range(nq):
            first, last = flash._band_blocks(i, blk, blk, window)
            assert int(first) * blk <= max(i * blk - window + 1, 0) \
                < (int(first) + 1) * blk and last == i


def test_without_a_window_the_program_is_the_one_it_was(qkv):
    """`window=None` (and a window that covers the sequence) lowers to the
    text the call without the argument lowers to, kernel and all; the band
    has its own name; its backward raises by name, the plain one runs."""
    q, k, v = (t[:, :64] for t in qkv)
    def text(debug_info=False, **kw):
        return jax.jit(lambda q, k, v: flash.flash_attention(
            q, k, v, block_q=16, block_k=16, **kw)).lower(q, k, v).as_text(
            debug_info=debug_info)
    plain = text()
    assert text(window=None) == plain and text(window=64) == plain
    assert text(window=24) != plain
    named = text(debug_info=True)       # the kernel's name is a location's
    assert flash.FWD_NAME in named and flash.BAND_NAME not in named
    assert flash.BAND_NAME in text(debug_info=True, window=24)
    loss = lambda **kw: jax.grad(lambda q: flash.flash_attention(  # noqa: E731
        q, k, v, block_q=16, block_k=16, **kw).sum())(q)
    assert loss().shape == q.shape
    with pytest.raises(NotImplementedError, match="banded flash BACKWARD"):
        loss(window=24)
    # the shared `attn_impl` knob: 'pallas' with a window is the band
    np.testing.assert_allclose(
        ops.attention(q, k, v, window=24, impl="pallas"),
        ops.reference_attention(q, k, v, window=24), atol=TOL)
