"""The two kernels of DENSE latent attention (`models/openpangu.py`) against
their plain `jax.numpy` forms, in interpret mode (the chip's compiler sees
them in `test_chip_compile.py`, the chip in `chip_smoke.py`): the prefill
kernel of `ops/pallas/mla_sparse.py` with NO bias (a chunk at the row's
start, one that starts past 0, one whose last live block the diagonal cuts
raggedly; blocks above the diagonal never read), and `ops/pallas/mla.py`'s
decode kernel at 128 heads, whose block is planned from the shapes. Each
kernel under ONE module-level `jax.jit` a shape."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention as ops
from deepspeed_tpu.ops.pallas import mla
from deepspeed_tpu.ops.pallas import mla_sparse as ms

F32 = jnp.float32
L, B, M, RANK, DR, H, DN, DV = 2, 3, 384, 32, 8, 4, 16, 16
LAYER, SCALE = 1, 0.3


def normal(key, shape):
    return jax.random.normal(key, shape, F32)


@pytest.fixture(scope="module")
def slab():
    return normal(jax.random.PRNGKey(0), (L, B, 1, M, RANK + DR))


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 64 queries x 128 slots, the expansion 2 heads and 128 slots
    a pass: a chunk of 128 queries walks two query tiles and up to three
    blocks of the 384-slot row."""
    monkeypatch.setattr(ms, "EXPAND_HEADS", 2)
    monkeypatch.setattr(ms, "EXPAND_BLOCK", 128)
    monkeypatch.setattr(ms, "PREFILL_QUERIES", 64)
    monkeypatch.setattr(ms, "PREFILL_BLOCK", 128)


def chunk(c):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    return (normal(k[0], (c, H, DN)), normal(k[1], (c, H, DR)),
            normal(k[2], (RANK, H, DN + DV)) * 0.2)


# start 0: the row's first chunk, every tile on the diagonal; 128: a chunk
# that starts past 0, a whole block wholly BELOW the diagonal (no mask) and
# one on it; 160: the chunk ends at 287, inside block 2 (256 .. 383), which
# the diagonal cuts raggedly, and neither query tile's edge is a block's
@pytest.mark.parametrize("start", [0, 128, 160])
def test_a_prefill_chunk_attends_every_row_up_to_its_own(slab, small_tiles,
                                                         start):
    c, row = 128, 1
    q_nope, q_rope, w_kvb = chunk(c)
    # slots past the chunk's end hold what an earlier batch left: inside the
    # diagonal's blocks they are masked, further blocks are never read
    lat = slab.at[:, :, :, start + c:].set(1e4)
    dead = -(-(start + c) // 128) * 128
    lat = lat.at[:, :, :, dead:].set(jnp.nan)
    got = jax.jit(ms.mla_dense_prefill, static_argnums=7)(
        q_nope, q_rope, w_kvb, lat, LAYER, row, start, SCALE)
    assert bool(jnp.all(jnp.isfinite(got)))
    want = ms.mla_dense_prefill_reference(
        q_nope, q_rope, w_kvb, slab.at[:, :, :, start + c:].set(0.0), LAYER,
        row, start, SCALE)
    np.testing.assert_allclose(got, want, atol=5e-6)
    # and it is the biased kernel under the causal bias, to the last bit of
    # what a bias of 0 and NEG_INF leaves
    under = jax.jit(ms.mla_sparse_prefill, static_argnums=8)(
        q_nope, q_rope, w_kvb, ms.causal_bias(start, c, M),
        slab.at[:, :, :, start + c:].set(0.0), LAYER, row, start, SCALE)
    np.testing.assert_allclose(got, under, atol=1e-6)


def test_the_dense_prefill_makes_no_bias_and_names_itself(slab, small_tiles):
    """The traced program holds no (chunk x cache) array and the call is
    named `mla_dense_prefill`; the biased form keeps its own name."""
    c = 128
    q_nope, q_rope, w_kvb = chunk(c)
    text = str(jax.make_jaxpr(lambda *a: ms.mla_dense_prefill(
        *a, LAYER, 1, 128, SCALE))(q_nope, q_rope, w_kvb, slab))
    assert f"f32[{c},{M}]" not in text and f"bf16[{c},{M}]" not in text
    assert ms.DENSE_PREFILL_NAME in text and "mla_sparse_prefill" not in text
    biased = str(jax.make_jaxpr(lambda *a: ms.mla_sparse_prefill(
        *a, LAYER, 1, 128, SCALE))(q_nope, q_rope, w_kvb,
                                   ms.causal_bias(128, c, M), slab))
    assert ms.PREFILL_NAME in biased and ms.DENSE_PREFILL_NAME not in biased


def test_off_the_chip_the_prefill_is_the_plain_form(slab):
    """`ops.attention.latent_dense_prefill` here (no chip): the plain form,
    whatever the shapes; a chunk no multiple of 128 too."""
    from deepspeed_tpu.inference.kv_cache import DenseLayer
    q_nope, q_rope, w_kvb = chunk(40)
    got = ops.latent_dense_prefill(q_nope, q_rope, w_kvb,
                                   DenseLayer(slab, LAYER), 2, 7, SCALE)
    want = ms.mla_sparse_attention_plain(
        q_nope, q_rope, w_kvb, ms.causal_bias(7, 40, M), slab[LAYER, 2, 0],
        SCALE)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ------------------------------------------------------- decode, 128 heads


def test_the_decode_block_is_planned_from_the_shapes():
    """ONE rule, `sparse_select.block_of`: the largest divisor of the row up
    to a cap the heads give, in whole lane tiles where the row has such a
    divisor. Under 128 heads the cap PR 47 read on the chip (Ling's cell: 32
    heads, rows of 2,048 slots, 512 a block as before); from 128 heads on
    the wide block."""
    assert mla.decode_block(32, 2048) == 512
    assert mla.decode_block(32, 25600) == 512
    # where halving from 512 stopped at 128 a row's larger divisor is taken;
    # a row with no divisor in whole lane tiles is its largest divisor
    assert mla.decode_block(32, 1152) == 384
    assert mla.decode_block(4, 96) == 96
    # read on the chip (PERF.md, PR 58): 2,560 slots, which every row
    # `latent.cache_slots` gives divides; a shorter row is one block
    assert mla.decode_block(128, 25600) == mla.decode_block(128, 33280) == 2560
    assert mla.decode_block(128, 1024) == 1024
    assert mla.decode_block(256, 5120) == 2560


@functools.partial(jax.jit, static_argnames=("staged",))
def decode(q_lat, q_rope, stack, lengths, new, staged):
    return mla.mla_latent_decode(
        q_lat, q_rope, stack, LAYER, lengths, SCALE,
        new=new if staged else None, slots=lengths - 1 if staged else None)


# lengths: a full row, a row that ends inside a block, one token (the staged
# one alone), at the published widths (rank 512, rope 64: the two lane views)
@pytest.mark.parametrize("staged", [True, False])
def test_the_latent_decode_at_128_heads(staged):
    h, m, rank, rope = 128, 1024, 512, 64
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    stack = normal(k[0], (L, B, 1, m, rank + rope))
    q_lat, q_rope = normal(k[1], (B, h, rank)) * 0.2, normal(k[2], (B, h, rope))
    new = normal(k[3], (B, rank + rope))
    lengths = jnp.asarray([m, 700, 1], jnp.int32)
    got = decode(q_lat, q_rope, stack, lengths, new, staged)
    want = mla.mla_latent_decode_reference(
        q_lat, q_rope, stack, LAYER, lengths, SCALE,
        new=new if staged else None, slots=lengths - 1 if staged else None)
    assert got.shape == (B, h, rank) and got.dtype == F32
    np.testing.assert_allclose(got, want, atol=2e-5)
