"""The differential decode kernel (interpret mode off the chip) against its
plain form: over a ring and over a slab, with and without a staged token,
short rows, full rings and rows that hold nothing; and the Mamba-1 state
update against the plain recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.diff_attention import (
    _blocks, diff_decode_attention, diff_decode_attention_reference)
from deepspeed_tpu.ops.pallas.ssm import (ssm_state_update_m1,
                                          ssm_state_update_m1_reference)

L, B, G, W, R = 3, 4, 2, 32, 2


def paired_queries(key):
    """(B, G, 2R, W): R pairs' [q1 | 0], then their [0 | q2]."""
    q = jax.random.normal(key, (B, G, 2 * R, W))
    half = (jnp.arange(W) < W // 2)[None, None, None, :]
    first = (jnp.arange(2 * R) < R)[None, None, :, None]
    return jnp.where(half == first, q, 0.0)


def inputs(m, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (paired_queries(ks[0]), jax.random.normal(ks[1], (L, B, G, m, W)),
            jax.random.normal(ks[2], (L, B, G, m, W)),
            jax.random.normal(ks[3], (B, G, W)),
            jax.random.normal(ks[4], (B, G, W)))


@pytest.mark.parametrize("staged", [True, False])
@pytest.mark.parametrize("positions", [[0, 16, 63, 39], [5, 64, 200, 127]])
def test_a_ring_is_read_as_its_valid_slots(staged, positions):
    """Position p's token stands in slot p mod 64; a row past the window
    reads all 64 slots, whatever their order."""
    m = 64
    q, k, v, kn, vn = inputs(m)
    pos = jnp.asarray(positions)
    kw = dict(k_new=kn, v_new=vn, slots=pos % m) if staged else {}
    args = (q, k, v, 1, jnp.minimum(pos + 1, m), jnp.float32(0.7), 0.25)
    got = diff_decode_attention(*args, ring=True, **kw)
    want = diff_decode_attention_reference(*args, **kw)
    assert got.shape == (B, G, R, W) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("staged", [True, False])
def test_a_slab_in_blocks_with_rows_of_every_length(staged, monkeypatch):
    """Several blocks of slots a row (the block budget shrunk): rows that
    end inside the first block, on a block's edge and in the last."""
    from deepspeed_tpu.ops.pallas import diff_attention
    m = 512
    monkeypatch.setattr(diff_attention, "_BLOCK_BYTES", 128 * W * 4)
    assert _blocks(G, m, W, 4) == (1, 128)
    q, k, v, kn, vn = inputs(m, seed=1)
    lengths = jnp.asarray([1, 128, 300, 512])
    kw = dict(k_new=kn, v_new=vn, slots=lengths - 1) if staged else {}
    args = (q, k, v, 2, lengths, jnp.float32(0.45), 0.25)
    got = diff_decode_attention(*args, **kw)
    want = diff_decode_attention_reference(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_the_combine_is_a1_less_lam_a2_normalised():
    """Against the mathematics written out a pair at a time: two softmaxes
    over the halves of the key, one value of full width, the subtraction and
    the RMS norm."""
    m = 16
    q, k, v, _, _ = inputs(m, seed=2)
    lengths = jnp.full((B,), m)
    lam = 0.6
    got = np.asarray(diff_decode_attention(q, k, v, 0, lengths,
                                           jnp.float32(lam), 0.25))
    h = W // 2
    for g in range(G):
        for r in range(R):
            q1, q2 = q[0, g, r, :h], q[0, g, R + r, h:]
            a1 = jax.nn.softmax(k[0, 0, g, :, :h] @ q1 * 0.25) @ v[0, 0, g]
            a2 = jax.nn.softmax(k[0, 0, g, :, h:] @ q2 * 0.25) @ v[0, 0, g]
            d = a1 - lam * a2
            want = d / jnp.sqrt(jnp.mean(d * d) + 1e-5)
            np.testing.assert_allclose(got[0, g, r], np.asarray(want),
                                       atol=3e-5)


def test_a_staged_token_with_no_slot_is_nowhere():
    m = 64
    q, k, v, kn, vn = inputs(m, seed=3)
    lengths = jnp.asarray([10, 20, 30, 40])
    base = diff_decode_attention(q, k, v, 1, lengths, jnp.float32(0.7), 0.25)
    parked = diff_decode_attention(q, k, v, 1, lengths, jnp.float32(0.7), 0.25,
                                   k_new=kn, v_new=vn,
                                   slots=jnp.full((B,), m))
    np.testing.assert_array_equal(np.asarray(parked), np.asarray(base))


@pytest.mark.parametrize("channels", [256, 96])
def test_the_mamba1_update_is_the_plain_recurrence(channels):
    n = 16
    ks = jax.random.split(jax.random.PRNGKey(4), 7)
    state = jax.random.normal(ks[0], (L, B, n, channels))
    x = jax.random.normal(ks[1], (B, channels))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, channels)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[3], (n, channels)))
    b, c = (jax.random.normal(k, (B, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (channels,))
    y, new = ssm_state_update_m1(state, 1, x, dt, a, b, c, d)
    y_ref, new_ref = ssm_state_update_m1_reference(state, 1, x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(new_ref), atol=1e-6)
    # one layer's slab is written, the others are as they were
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(state[2]))
    # every (channel, state) element decays on its own
    h = np.asarray(new[1, 0])
    want = np.exp(np.asarray(dt[0])[None] * np.asarray(a)) \
        * np.asarray(state[1, 0]) \
        + (np.asarray(dt[0] * x[0]))[None] * np.asarray(b[0])[:, None]
    np.testing.assert_allclose(h, want, atol=1e-5)


def test_the_mamba1_state_is_kept_in_float32():
    with pytest.raises(ValueError, match="float32"):
        ssm_state_update_m1(jnp.zeros((1, 2, 16, 128), jnp.bfloat16), 0,
                            jnp.zeros((2, 128)), jnp.zeros((2, 128)),
                            jnp.zeros((16, 128)), jnp.zeros((2, 16)),
                            jnp.zeros((2, 16)), jnp.zeros((128,)))


@pytest.mark.parametrize("length", [64, 37, 16, 9])
def test_banded_attention_is_the_windowed_causal_softmax(length):
    """Whole windows, a ragged tail, exactly one window and less than one,
    grouped heads: against the masked plain form."""
    from deepspeed_tpu.ops.attention import (banded_attention,
                                             reference_attention)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, length, 4, 8))
    k = jax.random.normal(ks[1], (2, length, 2, 8))
    v = jax.random.normal(ks[2], (2, length, 2, 8))
    got = banded_attention(q, k, v, 16, softmax_scale=0.3)
    want = reference_attention(q, k, v, causal=True, softmax_scale=0.3,
                               window=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
