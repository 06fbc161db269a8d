"""The KDA state-update kernel, the latent (MLA) decode kernel and the latent
cache's writer against plain `jax.numpy`, in interpret mode (the chip's
compiler sees them in `test_chip_compile.py`, the chip in `chip_smoke.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.kda import (kda_state_update,
                                          kda_state_update_reference, kda_step)
from deepspeed_tpu.ops.pallas.mla import (latent_write_dense,
                                          mla_latent_decode,
                                          mla_latent_decode_reference)

F32 = jnp.float32


def _kda_operands(key, rows, heads, d, layers=3, floor=-5.0):
    ks = jax.random.split(key, 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    return (jax.random.normal(ks[0], (layers, rows, heads, d, d), F32),
            unit(jax.random.normal(ks[1], (rows, heads, d), F32)) * d ** -0.5,
            unit(jax.random.normal(ks[2], (rows, heads, d), F32)),
            jax.random.normal(ks[3], (rows, heads, d), F32),
            floor * jax.random.uniform(ks[4], (rows, heads, d), F32),
            jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads), F32)))


@pytest.mark.parametrize("rows,heads,d", [(3, 4, 16), (2, 32, 128)],
                         ids=["toy", "published_heads"])
def test_kda_kernel_is_the_delta_rule_in_place(rows, heads, d):
    state, *rest = _kda_operands(jax.random.PRNGKey(0), rows, heads, d)
    layer = 1
    o, new = kda_state_update(state, layer, *rest, interpret=True)
    o_ref, new_ref = kda_state_update_reference(state, layer, *rest)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, new_ref, rtol=1e-5, atol=1e-5)
    # the other layers' states are the buffer's own, untouched
    assert bool(jnp.all(new[0] == state[0])) and bool(jnp.all(new[2] == state[2]))
    # and the step is the recurrence written out for one head of one row
    q, k, v, g, beta = (t[0, 0] for t in rest)
    s = state[layer, 0, 0] * jnp.exp(g)[:, None]              # S (dk, dv)
    s = s + beta * jnp.outer(k, v - s.T @ k)
    np.testing.assert_allclose(new[layer, 0, 0], s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o[0, 0], s.T @ q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(kda_step(state[layer], *rest)[0], o_ref)


def test_kda_kernel_refuses_a_bf16_state():
    state, *rest = _kda_operands(jax.random.PRNGKey(0), 2, 4, 16)
    with pytest.raises(ValueError, match="float32"):
        kda_state_update(state.astype(jnp.bfloat16), 0, *rest)


def _mla_operands(key, rows, heads, slots, rank, rope, layers=2,
                  dtype=jnp.bfloat16):
    kq, kr, kc, kn, kp = jax.random.split(key, 5)
    normal = lambda k_, shape: jax.random.normal(k_, shape, F32).astype(dtype)  # noqa: E731
    pos = jax.random.randint(kp, (rows,), 0, slots, jnp.int32)
    return (normal(kq, (rows, heads, rank)), normal(kr, (rows, heads, rope)),
            normal(kc, (layers, rows, 1, slots, rank + rope)), pos,
            normal(kn, (rows, rank + rope)))


@pytest.mark.parametrize("slots,rank,rope", [(32, 32, 8), (1024, 512, 64)],
                         ids=["toy", "published_width_576"])
def test_latent_decode_kernel_against_numpy(slots, rank, rope):
    q_lat, q_rope, stack, pos, new = _mla_operands(
        jax.random.PRNGKey(1), 3, 4, slots, rank, rope)
    scale = (rank + rope) ** -0.5
    args = (q_lat, q_rope, stack, 1, pos + 1, scale)
    got = mla_latent_decode(*args, new=new, slots=pos)
    want = mla_latent_decode_reference(*args, new=new, slots=pos)
    # the kernel rounds the softmax weights to the cache's bf16 before the
    # weighted sum; the reference keeps them float32: 2^-8 of a unit value
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # the staged token is read as if written: the same without staging over
    # a stack that holds it
    written = stack.at[1, jnp.arange(3), 0, pos].set(new)
    held = mla_latent_decode(q_lat, q_rope, written, 1, pos + 1, scale)
    np.testing.assert_array_equal(got, held)
    # nothing past a row's length is read
    junk = written.at[1, :, 0].set(jnp.where(
        (jnp.arange(slots)[None, :] > pos[:, None])[..., None], 77.0,
        written[1, :, 0]))
    np.testing.assert_array_equal(
        held, mla_latent_decode(q_lat, q_rope, junk, 1, pos + 1, scale))


def test_latent_writer_writes_one_row_in_place_and_drops_parked_rows():
    _, _, stack, pos, new = _mla_operands(jax.random.PRNGKey(2), 4, 4, 64,
                                          32, 8, layers=3)
    pos = pos.at[2].set(64)                     # parked: at max_len
    news = jnp.stack([new, 2 * new, 3 * new])   # (L, B, W)
    got = latent_write_dense(stack, news, pos)
    want = stack.at[:, jnp.arange(4), 0, pos].set(news, mode="drop")
    np.testing.assert_array_equal(got, want)
    assert bool(jnp.all(got[:, 2] == stack[:, 2]))
