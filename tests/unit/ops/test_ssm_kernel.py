"""`ssm_state_update` (Pallas, interpret mode off the chip) against one step
of the recurrence in plain `jax.numpy`, on a stacked state.

Tolerance: both sides are float32 and compute each state element from the
same three products, so they agree to rounding (1e-6 relative); `y` sums 16
or 128 of them, 1e-5.
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas.ssm import (ssm_state_update,
                                          ssm_state_update_reference)


def inputs(layers, b, h, p, n, g, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (layers, b, h, p, n)),
            jax.random.normal(ks[1], (b, h, p)),
            jax.nn.softplus(jax.random.normal(ks[2], (b, h)) - 2.0),
            -jnp.exp(jax.random.normal(ks[3], (h,))),
            jax.random.normal(ks[4], (b, g, n)),
            jax.random.normal(ks[5], (b, g, n)),
            jax.random.normal(ks[6], (h,)))


@pytest.mark.parametrize("shape", [(3, 4, 4, 8, 16, 2), (2, 3, 8, 8, 128, 1),
                                   (2, 8, 16, 64, 128, 2)])
def test_one_step_matches_the_recurrence(shape):
    state, x, dt, a, b, c, d = inputs(*shape)
    layer = shape[0] - 1
    y, new = jax.jit(ssm_state_update, static_argnums=1)(
        state, layer, x, dt, a, b, c, d)
    # the recurrence written out, a head at a time
    hb = shape[2] // shape[5]
    bh, ch = (jnp.repeat(v, hb, axis=1) for v in (b, c))
    want = jnp.exp(dt * a)[..., None, None] * state[layer] + \
        jnp.einsum("bh,bhp,bhn->bhpn", dt, x, bh)
    want_y = jnp.einsum("bhpn,bhn->bhp", want, ch) + d[None, :, None] * x
    assert jnp.allclose(new[layer], want, rtol=1e-6, atol=1e-6)
    assert jnp.allclose(y, want_y, rtol=1e-5, atol=1e-5)
    ref_y, ref_new = ssm_state_update_reference(state, layer, x, dt, a, b, c, d)
    assert jnp.allclose(y, ref_y, rtol=1e-5, atol=1e-5)
    assert jnp.allclose(new, ref_new, rtol=1e-6, atol=1e-6)


def test_the_other_layers_of_the_stack_are_untouched_and_the_layer_may_be_traced():
    state, x, dt, a, b, c, d = inputs(3, 4, 4, 8, 16, 2, seed=1)
    step = jax.jit(lambda s, l: ssm_state_update(s, l, x, dt, a, b, c, d))
    _, new = step(state, jnp.int32(1))
    assert jnp.array_equal(new[0], state[0]) and jnp.array_equal(new[2], state[2])
    assert not jnp.allclose(new[1], state[1])


def test_a_state_that_is_not_float32_is_refused():
    state, x, dt, a, b, c, d = inputs(1, 2, 4, 8, 16, 2)
    with pytest.raises(ValueError, match="float32"):
        ssm_state_update(state.astype(jnp.bfloat16), 0, x, dt, a, b, c, d)
