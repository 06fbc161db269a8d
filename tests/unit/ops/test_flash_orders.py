"""The flash forward in the projections' own order, (B, S, H, D), against
the head-major one: the same kernel bodies under other block specs, so the
values are EQUAL, not close; and which order a call takes (the head width
and whether it is differentiated decide, the hub's counters say).
"""

import os

os.environ.setdefault("DS_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import flash_attention as flash_module
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.telemetry import TelemetryHub
from deepspeed_tpu.telemetry.hub import get_hub, set_hub

S = 256
# form -> (sq, sk, window)
FORMS = {"band": (S, S, 96), "triangular": (S, S, None),
         "rectangular": (S // 2, S, None)}


def _qkv(sq, sk, h, hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = lambda s, n: (2, s, n, d)  # noqa: E731
    return (jax.random.normal(ks[0], shape(sq, h), jnp.bfloat16),
            jax.random.normal(ks[1], shape(sk, hkv), jnp.bfloat16),
            jax.random.normal(ks[2], shape(sk, hkv), jnp.bfloat16))


@pytest.fixture
def forms():
    """The forward calls this test's traces make, by order."""
    was = get_hub()
    hub = set_hub(TelemetryHub(enabled=False))
    yield lambda: {k.split("/")[1]: v for k, v in hub.counters.items()
                   if k.startswith("flash_fwd/")}
    set_hub(was)


@pytest.mark.parametrize("blk", [64, 128])
@pytest.mark.parametrize("n_rep", [1, 8])
@pytest.mark.parametrize("form", list(FORMS))
def test_token_major_is_head_major_bit_for_bit(form, n_rep, blk, forms):
    sq, sk, window = FORMS[form]
    q, k, v = _qkv(sq, sk, 8, 8 // n_rep, 128)
    args = (128 ** -0.5, True, blk, blk, window)
    swap = flash_module._swap
    out_h, lse_h, qs, _, _ = flash_module._flash_bhsd_fwd(q, k, v, *args)
    out_t, lse_t = flash_module._fwd(swap(qs), k, v, *args[1:],
                                     token_major=True)
    assert forms() == {"head_major": 1, "token_major": 1}
    assert out_t.shape == q.shape and out_h.shape == swap(q).shape
    np.testing.assert_array_equal(np.asarray(out_t, np.float32),
                                  np.asarray(swap(out_h), np.float32))
    np.testing.assert_array_equal(np.asarray(lse_t), np.asarray(lse_h))
    # and the public call at this width IS the token-major one
    got = flash_attention(q, k, v, block_q=blk, block_k=blk, window=window)
    assert forms() == {"head_major": 1, "token_major": 2}
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(out_t, np.float32))


def test_a_narrow_head_goes_head_major(forms):
    """Head width 64 is half a lane tile: no column block of (B, S, H x D)
    is a head's alone, so the call transposes as it always has."""
    q, k, v = _qkv(S, S, 4, 2, 64)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    assert forms() == {"head_major": 1}
    ref = flash_module._swap(flash_module._flash_bhsd_fwd(
        q, k, v, 64 ** -0.5, True, 64, 64)[0])
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_differentiated_call_is_head_major(remat, forms):
    """Differentiated, a call at width 128 runs the head-major `_fwd` and
    `_bwd` (so does its rematerialised forward): the gradients are the ones
    `_bwd` gives from the head-major forward's residuals, and the value is
    the undifferentiated call's."""
    q, k, v = _qkv(S, S, 8, 2, 128)
    blk, scale = 128, 128 ** -0.5
    attn = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_q=blk, block_k=blk)
    if remat:
        attn = jax.checkpoint(attn)
    out, vjp = jax.vjp(attn, q, k, v)
    do = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
        out.shape).astype(out.dtype)
    grads = vjp(do)
    # the counter counts TRACES: `jax.checkpoint` (like a scanned layer)
    # traces the primal before it is differentiated and never lowers it
    assert forms() == ({"head_major": 1, "token_major": 1} if remat
                       else {"head_major": 1})
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(attn, *a)[1](do))(q, k, v))
    assert text.count("name=self_attn_flash_bwd") == 1
    assert text.count("name=self_attn_flash_fwd") == 1 + remat
    assert "bf16[2,256,1024]" not in text  # no (B, S, H x D) view: no primal

    swap = flash_module._swap
    o, lse, qs, kt, vt = flash_module._flash_bhsd_fwd(q, k, v, scale, True,
                                                      blk, blk)
    want = flash_module._bwd(qs, kt, vt, o, lse, swap(do), scale, True,
                             blk, blk)
    for g, w in zip(grads, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(swap(w), np.float32))
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(swap(o), np.float32))
    before = forms()
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, block_q=blk, block_k=blk),
                   np.float32), np.asarray(out, np.float32))
    assert forms() == {**before,
                       "token_major": before.get("token_major", 0) + 1}
