"""The chunked delta rule's prefill kernel (`ops/pallas/delta_rule.py`) in
the Pallas interpreter: against the recurrence a position
(`ops/pallas/kda.kda_step`) and against the plain chunked form
(`models/hybrid.delta_chunked`, its reference) between the two norms a head
the kernel takes in (`hybrid.recurrence_keys`, `hybrid.head_norm`), `o` and
the state after the call, at float32's rounding: both decay forms, two key
heads serving four value heads, a state that is not zero to start from, three
blocks a call with and without a padded tail, two sequences a call. And what
the serving dispatch
(`models/hybrid.delta_prefill`) counts on the telemetry hub.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.ops.pallas import delta_rule
from deepspeed_tpu.ops.pallas.kda import kda_step

B, NK, NV, D, CHUNK = 2, 2, 4, 128, 16
EPS = 1e-6
TOL = 1e-5      # of the reference's largest value: float32's


def operands(s: int, seed: int = 0, channel: bool = False):
    """(q, k, v, g, beta, s0, the norm's weight): the keys as a mixer's
    convolution leaves them, before their norm. A decay a head on two key
    heads for four value heads, or a decay a CHANNEL (every head its own
    keys)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    nk = NV if channel else NK
    return (jax.random.normal(ks[0], (B, s, nk, D)),
            jax.random.normal(ks[1], (B, s, nk, D)),
            jax.random.normal(ks[2], (B, s, NV, D)),
            -2.0 * jax.nn.softplus(jax.random.normal(
                ks[3], (B, s, NV) + ((D,) if channel else ()))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, s, NV))),
            0.5 * jax.random.normal(ks[5], (B, NV, D, D)),
            1.0 + 0.1 * jax.random.normal(ks[6], (D,)))


def spread(q, k):
    return (jnp.repeat(t, NV // t.shape[2], axis=2)
            for t in hybrid.recurrence_keys(q, k))


@jax.jit
def recurrence(q, k, v, g, beta, s0, weight):
    """A position at a time, at `highest` as the chunked forms are."""
    q, k = spread(q, k)

    def step(s, x):
        o, s = kda_step(s, *x)
        return s, o
    with jax.default_matmul_precision("highest"):
        last, o = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return hybrid.head_norm(jnp.moveaxis(o, 0, 1), weight, EPS), last


@jax.jit
def chunked(q, k, v, g, beta, s0, weight):
    return hybrid.delta_prefill_reference(q, k, v, g, beta, s0, CHUNK, weight,
                                          EPS)


@functools.lru_cache(maxsize=None)
def _kernel(kw):
    return jax.jit(lambda *ops: delta_rule.delta_rule_prefill(
        *ops[:-1], CHUNK, ops[-1], l2_eps=hybrid.L2_EPS, norm_eps=EPS,
        interpret=True, **dict(kw)))


def kernel(**kw):
    return _kernel(tuple(kw.items()))


def close(got, want):
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype == jnp.float32
        np.testing.assert_allclose(x, y, rtol=0, atol=TOL * float(
            jnp.max(jnp.abs(y))))


@pytest.mark.parametrize("reference", [recurrence, chunked],
                         ids=["recurrence", "chunked"])
@pytest.mark.parametrize("tail", [0, 5], ids=["whole", "padded"])
@pytest.mark.parametrize("channel", [False, True], ids=["head", "channel"])
def test_kernel_is_the_delta_rule(channel, reference, tail):
    """Three blocks a call (the last one short of `tail` positions), B = 2,
    a state that is not zero; a decay a head with value heads 2j and 2j + 1
    on key head j, and a decay a channel."""
    ops = operands(3 * CHUNK - tail, channel=channel)
    close(kernel()(*ops), reference(*ops))


@pytest.mark.parametrize("kw", [dict(solve=0), dict(solve=CHUNK),
                                dict(heads=2)],
                         ids=lambda kw: "-".join(f"{k}{v}" for k, v in
                                                 kw.items()))
def test_every_grid_and_solve_is_the_same_rule(kw):
    """The Neumann inverse, one sub-block a block, a key head's pair of
    value heads a step: `tools/delta_prefill_forms.py` sweeps these on the
    chip."""
    ops = operands(3 * CHUNK - 5, seed=1)
    close(kernel(**kw)(*ops), chunked(*ops))


def test_kernel_refuses_what_it_cannot_tile():
    q, k, v, g, beta, s0, weight = operands(CHUNK)
    call = functools.partial(delta_rule.delta_rule_prefill, l2_eps=EPS,
                             norm_eps=EPS, interpret=True)
    with pytest.raises(ValueError, match="whole"):
        call(q[..., :64], k[..., :64], v, g, beta, s0[:, :, :64], CHUNK,
             weight)
    with pytest.raises(ValueError, match="delta_rule_prefill: q"):
        call(q, k, v, g[:, :, :2], beta, s0, CHUNK, weight)


@pytest.mark.parametrize("on_chip", [False, True], ids=["cpu", "one_device"])
@pytest.mark.parametrize("channel", [False, True], ids=["head", "channel"])
def test_dispatch_counts_the_form_a_serving_chunk_took(channel, on_chip,
                                                       monkeypatch):
    """`hybrid.delta_prefill` takes the kernel where a bare Pallas call may
    run (`_one_device_kernel`, patched true here, the kernel in the
    interpreter) and the chunked form elsewhere, with one result; the hub
    counts which a trace took."""
    from deepspeed_tpu.ops import attention as dispatch
    from deepspeed_tpu.telemetry import get_hub
    monkeypatch.setattr(dispatch, "_one_device_kernel", lambda name: on_chip)
    ops = operands(2 * CHUNK, seed=2, channel=channel)
    before = dict(get_hub().counters)
    got = hybrid.delta_prefill(*ops[:-1], CHUNK, ops[-1], EPS)
    counted = {k: v - before.get(k, 0) for k, v in get_hub().counters.items()
               if k.startswith("delta_prefill/")}
    assert {k: v for k, v in counted.items() if v} == {
        "delta_prefill/" + ("kernel" if on_chip else "chunked"): 1}
    close(got, chunked(*ops))
