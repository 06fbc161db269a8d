"""What the kernel tests of the stacked KV pool share
(`test_kv_pool_decode_kernel.py`, `test_kv_pool_prefill_writer_kernels.py`;
the programs' tests are `test_kv_pool_in_place.py`): the pool's toy shape,
random and poisoned pools, and each paged kernel under ONE `jax.jit`."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.kv_cache import quantize_kv_tokens
from deepspeed_tpu.ops.pallas import paged_attention

L, HKV, NB, BS, D, T = 3, 2, 10, 8, 16, 3

# Each kernel under ONE `jax.jit` a process: a bare call of a Pallas kernel
# compiles its interpreted program anew every time (about a second), and the
# tests call one kernel up to ten times a case. Under the jit a shape
# compiles once, whichever case asks first.
paged_decode_attention = jax.jit(paged_attention.paged_decode_attention,
                                 static_argnames=("window",))
paged_prefill_attention = jax.jit(paged_attention.paged_prefill_attention,
                                  static_argnames=("window", "block_q"))
paged_kv_write = jax.jit(paged_attention.paged_kv_write)


def random_pools(rng, quantized, dtype=jnp.bfloat16, nb=NB):
    k = jnp.asarray(rng.standard_normal((L, HKV, nb, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((L, HKV, nb, BS, D)), dtype)
    if not quantized:
        return k, v, None, None
    (k, ks), (v, vs) = quantize_kv_tokens(k), quantize_kv_tokens(v)
    return k, v, ks, vs


# a batch with parked rows first, between and last; live rows own blocks
# 1.. of the pool, block 0 is NaN
PARKED_ROWS, LIVE_ROWS, WINDOW = [0, 2, 5], [1, 3, 4], 5


def poisoned(rng, quantized, stacked):
    """Pools whose block 0 is NaN in every layer (an int8 pool holds no NaN:
    its block 0 has NaN scales), tables of which the live rows own blocks
    1.. and the parked rows nothing (-1: a read through it clips to block
    0) or, row 2, what a request left behind; and what selects the layer."""
    k, v, ks, vs = random_pools(rng, quantized)
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
