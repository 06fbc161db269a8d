"""1-bit Adam tests (reference tests/unit/runtime/half_precision/onebit/
test_onebit.py): warmup parity with Adam, frozen variance + compressed
momentum after freeze, and the sign-compressed allreduce backend.

The `no-set-mesh` pragmas answer a tpulint rule that outlived its reason
(ROADMAP C2 retires it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.ops.optimizers import fused_adam, onebit_adam
from deepspeed_tpu.utils import groups

from tests.simple_model import base_config, random_dataset, simple_params


def test_onebit_warmup_matches_adam():
    params = {"w": jnp.arange(8.0) / 8.0}
    g = {"w": jnp.ones(8) * 0.1}
    ob, ad = onebit_adam(freeze_step=100), fused_adam()
    s1, s2 = ob.init(params), ad.init(params)
    p1, p2 = params, params
    for _ in range(5):
        p1, s1 = ob.update(g, s1, p1, 0.01)
        p2, s2 = ad.update(g, s2, p2, 0.01)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-6)


def test_onebit_freezes_variance_and_compresses():
    params = {"w": jnp.arange(8.0) / 8.0}
    ob = onebit_adam(freeze_step=2)
    s = ob.init(params)
    p = params
    rng = np.random.default_rng(0)
    for i in range(5):
        g = {"w": jnp.asarray(rng.normal(size=8), jnp.float32)}
        p, s = ob.update(g, s, p, 0.01)
        if i == 1:
            v_at_freeze = np.asarray(s.exp_avg_sq["w"])
    np.testing.assert_array_equal(np.asarray(s.exp_avg_sq["w"]), v_at_freeze)
    assert float(jnp.abs(s.error["w"]).max()) > 0  # error feedback active


def test_onebit_engine_training_converges():
    groups.reset_topology()
    model, params = simple_params(hidden_dim=32)
    cfg = base_config(stage=1, mbs=1,
                      opt="OneBitAdam", lr=1e-2)
    cfg["optimizer"]["params"]["freeze_step"] = 3
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    data = random_dataset()
    losses = [float(engine.train_batch(batch={k: v[:8] for k, v in data.items()}))
              for _ in range(8)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_compressed_allreduce_error_feedback():
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)  # row per worker

    def region(x_local, err):
        avg, new_err = compressed_allreduce(x_local[0], err[0], "data")
        return avg, new_err[None]

    f = jax.shard_map(region, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P(), P("data")), axis_names={"data"},
                      check_vma=False)
    err = jnp.zeros((8, 16), jnp.float32)
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        avg, new_err = jax.jit(f)(x, err)
    # per-worker error is exactly the local compression residual
    np.testing.assert_allclose(
        np.asarray(new_err[0]),
        np.asarray(x[0] - jnp.sign(x[0]) * jnp.mean(jnp.abs(x[0]))),
        rtol=1e-5, atol=1e-6)

    # identical inputs on every worker → avg reproduces sign(x)*scale exactly
    same = jnp.broadcast_to(x[0], (8, 16))
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        avg2, _ = jax.jit(f)(same, err)
    np.testing.assert_allclose(
        np.asarray(avg2),
        np.asarray(jnp.sign(x[0]) * jnp.mean(jnp.abs(x[0]))), rtol=1e-5)
