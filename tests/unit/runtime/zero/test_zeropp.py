"""ZeRO++ tests (reference tests/unit/runtime/zero/test_zeropp.py):
quantized gradients (qgZ) and quantized weight gathers (qwZ).

The `no-set-mesh` pragmas answer a tpulint rule that outlived its reason
(ROADMAP C2 retires it); `jax.set_mesh` is the plain way to run these
manual regions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.ops.quantization import (
    dequantize_int4_blockwise, dequantize_int8_blockwise,
    quantize_int4_blockwise, quantize_int8_blockwise)
from deepspeed_tpu.utils import groups

from tests.simple_model import base_config, random_dataset, simple_params


def test_int8_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    q, s = quantize_int8_blockwise(x, 128)
    y = dequantize_int8_blockwise(q, s)
    err = np.abs(np.asarray(y - x)).max() / np.abs(np.asarray(x)).max()
    assert err < 0.01, err


def test_int4_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 4)) * 2.0
    packed, s = quantize_int4_blockwise(x, 128)
    assert packed.size == x.size // 2
    y = dequantize_int4_blockwise(packed, s, x.shape)
    err = np.abs(np.asarray(y - x)).max() / np.abs(np.asarray(x)).max()
    assert err < 0.1, err


def test_quantized_collectives_match_exact():
    """quantized reduce-scatter / all-gather vs exact collectives."""
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        quantized_all_gather, quantized_reduce_scatter, _psum_scatter_dim)
    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)), jnp.float32)

    qrs = jax.shard_map(
        lambda v: quantized_reduce_scatter(v, "data", 0, block=32),
        mesh=mesh, in_specs=P(), out_specs=P("data"), axis_names={"data"})
    rs = jax.shard_map(
        lambda v: _psum_scatter_dim(v, "data", 0) / 4.0,
        mesh=mesh, in_specs=P(), out_specs=P("data"), axis_names={"data"})
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        a = jax.jit(qrs)(x)
        b = jax.jit(rs)(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0.02)

    xs = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)), jnp.float32)
    qag = jax.shard_map(
        lambda v: quantized_all_gather(v, "data", 0, block=32),
        mesh=mesh, in_specs=P("data"), out_specs=P(), axis_names={"data"},
        check_vma=False)
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        g = jax.jit(qag)(xs)
    np.testing.assert_allclose(np.asarray(g), np.asarray(xs), rtol=0, atol=0.03)


def _train(cfg_extra, steps=4, seed=0):
    groups.reset_topology()
    model, params = simple_params(hidden_dim=32)
    cfg = base_config(stage=3, mbs=1, lr=1e-2)
    cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
    cfg["zero_optimization"].update(cfg_extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    data = random_dataset(seed=seed)
    losses = [float(engine.train_batch(batch={k: v[i * 8:(i + 1) * 8]
                                              for k, v in data.items()}))
              for i in range(steps)]
    return losses, engine


def test_qgz_training_tracks_baseline():
    """Stage-3 + quantized gradients: loss trajectory within quantization
    tolerance of the exact run, params still ZeRO-sharded."""
    base, _ = _train({})
    quant, engine = _train({"zero_quantized_gradients": True,
                            "zero_quantized_weights": True})
    assert all(np.isfinite(quant))
    np.testing.assert_allclose(quant, base, rtol=0.05)
    kernel = engine.state.params["linear_0"]["kernel"]
    # spec may shard any free dim over the data axes — just require sharded
    assert "data" in str(kernel.sharding.spec) or "expert" in str(kernel.sharding.spec)


def test_qgz_emits_int8_collectives():
    """The wire format must actually be int8: the compiled step contains an
    s8 all-to-all (comm-volume reduction is real, not cosmetic)."""
    groups.reset_topology()
    model, params = simple_params(hidden_dim=32)
    cfg = base_config(stage=3, mbs=1)
    cfg["zero_optimization"]["zero_quantized_gradients"] = True
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    data = random_dataset()
    batch = {k: v[:8] for k, v in data.items()}
    batch_dev = engine._put_batch(batch, extra_leading=False)
    import jax.numpy as jnp_
    stacked = jax.tree_util.tree_map(lambda x: x[None], batch_dev)
    with engine.mesh:
        txt = engine._get_jit("train_batch").lower(
            engine.state, stacked, jax.random.PRNGKey(0)).compile().as_text()
    assert "all-to-all" in txt
    assert "s8[" in txt, "no int8 tensors in compiled step — qgZ not on the wire"
