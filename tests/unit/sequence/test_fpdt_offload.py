"""FPDT host-offload tier (reference `sequence/fpdt_layer.py:510`):
the 'host_offload' remat policy stages block-boundary residuals to pinned
host memory; numbers must match the all-HBM whole-block-remat run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.models.llama import (llama_config, llama_loss_fn,
                                        materialize_params)
from deepspeed_tpu.utils import groups

from tests.simple_model import base_config


_BATCH = {"input_ids": np.random.default_rng(0)
          .integers(0, 256, (8, 64)).astype(np.int32)}


def _run(policy):
    groups.reset_topology()
    cfg = llama_config("llama-tiny", dtype=jnp.float32, remat=True,
                       remat_policy=policy, loss_chunk_size=32)
    model, params = materialize_params(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=base_config(stage=3, mbs=1), loss_fn=llama_loss_fn(model))
    losses = [float(engine.train_batch(batch=_BATCH)) for _ in range(3)]
    return losses, jax.tree_util.tree_map(np.asarray, engine.state.params)


@pytest.fixture(scope="module")
def hbm_reference():
    """One whole-block-remat reference run shared by every policy case
    (each engine build costs minutes of real time on this box)."""
    return _run("nothing")


def _run_or_skip(policy):
    try:
        return _run(policy)
    except Exception as e:  # pragma: no cover - backend capability gate
        if on_tpu():
            raise  # host offload WORKS on real TPU — a failure is a bug
        pytest.skip(f"host offload unsupported on this backend: {e}")


@pytest.mark.slow
def test_host_offload_remat_matches_hbm(hbm_reference):
    off_losses, off_params = _run_or_skip("host_offload")
    ref_losses, ref_params = hbm_reference
    np.testing.assert_allclose(off_losses, ref_losses, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        off_params, ref_params)


@pytest.mark.parametrize("policy", ["host_offload_dense",
                                    "host_offload_dense_mlp"])
def test_dense_offload_policies_match(policy, hbm_reference):
    """The r5 dense-intermediate offload tiers (attn_qkv/resid_mid/
    mlp_gate_up names) must be numerically exact vs whole-block remat —
    they lose on v5e PCIe (see models/llama.py notes) but stay correct."""
    off_losses, off_params = _run_or_skip(policy)
    ref_losses, ref_params = hbm_reference
    np.testing.assert_allclose(off_losses, ref_losses, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        off_params, ref_params)
