"""The layers' tensor-parallel reductions as exchanges under the other
half-batch's compute (`runtime/domino/transformer.py`, engaged by
`models/llama._exchange_layout` from the mesh and the shapes alone): the
dp2 x tp2 step is the single-device step, its trace holds 8 exchanges a
layer and no `psum` over `model`, whatever the remat policy saves, and every
mesh or shape the exchange does not know gets the program it got before."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name
from jax.core import jaxprs_in_params

from deepspeed_tpu.models import llama
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.partitioning import BATCH_AXES, shard_along


def _case(rows=4, **cfg):
    """Llama-tiny in float32 with the chunked loss, its parameters (made
    before any topology is installed), ids and `loss(params)`."""
    groups.reset_topology()
    cfg = llama.llama_config("llama-tiny", dtype=jnp.float32,
                             loss_chunk_size=8, **cfg)
    model, params = llama.materialize_params(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (rows, 16)),
                      jnp.int32)
    loss_fn = llama.llama_loss_fn(model)
    return cfg, params, lambda p: loss_fn(p, {"input_ids": ids}, None)[0]


def _install(**mesh_dims):
    n = int(np.prod(list(mesh_dims.values())))
    return groups.initialize(devices=jax.devices()[:n], **mesh_dims).mesh


REMAT = {"no_remat": dict(remat=False),
         "checkpoint_dots": dict(remat=True, remat_policy="checkpoint_dots"),
         "dots": dict(remat=True, remat_policy="dots")}


@pytest.mark.parametrize("remat", ["no_remat", "checkpoint_dots"])
def test_dp2_tp2_step_is_the_single_device_step(remat):
    """Loss and every gradient of a dp2 x tp2 step (two half-batches a
    layer, four exchanges forward and four backward) against one device's,
    at the tolerances the sharded chunked loss is held to."""
    cfg, params, loss = _case(**REMAT[remat])
    want_loss, want = jax.jit(jax.value_and_grad(loss))(params)
    _install(dp=2, tp=2)
    assert llama._exchange_layout(cfg, 4) is not None
    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


def _collectives_by_scan(jaxpr):
    """For each `scan` of a traced program, outermost first, the number of
    `ppermute`s and of `psum`s over `model` its body holds (a nested scan's
    are its own)."""
    found = []

    def over_model(eqn, *names):
        axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
        axes = axes if isinstance(axes, tuple) else (axes,)
        return eqn.primitive.name in names and "model" in axes

    def walk(jx, counts):
        for eqn in jx.eqns:
            counts[0] += over_model(eqn, "ppermute")
            counts[1] += over_model(eqn, "psum", "psum_invariant")
            inner = counts
            if eqn.primitive.name == "scan":
                inner = [0, 0]
                found.append(inner)
            for sub in jaxprs_in_params(eqn.params):
                walk(sub, inner)

    outside = [0, 0]
    walk(jaxpr, outside)
    return outside, [tuple(c) for c in found]


@pytest.mark.parametrize("remat", list(REMAT))
def test_step_holds_eight_exchanges_a_layer_and_no_psum(remat):
    """The mechanism's counter, in the traced dp2 x tp2 step: the forward
    layer scan holds 4 exchanges over `model` (2 sites x 2 half-batches),
    the backward layer scan 4 and no more: a remat policy that saved the
    dots but not the exchanged sum would run `o_proj`, `down_proj` and their
    exchanges again there (8). No `psum` over `model` is left in either,
    and `count_exchanges`, the engine's counter, reads the same 8."""
    from deepspeed_tpu.runtime.domino import count_exchanges
    cfg, params, loss = _case(**REMAT[remat])
    _install(dp=2, tp=2)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr
    outside, scans = _collectives_by_scan(jaxpr)
    assert outside == [0, 0]
    assert sorted(s for s in scans if s != (0, 0)) == [(4, 0), (4, 0)]
    assert count_exchanges(jaxpr) == 8


def _parent_block():
    """`LlamaBlock`'s training path as it stood until PR 53 (the plain walk,
    spelled out), kept as the yardstick of the programs nothing is named
    in."""
    class LlamaBlock(nn.Module):
        cfg: llama.LlamaConfig

        @nn.compact
        def __call__(self, h, cos_sin, kv=None):
            cfg = self.cfg
            cos, sin = cos_sin
            h = shard_along(h, BATCH_AXES, "sequence", None)
            h = checkpoint_name(h, "fpdt_residual")
            attn = llama.LlamaAttention(cfg, name="self_attn")
            ln1 = llama.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                name="input_layernorm")
            mlp = llama.LlamaMLP(cfg, name="mlp")
            ln2 = llama.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                name="post_attention_layernorm")
            h = h + attn(ln1(h), cos, sin)
            h = checkpoint_name(h, "resid_mid")
            h = h + mlp(ln2(h))
            return h, None

    return LlamaBlock


@pytest.mark.parametrize("mesh_dims,rows", [
    (None, 4),                      # no topology at all
    (dict(dp=1), 4),                # one device (train-2k runs under one)
    (dict(tp=2, sp=2), 4),          # an axis the exchange does not name
    (dict(pp=2, tp=2), 4),
    (dict(dp=2, tp=2), 6),          # an odd row count a device
    (dict(dp=2, tp=4), 4),          # `model` past the exchange's size
], ids=["no_topology", "one_device", "tp_sp", "pipe", "odd_rows", "tp4"])
def test_a_mesh_the_exchange_does_not_know_gets_the_parent_program(
        monkeypatch, mesh_dims, rows):
    """What the layers observe: a `model` axis of 2, batch axes beside it
    and nothing else, an even number of rows a device. Anywhere else
    NOTHING is named: the lowered text of `value_and_grad` is the text of
    the plain block's, the form until PR 53."""
    cfg, params, loss = _case(rows=rows, remat=True,
                              remat_policy="checkpoint_dots")
    if mesh_dims is not None:
        _install(**mesh_dims)
    assert llama._exchange_layout(cfg, rows) is None

    def lowered():
        return jax.jit(jax.value_and_grad(loss)).lower(params).as_text()

    text = lowered()
    monkeypatch.setattr(llama, "LlamaBlock", _parent_block())
    assert text == lowered()


@pytest.mark.parametrize("mesh_dims,want", [
    (dict(dp=2, tp=2), 8), (dict(dp=1), 0)],
    ids=["dp2_tp2", "one_device"])
def test_the_engine_counts_what_the_step_named(mesh_dims, want):
    """`tp_exchange_sites` on the `compile` span of `train:train_batch`
    and as a hub gauge (docs/telemetry.md): read off the traced step, 8
    where the layers' reductions are exchanges (a layer then walks two
    half-batches), 0 where the partitioner places them."""
    import deepspeed_tpu
    from deepspeed_tpu.telemetry.spans import get_span_store
    cfg, params, _ = _case(remat=True, remat_policy="checkpoint_dots")
    model = llama.LlamaForCausalLM(cfg)
    _, specs = llama.init_params_and_specs(cfg)
    n = int(np.prod(list(mesh_dims.values())))
    topology = groups.MeshTopology(devices=jax.devices()[:n], **mesh_dims)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, topology=topology,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2, "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "tensor_parallel": {"tp_size": mesh_dims.get("tp", 1)}},
        loss_fn=llama.llama_loss_fn(model), base_param_specs=specs)
    rows = 2 * 2 * mesh_dims["dp"]
    ids = np.random.default_rng(0).integers(0, 256, (rows, 16))
    assert np.isfinite(float(engine.train_batch(
        batch={"input_ids": ids.astype(np.int32)})))
    fields = [s["fields"] for s in get_span_store().spans()
              if s["name"] == "compile"
              and s["fields"]["program"] == "train:train_batch"][-1]
    assert fields["tp_exchange_sites"] == want
    assert "tp_half_batches" not in fields   # it was `sites > 0`: gone
    assert engine.telemetry.gauges["tp_exchange_sites"] == want
