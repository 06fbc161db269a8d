"""The layers' tensor-parallel reductions as exchanges under the other
half-batch's compute (`runtime/domino/transformer.py`, engaged by
`models/llama._exchange_layout` from the mesh and the shapes alone): the
dp2 x tp2 step is the single-device step, its trace holds 8 exchanges a
layer and no `psum` over `model`, whatever the remat policy saves, and every
mesh or shape the exchange does not know gets the program it got before.

And, under a ZeRO plan whose gradient accumulators are cut over the batch
axes (`runtime/zero/partition.landing_on`, as the engine installs it for the
trace of a step): the seven kernels' `dW` reductions over `data` as
exchanges onto the accumulators' shards (`land_dw`), 7 a backward layer,
the partitioner's sums, nothing re-laid afterwards, and the program
untouched wherever the plan does not cut a kernel so."""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name
from jax.core import jaxprs_in_params

from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.runtime.zero.partition import ZeroShardingPlan, landing_on
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


@contextlib.contextmanager
def _planned(cfg, params, stage=3, threshold=0):
    """A ZeRO plan over the installed topology, readable by the layers as
    the engine makes it for the trace of a step; yields the shardings of
    the parameters at rest and of the gradients' accumulators."""
    topology = groups.get_topology()
    plan = ZeroShardingPlan(topology, DeepSpeedZeroConfig(
        stage=stage, stage3_param_persistence_threshold=threshold))
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    _, base = llama.init_params_and_specs(cfg)
    at_rest = {kind: plan.tree_shardings(plan.tree_specs(shapes, base, kind))
               for kind in ("param", "grad")}
    accumulators = jax.tree_util.tree_map(
        lambda p, s: jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=s),
        shapes, at_rest["grad"])
    with landing_on(plan, accumulators):
        yield at_rest["param"], at_rest["grad"]


def _step(loss, params_at_rest, grads_at_rest):
    return jax.jit(jax.value_and_grad(loss), in_shardings=(params_at_rest,),
                   out_shardings=(None, grads_at_rest))


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


def _all_reduce_and_slice(tensor, group="data", scatter_dim=0, sum_dtype=None):
    """`comm.reduce_scatter_by_exchange`'s contract said the partitioner's
    way: all-reduce the whole tensor, keep this rank's slice."""
    total = jax.lax.psum(tensor.astype(sum_dtype or tensor.dtype), group)
    width = tensor.shape[scatter_dim] // jax.lax.psum(1, group)
    return jax.lax.dynamic_slice_in_dim(
        total, jax.lax.axis_index(group) * width, width, scatter_dim)


@pytest.mark.parametrize("remat", ["no_remat", "checkpoint_dots"])
def test_exchanged_dw_is_the_partitioners_sum(monkeypatch, remat):
    """Every gradient of a dp2 x tp2 step whose seven `dW` reductions are
    exchanges onto the ZeRO-3 accumulators' shards, in float32. BIT FOR BIT
    the step that all-reduces each whole `dW` in the same place and keeps a
    slice (over two ranks `a + b` is `b + a`). Against the partitioner-only
    step of the same mesh and layouts: every leaf but the seven kernels bit
    for bit, the kernels to the last bits (a rank adds its two half-batches'
    products and then the peer's, `(a0 + a1) + (b0 + b1)`, the order the
    chip's partitioner has too; this backend's reduces a half-batch at a
    time, `(a0 + b0) + (a1 + b1)`). Against one device at the file's
    tolerances. (14-19 s: four steps of the tiny model compiled for a
    4-device mesh, each a program of its own; none can be left out.)"""
    from deepspeed_tpu.comm import comm
    cfg, params, loss = _case(**REMAT[remat])
    want_loss, want = jax.jit(jax.value_and_grad(loss))(params)
    _install(dp=2, tp=2)
    with _planned(cfg, params) as at_rest:
        partitioner_only = _step(loss, *at_rest)   # traced under no plan
        got_loss, got = _step(loss, *at_rest)(params)
        monkeypatch.setattr(comm, "reduce_scatter_by_exchange",
                            _all_reduce_and_slice)
        _, sliced = _step(loss, *at_rest)(params)
    _, theirs = partitioner_only(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    for (path, g), s, t, r in zip(jax.tree_util.tree_leaves_with_path(got),
                                  *map(jax.tree_util.tree_leaves,
                                       (sliced, theirs, want))):
        g, t = np.asarray(g), np.asarray(t)
        np.testing.assert_array_equal(g, np.asarray(s))
        if jax.tree_util.keystr(path).endswith("['kernel']"):
            np.testing.assert_allclose(g, t, rtol=2e-6, atol=2e-8)
        else:
            np.testing.assert_array_equal(g, t)
        np.testing.assert_allclose(g, np.asarray(r), rtol=1e-5, atol=1e-6)


def _collectives_by_scan(jaxpr, axis="model"):
    """For each `scan` of a traced program, outermost first, the number of
    `ppermute`s and of `psum`s over `axis` its body holds (a nested scan's
    are its own)."""
    found = []

    def over_model(eqn, *names):
        axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
        axes = axes if isinstance(axes, tuple) else (axes,)
        return eqn.primitive.name in names and axis in axes

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


def _data_collectives_in_loops(step, params):
    """The compiled step's collectives over `data` alone, inside loop
    bodies, whose result is a matrix the size of a layer kernel's shard or
    larger (16 x 16 up): kind by kind. What a kernel's gradient crosses
    `data` by; the parameters' own gathers are `all-gather`s."""
    from deepspeed_tpu.tools.tpucomms.hlo import op_axes, parse_collectives
    sizes = groups.get_topology().sizes
    found = {}
    for op in parse_collectives(step.lower(params).compile().as_text()):
        if (op.in_loop and op_axes(op, sizes)[0] == ("data",)
                and len(op.shape) == 2 and min(op.shape) >= 16):
            found[op.kind] = found.get(op.kind, 0) + 1
    return found


@pytest.mark.parametrize("remat", ["no_remat", "checkpoint_dots"])
def test_planned_step_holds_seven_dw_exchanges_and_relays_nothing(remat):
    """Under a ZeRO-3 plan the backward layer scan of the traced dp2 x tp2
    step holds 7 `ppermute`s over `data`, one a kernel (the half-batches'
    partials are added first), under the `dw_exchange` scope, which is what
    the engine counts; the forward scan none; the 8 over `model` stand.
    And the COMPILED step moves no kernel-sized matrix over `data` in a
    loop by any other collective: no all-reduce (the partitioner-only step
    has them: the control), and no all-to-all, reduce-scatter or further
    permute, which a shard guessed wrong would be re-laid by."""
    from deepspeed_tpu.runtime.domino import DW_EXCHANGE, count_exchanges
    cfg, params, loss = _case(**REMAT[remat])
    _install(dp=2, tp=2)
    with _planned(cfg, params) as at_rest:
        partitioner_only = _step(loss, *at_rest)   # traced under no plan
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr
        ours = _data_collectives_in_loops(_step(loss, *at_rest), params)
    theirs = _data_collectives_in_loops(partitioner_only, params)
    assert count_exchanges(jaxpr, BATCH_AXES, DW_EXCHANGE) == 7
    assert count_exchanges(jaxpr) == 8
    _, scans = _collectives_by_scan(jaxpr, "data")
    # the loss's chunk loop exchanges its own `dW` (PR 52): 1
    assert sorted(s for s in scans if s != (0, 0)) == [(1, 0), (7, 0)]
    assert theirs.get("all-reduce", 0) + theirs.get("reduce-scatter", 0) > 0
    assert {k: n for k, n in ours.items() if k != "all-gather"} == {
        "collective-permute": 7 + 1}


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


@pytest.mark.parametrize("mesh_dims,rows,plan", [
    (dict(dp=2, tp=2), 4, dict(stage=0)),
    (dict(dp=1, tp=2), 4, dict()),
    (dict(dp=2, tp=2), 4, dict(threshold=10 ** 9)),
    (dict(dp=3, tp=2), 12, dict()),
    (dict(dp=2, tp=2), 4, None),
], ids=["stage_0", "dp_1", "under_persistence_threshold", "dp_3_divides_none",
        "no_plan_after_a_planned_trace"])
def test_a_plan_that_cuts_no_kernel_gets_the_parent_program(mesh_dims, rows,
                                                            plan):
    """What `land_dw` observes: a plan being traced under, whose
    accumulator of THIS kernel is cut over the rows' batch axes in one free
    dimension of a layer's slice, the kernel not smaller than the plan's
    `param_persistence_threshold`. Anywhere else NOTHING is named: the
    lowered `value_and_grad` is the one lowered with no plan at all (whose
    equality with the parent commit's is checked tree against tree, PR 57);
    and a plan does not outlive its trace."""
    cfg, params, loss = _case(rows=rows, remat=True,
                              remat_policy="checkpoint_dots")
    _install(**mesh_dims)

    def traced():
        return jax.jit(jax.value_and_grad(loss)).lower(params).as_text()

    bare = traced()
    with _planned(cfg, params, **(plan or {})):
        planned = traced()
    if plan is None:    # a plan that DOES cut the kernels: 7 exchanges
        assert planned != bare and "dw_exchange" not in bare
        planned = traced()
    assert planned == bare


@pytest.mark.parametrize("mesh_dims,want,want_dw", [
    (dict(dp=2, tp=2), 8, 7), (dict(dp=1), 0, 0)],
    ids=["dp2_tp2", "one_device"])
def test_the_engine_counts_what_the_step_named(mesh_dims, want, want_dw):
    """`tp_exchange_sites` and `dw_exchange_sites` on the `compile` span of
    `train:train_batch` and as hub gauges (docs/telemetry.md): read off
    the traced step, 8 where the layers' reductions over `model` are
    exchanges (a layer then walks two half-batches) and 7 where its
    kernels' `dW` reductions over `data` are (the engine's own ZeRO-3 plan,
    read by the layers while it traces the step), 0 where the partitioner
    places them."""
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
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 0},
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
    assert fields["dw_exchange_sites"] == want_dw
    assert "tp_half_batches" not in fields   # it was `sites > 0`: gone
    assert engine.telemetry.gauges["tp_exchange_sites"] == want
    assert engine.telemetry.gauges["dw_exchange_sites"] == want_dw
