"""What a remat'd layer scan keeps of the flash kernel, read from the jaxpr.

A `pallas_call` is no dot, so a policy that keeps dot results alone makes a
layer's backward run `self_attn_flash_fwd` a second time for `out` and the
logsumexp. `checkpoint_dots` (and `checkpoint_dots_gmm`, built on it) keep the
two by the names the kernel's forward rule gives them. A layer's backward is
ONE `self_attn_flash_bwd` call (the one-pass form, PR 55; two past
`ONE_PASS_DQ_BYTES` of resident dq). No chip: the graph is traced, and the one
numeric case runs the kernels in the Pallas interpreter.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.models.llama import (
    LlamaForCausalLM, _remat_policy, llama_config, llama_loss_fn,
    materialize_params)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.sharded import sharded_flash_attention
from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns

FWD, BWD = "self_attn_flash_fwd", "self_attn_flash_bwd"
KEEPING = ["checkpoint_dots", "checkpoint_dots_gmm"]
HEADS, HEAD_DIM, SEQ = 2, 64, 128
HIDDEN = HEADS * HEAD_DIM


def _layer(h, w, attend=flash_attention):
    """Projections (dots) around one flash call: a block's shape in small."""
    b, s, _ = h.shape
    q, k, v = ((h @ w[n]).reshape(b, s, HEADS, HEAD_DIM) for n in "qkv")
    a = attend(q, k, v, causal=True).reshape(b, s, HIDDEN)
    return h + a @ w["o"], None


def _stack_loss(policy, layer=_layer):
    body = layer if policy is None else jax.checkpoint(
        layer, prevent_cse=False, policy=_remat_policy(policy))

    def loss(ws, h):
        return jnp.sum(jax.lax.scan(body, h, ws)[0] ** 2)
    return loss


def _stack_inputs(layers=2, rows=1):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    ws = {n: 0.05 * jax.random.normal(k, (layers, HIDDEN, HIDDEN), jnp.float32)
          for n, k in zip("qkvo", ks)}
    return ws, jax.random.normal(ks[4], (rows, SEQ, HIDDEN), jnp.float32)


def _kernels_by_scan(jaxpr):
    """One sorted list of kernel names a scan that holds kernels, in program
    order: the forward layer scan first."""
    scans = []
    for _, scan in primitive_eqns(jaxpr, {"scan"}):
        calls = primitive_eqns(scan.params["jaxpr"], {"pallas_call"})
        if calls:
            scans.append(sorted(
                e.params["name"] if "name" in e.params
                else e.params["name_and_src_info"].name for _, e in calls))
    return scans


def _stack_scans(policy, layer=_layer, rows=1):
    ws, h = _stack_inputs(rows=rows)
    return _kernels_by_scan(
        jax.make_jaxpr(jax.grad(_stack_loss(policy, layer)))(ws, h).jaxpr)


@pytest.mark.parametrize("policy", KEEPING)
def test_backward_scan_never_runs_the_forward_kernel(policy):
    assert _stack_scans(policy) == [[FWD], [BWD]]


def test_without_the_names_the_forward_kernel_runs_twice():
    """The same graph under a policy that keeps nothing: so the case above
    can fail."""
    assert _stack_scans("nothing") == [[FWD], [BWD, FWD]]


@pytest.mark.parametrize("policy", KEEPING + ["nothing"])
def test_the_names_are_seen_through_the_sharded_wrapper(policy):
    """dp2 x tp2, as the four-chip cell calls the kernel: inside a
    `shard_map` over batch and heads."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))

    def attend(q, k, v, causal):
        return sharded_flash_attention(
            q, k, v, mesh, P("data", None, "model", None), causal=causal)

    again = [] if policy in KEEPING else [FWD]
    assert _stack_scans(policy, partial(_layer, attend=attend), rows=2) == [
        [FWD], [BWD] + again]


def test_a_graph_without_the_kernel_is_checkpoint_dots_exactly():
    """No value of those names: what is kept is what
    `jax.checkpoint_policies.checkpoint_dots` keeps."""
    def layer(h, w):
        return jnp.tanh(h @ w["q"]) @ w["o"], None

    def residuals(policy):
        body = jax.checkpoint(layer, prevent_cse=False, policy=policy)
        ws, h = _stack_inputs()
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda ws: jnp.sum(jax.lax.scan(body, h, ws)[0])))(ws)
        fwd = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")
        return [v.aval.shape for v in fwd.outvars]

    assert residuals(_remat_policy("checkpoint_dots")) == residuals(
        jax.checkpoint_policies.checkpoint_dots)


@pytest.mark.parametrize("policy", KEEPING + ["nothing"])
def test_the_model_layer_scan(policy):
    """The model's own remat'd scan (`nn.remat` under `nn.scan`), the flash
    kernel asked for by name since no chip is here."""
    cfg = llama_config(
        "llama-tiny", dtype=jnp.float32, hidden_size=HIDDEN,
        num_attention_heads=HEADS, num_key_value_heads=1,
        max_position_embeddings=SEQ, remat=True, remat_policy=policy,
        attn_impl="pallas")
    model = LlamaForCausalLM(cfg)
    params = jax.eval_shape(lambda: materialize_params(cfg)[1])
    ids = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    loss_fn = llama_loss_fn(model)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, i: loss_fn(p, {"input_ids": i}, None)[0]))(params, ids)
    again = [] if policy in KEEPING else [FWD]
    assert _kernels_by_scan(jaxpr.jaxpr) == [[FWD], [BWD] + again]


def test_gradients_equal_those_without_remat():
    ws, h = _stack_inputs()
    plain = jax.jit(jax.grad(_stack_loss(None)))(ws, h)
    kept = jax.jit(jax.grad(_stack_loss("checkpoint_dots")))(ws, h)
    for name in ws:
        np.testing.assert_array_equal(np.asarray(kept[name]),
                                      np.asarray(plain[name]), err_msg=name)
