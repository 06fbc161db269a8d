"""Shared model-zoo pieces: losses, embedding helpers.

The loss here is the counterpart of the reference's sequence-parallel
vocab-parallel cross entropy (`deepspeed/sequence/cross_entropy.py`): with
logits sharded over the `model` (vocab) and/or `sequence` axes, the reductions
XLA emits from the shardings are the same ones the reference codes by hand.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       ignore_index: int = IGNORE_INDEX,
                       z_loss: float = 0.0) -> jnp.ndarray:
    """Mean token CE in fp32. logits (B, S, V), labels (B, S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    idx = jnp.clip(labels, 0, logits.shape[-1] - 1)
    picked = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
    token_loss = lse - picked
    if z_loss > 0.0:
        token_loss = token_loss + z_loss * jnp.square(lse)
    mask = (labels != ignore_index).astype(jnp.float32)
    return jnp.sum(token_loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def shift_labels(input_ids: jnp.ndarray, ignore_index: int = IGNORE_INDEX) -> jnp.ndarray:
    """Next-token labels: labels[t] = input_ids[t+1]; last position ignored."""
    return jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], ignore_index)], axis=1)


def causal_lm_loss(logits: jnp.ndarray, input_ids: jnp.ndarray,
                   labels: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if labels is None:
        labels = shift_labels(input_ids)
    return cross_entropy_loss(logits, labels)


def dense(features, logical, dtype, name, use_bias: bool = False):
    """Zoo-standard projection: logical-axis-partitioned kernel (+ bias)."""
    import flax.linen as nn
    return nn.Dense(features, use_bias=use_bias, dtype=dtype,
                    param_dtype=jnp.float32,
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), logical),
                    bias_init=nn.with_logical_partitioning(
                        nn.initializers.zeros_init(), (logical[-1],)),
                    name=name)


def layer_norm(eps, dtype, name):
    """Zoo-standard LayerNorm (fp32 scale+bias, 'embed' logical axis)."""
    import flax.linen as nn
    return nn.LayerNorm(epsilon=eps, dtype=dtype, param_dtype=jnp.float32,
                        scale_init=nn.with_logical_partitioning(
                            nn.initializers.ones_init(), ("embed",)),
                        bias_init=nn.with_logical_partitioning(
                            nn.initializers.zeros_init(), ("embed",)),
                        name=name)


def collect_router_metrics(mut) -> dict:
    """Per-layer router telemetry out of a model apply's mutated 'metrics'
    collection: the MoE layers sow per-expert load and drop fractions
    (moe/layer.py), which nn.scan stacks to (L, E)/(L,) per model. Returned
    as plain aux-dict entries so the engine's MetricsState carries them to
    the host with the loss."""
    metrics = mut.get("metrics", {}) if hasattr(mut, "get") else {}
    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(metrics)
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "router_load" in keys:
            out["router_load"] = leaf
        elif "router_drop" in keys:
            out["router_drop"] = leaf
    return out


def make_causal_loss_fn(model):
    """Standard engine loss_fn for a causal-LM zoo model: shift labels when
    the batch doesn't carry them."""
    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = shift_labels(ids)
        return model.apply({"params": params}, ids, labels=labels)
    return loss_fn


def abstract_specs(model, rng=None, seq_len: int = 8):
    """The partition specs of `model`'s parameter tree, from shapes alone."""
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    variables = jax.eval_shape(model.init, rng,
                               jnp.zeros((1, seq_len), jnp.int32))
    return extract_params_and_specs(variables)[1]


def materialize(model, rng=None, seq_len: int = 8, param_dtype=None):
    """`model`'s whole parameter tree on the device from the seed, ONE jitted
    call; `param_dtype` casts inside it (a serving tree's float32 form beside
    its bf16 copy fits no chip at the benchmark's sizes)."""
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ids = jnp.zeros((1, seq_len), jnp.int32)

    def init_fn(rng):
        raw, _ = extract_params_and_specs(model.init(rng, ids))
        if param_dtype is not None:
            raw = jax.tree_util.tree_map(
                lambda x: x.astype(param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, raw)
        return raw
    return jax.jit(init_fn)(rng)


# ---------------------------------------------------------------- pipeline
def apply_ln(sub_params, h, eps, dtype):
    """Apply a flax LayerNorm given its param subtree — pipeline head/embed
    fns reuse the module math instead of hand-rolling it."""
    import flax.linen as nn
    return nn.LayerNorm(epsilon=eps, dtype=dtype,
                        param_dtype=jnp.float32).apply({"params": sub_params}, h)


def apply_rms(sub_params, h, eps, dtype):
    from deepspeed_tpu.models.llama import RMSNorm
    return RMSNorm(eps, dtype).apply({"params": sub_params}, h)


def make_chunk_fn(block_cls, cfg, moe_aux_coef=None):
    """Pipeline stage body shared by the zoo (see
    `models/llama.py:llama_pipeline_fns`): scan `block_cls` over the stage's
    local layer stack, rematting per block like the dp path. With
    `moe_aux_coef`, blocks are applied with a mutable `aux_loss` collection
    and the chunk returns `(y, coef * sum(l_aux))` for the pipeline engine's
    aux accumulator (gating runs rng-free — deterministic — in the rotation;
    the dp parity partner must also run without a gating rng)."""
    from deepspeed_tpu.models.llama import _remat_policy

    def chunk_fn(local_layers, x, aux):
        if moe_aux_coef is None:
            def body(h, layer_params):
                h, _ = block_cls(cfg).apply({"params": layer_params}, h, aux)
                return h, None
        else:
            def body(carry, layer_params):
                h, acc = carry
                (h, _), mut = block_cls(cfg).apply(
                    {"params": layer_params}, h, aux, mutable=["aux_loss"])
                l = jax.tree_util.tree_reduce(
                    lambda a, b: a + jnp.sum(b), mut.get("aux_loss", {}), 0.0)
                return (h, acc + l), None
        if getattr(cfg, "remat", False):
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=_remat_policy(getattr(cfg, "remat_policy", "nothing")))
        if moe_aux_coef is None:
            return jax.lax.scan(body, x, local_layers)[0]
        # runs inside the pipeline's manual region — the accumulator must be
        # born pipe-varying or the scan carry types mismatch
        acc0 = jax.lax.pcast(jnp.zeros((), jnp.float32), ("pipe",),
                             to="varying")
        (y, acc), _ = jax.lax.scan(body, (x, acc0), local_layers)
        return y, jnp.float32(moe_aux_coef) * acc
    return chunk_fn
