"""Llama model family (Llama-2/3-style decoder; the flagship training model).

Fills the slot of the reference's model implementations for llama
(`module_inject/containers/llama.py`, `inference/v2/model_implementations/
llama_v2`): RMSNorm + RoPE + GQA attention + SwiGLU MLP, pre-norm decoder.

TPU-first design:
- layers run under `nn.scan` (one compiled block body regardless of depth) +
  optional `nn.remat` (activation checkpointing, reference
  `runtime/activation_checkpointing/checkpointing.py`);
- parameters carry logical axis names; tensor parallelism = the
  'heads'/'mlp'→'model' mapping in `utils/partitioning.DEFAULT_RULES`
  (column-parallel qkv/up, row-parallel out/down — AutoTP's slicing,
  declaratively);
- sequence parallelism via `sequence.layer.DistributedAttention` (Ulysses
  all-to-all) around the attention core;
- attention core is the Pallas flash kernel on TPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import causal_lm_loss
from deepspeed_tpu.ops.attention import apply_rotary_emb, attention, rope_cos_sin
from deepspeed_tpu.runtime.domino.transformer import (
    TP_EXCHANGE, DominoTransformerLayer, exchange_layout, forward_hold, hold_until, land_dw,
    merge_rows, parallel_products, split_rows)
from deepspeed_tpu.sequence.layer import DistributedAttention
from deepspeed_tpu.utils.partitioning import BATCH_AXES, shard_along

Dtype = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    remat: bool = True
    # jax.checkpoint policy: 'nothing' recomputes the whole block (minimum
    # memory); 'dots' saves matmul outputs (no recompute of MXU work — faster
    # when HBM headroom allows — reference activation_checkpointing's
    # partial-checkpointing knobs).
    remat_policy: str = "nothing"
    attn_impl: str = "auto"
    # When set, training loss runs through the sequence-chunked cross entropy
    # (sequence/cross_entropy.py) and the full (B, S, V) logits are never
    # materialized — required for 128k+ context (BASELINE config 5).
    loss_chunk_size: Optional[int] = None
    # FPDT chunked FFN (reference sequence/fpdt_layer.py:1056): the MLP runs
    # per sequence chunk so its intermediates — ~6·S·I bytes live at once
    # through fwd+bwd, the 128k-ctx OOM after everything else is
    # offloaded/blockwise — peak at chunk granularity instead of S.
    mlp_chunk_size: Optional[int] = None
    # Family variants that share the llama decoder skeleton: Qwen2 adds bias
    # on the q/k/v projections; Mistral bands attention to a sliding window.
    attention_qkv_bias: bool = False
    # InternLM-style bias on the o projection too (HF internlm `bias`)
    attention_o_bias: bool = False
    sliding_window: Optional[int] = None
    # Explicit per-head width (HF configs with decoupled head_dim; also set
    # by structural head pruning, which shrinks the head COUNT while each
    # surviving head keeps its width — compression/structured.py).
    head_dim_override: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads


PRESETS = {
    "llama3-8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                      num_hidden_layers=32, num_attention_heads=32,
                      num_key_value_heads=8, max_position_embeddings=8192,
                      rope_theta=500000.0),
    "llama2-7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                      num_hidden_layers=32, num_attention_heads=32,
                      num_key_value_heads=32, max_position_embeddings=4096),
    "llama-1b": dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                     num_hidden_layers=22, num_attention_heads=32,
                     num_key_value_heads=4, max_position_embeddings=4096),
    "llama-tiny": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128,
                       remat=False),
}


def llama_config(name: str, **overrides) -> LlamaConfig:
    return LlamaConfig(**{**PRESETS[name], **overrides})


def _host_offload_policy(*extra_names: str):
    """save flash_lse in HBM, offload the residual names (+ any extras)
    to pinned host — the single source of truth for the host_offload
    policy family's name lists."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=["flash_lse"],
        names_which_can_be_offloaded=[
            "fpdt_residual", "flash_resid", *extra_names],
        offload_src="device", offload_dst="pinned_host")


def _remat_policy(name: str):
    if name == "dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(TP_EXCHANGE))
    if name in ("checkpoint_dots", "checkpoint_dots_gmm"):
        # dot results plus the flash forward kernel's out and logsumexp
        # (ops/pallas/flash_attention.py names them): a pallas_call is no
        # dot, so without the names a layer's backward ran the forward
        # kernel a second time. Measured on one v5e (PR 44, Qwen2.5-0.5B at
        # 2 x 2048, seed 4400011001, parent and change in one call): the
        # kernel runs 96 times a step and not 192, the step takes 589.5 ms
        # and not 615.5, every loss is the same bit for bit; the "18x
        # slower" of round 4 is not reproduced. A graph without the flash
        # kernel holds no such name and the policy is checkpoint_dots.
        # ...and a row-parallel product's summed output where the layers'
        # tensor-parallel reductions are exchanges in a manual region
        # (runtime/domino/transformer.py): a `shard_map` equation is no dot
        # either, and without the name the backward ran `o_proj`,
        # `down_proj` AND their exchanges again.
        names = ["flash_resid", "flash_lse", TP_EXCHANGE]
        if name == "checkpoint_dots_gmm":
            # ...and the named grouped-GEMM outputs (moe/layer.py Experts
            # grouped path): megablox gmm is a Pallas call too, and without
            # the name the backward recomputes all three grouped GEMMs per
            # MoE layer. MoE configurations ask for it by this name.
            names.append("moe_gmm")
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(*names))
    if name == "host_offload":
        # FPDT's host-offload tier (reference `sequence/fpdt_layer.py:510`
        # `_FPDTGPUOffloadingAttentionImpl_` / `SequenceChunk:462` CPU↔GPU
        # staging): the per-layer residual-stream checkpoints — the ONLY
        # live activations under whole-block remat, but at 128k ctx ~6 GB
        # across a 24-layer stack — are saved to pinned host memory instead
        # of HBM; XLA schedules the D2H/H2D streams around the block
        # compute. Blocks tag the tensor via checkpoint_name below.
        #
        # 'flash_resid' (ops/pallas/flash_attention.py fwd residuals: the
        # attention output + logsumexp) offloads too: without it, backward
        # re-runs the flash FORWARD kernel per layer just to regenerate lse
        # — at 128k that recompute is ~22% of total attention FLOPs (~6 s
        # of a 36 s step on v5e), far more than the ~0.3 GB/layer of PCIe
        # the offload costs.
        return _host_offload_policy()
    if name == "host_offload_dense":
        # host_offload + the post-rotary q/k/v and the mid-block residual:
        # backward then skips the qkv-GEMM, rotary and o-projection
        # recompute of whole-block remat; ~1 GB/layer extra PCIe.
        # MEASURED LOSING on 1×v5e (r5, 470m @ 32k): 48.1% → 39.9% MFU —
        # the staging does NOT overlap at this volume; PCIe is the
        # bottleneck, not the recompute. Kept for large-HBM parts (v5p)
        # where these names could be saved in HBM via save_names_hbm-style
        # policies instead.
        return _host_offload_policy("attn_qkv", "resid_mid")
    if name == "host_offload_dense_mlp":
        # ...plus the gate/up projections — the FULL dense re-fwd is gone,
        # at ~2 GB/layer more PCIe (the (S, F) pair). MEASURED LOSING
        # HARD on 1×v5e (r5, 470m @ 32k): 48.1% → 23.8% MFU (2.2× slower;
        # see host_offload_dense note).
        return _host_offload_policy("attn_qkv", "resid_mid", "mlp_gate_up")
    if name == "save_names_hbm":
        # whole-block remat with BOTH named residuals saved in HBM — no
        # PCIe staging at all; fits mid-range contexts (≤64k on v5e with
        # host-parked optimizer state)
        return jax.checkpoint_policies.save_only_these_names(
            "flash_lse", "flash_resid", "fpdt_residual")
    if name == "host_offload_flash_hbm":
        # host_offload with the flash residual (attn out) kept in HBM —
        # halves the PCIe staging volume at the cost of ~S·d·2B per layer
        # of HBM; viable when the optimizer state is parked on host
        # (offload_optimizer cpu) so HBM has the headroom.
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=["flash_lse", "flash_resid"],
            names_which_can_be_offloaded=["fpdt_residual"],
            offload_src="device", offload_dst="pinned_host")
    return jax.checkpoint_policies.nothing_saveable


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)), (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return ((x32 * jax.lax.rsqrt(var + self.eps)) * w).astype(self.dtype)


def _dense(features, logical, dtype, name, use_bias: bool = False,
           dot_general=None):
    return nn.Dense(features, use_bias=use_bias, dtype=dtype,
                    param_dtype=jnp.float32, dot_general=dot_general,
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), logical),
                    bias_init=nn.with_logical_partitioning(
                        nn.initializers.zeros_init(), (logical[-1],)),
                    name=name)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    # an `ExchangeLayout` where the layer's tensor-parallel reductions are
    # exchanges (`_exchange_layout`), else None
    tp: Any = None

    @nn.compact
    def __call__(self, h, cos, sin, kv=None, mask=None, index=None,
                 landings=None, **tie):
        """`landings`: the kernels' `dW` carriers where their reductions
        over the batch axes are exchanges (`_dw_landings`), by name.
        `tie`: one array by the keyword `parallel_products` knows it by
        (`DominoTransformerLayer` orders its half-batches' backward phases
        with it); then `(out, tied)` is returned."""
        cfg = self.cfg
        hd, nh, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        qb = cfg.attention_qkv_bias  # Qwen2-style qkv bias (o_proj stays bias-free)
        b, s = h.shape[:2]
        column, row, tied = parallel_products(h, self.tp, landings, **tie)
        q = _dense(nh * hd, ("embed", "heads"), cfg.dtype, "q_proj", qb,
                   column("q_proj"))(h)
        k = _dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "k_proj", qb,
                   column("k_proj"))(h)
        v = _dense(nkv * hd, ("embed", "kv_heads"), cfg.dtype, "v_proj", qb,
                   column("v_proj"))(h)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        q = apply_rotary_emb(q, cos, sin)
        k = apply_rotary_emb(k, cos, sin)
        if kv is None:
            # post-rotary q/k/v are exactly what flash bwd consumes; the
            # 'host_offload_dense*' policies offload them so backward
            # skips the qkv-GEMM + rotary recompute (identity otherwise)
            from jax.ad_checkpoint import checkpoint_name
            q = checkpoint_name(q, "attn_qkv")
            k = checkpoint_name(k, "attn_qkv")
            v = checkpoint_name(v, "attn_qkv")

        if kv is not None:
            # Decode/prefill against the static KV cache: insert the S new
            # tokens at `index`, attend q over the whole cache under the
            # position mask (inference_context.h / transform.cu:727 analog).
            from deepspeed_tpu.inference.kv_cache import update_layer
            from deepspeed_tpu.ops.attention import cached_attention
            k_cache, v_cache = update_layer(kv[0], kv[1], k, v, index)
            # `window` tells the dispatcher the mask is banded over a
            # full-length cache, keeping the Pallas decode kernel (which
            # masks by a count of live slots) off that path
            ctx = cached_attention(q, k_cache, v_cache, index, mask,
                                   impl=cfg.attn_impl,
                                   window=cfg.sliding_window)
            out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                         "o_proj", cfg.attention_o_bias)(
                ctx.reshape(b, s, nh * hd))
            return out, (k_cache, v_cache)

        if cfg.attn_impl == "ring":
            # context parallelism: KV chunks rotate the sequence ring; no
            # Ulysses head re-sharding (works for any head count)
            assert cfg.sliding_window is None, \
                "ring attention + sliding window not supported"
            from deepspeed_tpu.sequence.ring_attention import RingAttention
            ctx = RingAttention()(q, k, v)
        else:
            def core(q, k, v):
                return attention(q, k, v, causal=True, impl=cfg.attn_impl,
                                 window=cfg.sliding_window)

            ctx = DistributedAttention(core)(q, k, v)
        ctx = ctx.reshape(b, s, nh * hd)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj", cfg.attention_o_bias, row("o_proj"))(ctx)
        return out if tied is None else (out, tied)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig
    tp: Any = None      # as `LlamaAttention.tp`

    @nn.compact
    def __call__(self, h, hold=None, landings=None, **tie):
        """`landings` and `tie`: as `LlamaAttention`'s. `hold`: an array
        that no one may use before this FFN's activation stands
        (`hold_until`); it comes back beside the output as a tied one
        does, which is held the same way forward (`forward_hold`)."""
        cfg = self.cfg
        column, row, tied = parallel_products(h, self.tp, landings, **tie)
        gate_d = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg.dtype,
                        "gate_proj", dot_general=column("gate_proj"))
        up_d = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg.dtype,
                      "up_proj", dot_general=column("up_proj"))
        down_d = _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                        "down_proj", dot_general=row("down_proj"))
        from jax.ad_checkpoint import checkpoint_name

        def ffn(hc, hold=None, barrier=hold_until):
            # gate/up outputs are the S-proportional dot saves that OOM
            # HBM at long context — 'host_offload_dense_mlp' offloads the
            # named tensors instead so backward skips both GEMM recomputes
            g = checkpoint_name(gate_d(hc), "mlp_gate_up")
            u = checkpoint_name(up_d(hc), "mlp_gate_up")
            if hold is None:
                return down_d(nn.silu(g) * u)
            act, hold = barrier(nn.silu(g) * u, hold)
            return down_d(act), hold
        if tied is not None:
            # a tied array is held as well, forward only: the backward has
            # the tie, and the forward stays what `hold` made it
            return ffn(h, tied, forward_hold)
        if hold is not None:
            return ffn(h, hold)
        cs = cfg.mlp_chunk_size
        if not cs or h.shape[1] <= cs or h.shape[1] % cs:
            return ffn(h)
        # FPDT chunked FFN: static unroll over sequence chunks — the MLP is
        # positionwise, so this is exact; each chunk's (cs, I) intermediates
        # die before the next chunk's are born (fwd AND transposed bwd)
        outs = [ffn(hc) for hc in jnp.split(h, h.shape[1] // cs, axis=1)]
        return jnp.concatenate(outs, axis=1)


# a half-batch's attention, traced once for both halves
_SharedAttention = nn.jit(LlamaAttention)


def _exchange_layout(cfg: LlamaConfig, rows: int):
    """The layout under which a TRAINING layer walks `rows` as two
    half-batches with its two tensor-parallel reductions exchanged
    (`runtime/domino/transformer.exchange_layout`: read off the installed
    mesh, the rows and the widths `model` must divide), or None: then
    nothing is named and the partitioner's program is what it was."""
    if cfg.attn_impl == "ring" or cfg.mlp_chunk_size:
        return None
    return exchange_layout(rows, cfg.num_attention_heads,
                           cfg.num_key_value_heads, cfg.intermediate_size)


# Which product each of a layer's kernels enters (`column_parallel`,
# `row_parallel`), by child module and name.
_LAYER_KERNELS = {
    "self_attn": {"q_proj": "column", "k_proj": "column", "v_proj": "column",
                  "o_proj": "row"},
    "mlp": {"gate_proj": "column", "up_proj": "column", "down_proj": "row"}}


def _dw_landings(block: nn.Module, tp) -> dict:
    """`{child: {kernel name: carrier}}`: where a training layer's `dW`
    reductions over the batch axes are exchanges onto the shards the ZeRO
    plan gives the gradients' accumulators (`land_dw`: read off the plan of
    the step being traced, the layer's own place in the parameters' tree
    and the shapes). The block reads its children's kernels itself, once
    for both half-batches, so that their partial `dW` add up before ONE
    exchange a kernel. Empty where nothing is named: no layout, no plan, no
    parameters yet (`init`)."""
    if tp is None:
        return {}
    found = {}
    for child, uses in _LAYER_KERNELS.items():
        if not block.has_variable("params", child):
            return {}
        held = nn.meta.unbox(block.get_variable("params", child))
        found[child] = land_dw(
            tp, {name: (held[name]["kernel"].astype(block.cfg.dtype), product)
                 for name, product in uses.items()}, (*block.path, child))
    return found


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, h, cos_sin, kv=None):
        cfg = self.cfg
        if kv is not None:
            cos, sin, index, mask = cos_sin
            attn, new_kv = LlamaAttention(cfg, name="self_attn")(
                RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(h),
                cos, sin, kv=kv, mask=mask, index=index)
            h = h + attn
            h = h + LlamaMLP(cfg, name="mlp")(
                RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                        name="post_attention_layernorm")(h))
            return h, new_kv
        cos, sin = cos_sin
        # `h` is the rows, or a PAIR of half-batches where the layers'
        # tensor-parallel reductions are exchanges (`_exchange_layout`, cut
        # before the layer scan): each half's exchange then lies under the
        # other half's products (runtime/domino/transformer.py). Same
        # params (shared module instances), and a row's products do not
        # depend on the other rows.
        tp = (_exchange_layout(cfg, 2 * h[0].shape[0])
              if isinstance(h, tuple) else None)
        h = jax.tree_util.tree_map(
            lambda x: shard_along(x, BATCH_AXES, "sequence", None), h)
        # name the block-boundary residual so the 'host_offload' remat
        # policy can stage it to pinned host memory (no-op otherwise)
        from jax.ad_checkpoint import checkpoint_name
        h = checkpoint_name(h, "fpdt_residual")
        # (the two halves' attention is ONE traced function, `nn.jit`: it
        # keeps the step's tracing and lowering, which is set-up time, near
        # what one walk costs; the FFN's two calls differ by the hold, and
        # so do the attention's where the phases are `ordered`)
        attention = LlamaAttention if tp is None else _SharedAttention
        attn = attention(cfg, tp, name="self_attn")
        mlp = LlamaMLP(cfg, tp, name="mlp")
        landings = _dw_landings(self, tp)
        ordered = any(landings.values())
        layer = DominoTransformerLayer(
            lambda x, **tie: attn(x, cos, sin,
                                  landings=landings.get("self_attn"), **tie),
            lambda x, *hold, **tie: mlp(x, *hold,
                                        landings=landings.get("mlp"), **tie),
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm"),
            RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm"),
            # mid-block residual: saving it lets backward rebuild mlp_normed
            # with one cheap RMSNorm instead of re-running the o-projection
            mid=lambda x: checkpoint_name(x, "resid_mid"), ordered=ordered)
        return layer(h), None


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, cache=None):
        cfg = self.cfg
        embed = self.param("embed_tokens", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        h = jnp.take(embed.astype(cfg.dtype), input_ids, axis=0)
        h = shard_along(h, BATCH_AXES, "sequence", None)

        if cache is not None:
            # Cached decode/prefill path (reference inference/engine.py:579):
            # same params, scan carries KV through the stacked layer cache.
            from deepspeed_tpu.inference.kv_cache import (
                PagedKVCache, decode_mask, scan_cache_layers)
            b, s = input_ids.shape
            index = cache.index  # (B,) per-sequence cursors
            positions = index[:, None] + jnp.arange(s)[None, :]  # (B, S)
            cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                    cfg.dtype)
            mask = decode_mask(positions, cache.max_len,
                               window=cfg.sliding_window)
            if isinstance(cache, PagedKVCache) or cache.stacked:
                # the pools / the stacked dense cache stay whole: layers
                # address them by index
                h, new_cache = scan_cache_layers(
                    functools.partial(LlamaBlock, cfg), h,
                    (cos, sin, index, mask), cache, s, name="layers",
                    variable_axes={"params": 0}, split_rngs={"params": True},
                    metadata_params={nn.meta.PARTITION_NAME: "layers"})
            else:
                # the per-layer view (int8 dense caches, the v2 slot
                # layout, docs/kv_cache.md): a layer's cache is a scanned
                # input and output
                ScanBlocks = nn.scan(
                    LlamaBlock, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast, 0), out_axes=0,
                    length=cfg.num_hidden_layers,
                    metadata_params={nn.meta.PARTITION_NAME: "layers"})
                h, (k_new, v_new) = ScanBlocks(cfg, name="layers")(
                    h, (cos, sin, index, mask), (cache.k, cache.v))
                new_cache = cache.replace(k=k_new, v=v_new, index=index + s)
            h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(h)
            logits = self._lm_head(h, embed)
            return logits, new_cache

        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, cfg.dtype)

        block = LlamaBlock
        if cfg.remat:
            block = nn.remat(block, prevent_cse=False,
                             policy=_remat_policy(cfg.remat_policy))
        ScanBlocks = nn.scan(
            block, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=nn.broadcast, length=cfg.num_hidden_layers,
            metadata_params={nn.meta.PARTITION_NAME: "layers"})
        tp = _exchange_layout(cfg, h.shape[0])
        if tp is not None:
            h = split_rows(h, tp)
        h, _ = ScanBlocks(cfg, name="layers")(h, (cos, sin))
        if tp is not None:
            h = merge_rows(h, tp)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(h)

        if labels is not None and cfg.loss_chunk_size:
            from deepspeed_tpu.sequence.cross_entropy import (
                chunked_softmax_cross_entropy)
            if cfg.tie_word_embeddings:
                w, tied = embed.astype(cfg.dtype), True
            else:
                w = self.param("lm_head", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", "vocab")),
                    (cfg.hidden_size, cfg.vocab_size), jnp.float32)
                w, tied = w.astype(cfg.dtype), False
            loss = chunked_softmax_cross_entropy(
                h, w, labels, chunk_size=cfg.loss_chunk_size, tied_embedding=tied)
            return loss, {}

        logits = self._lm_head(h, embed)
        if labels is None:
            return logits
        return causal_lm_loss(logits, input_ids, labels), {}

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a v1 serving program carries for `batch` sequences of
        up to `max_len` positions: the STACKED view, which the cached scan
        above addresses by layer and never moves (docs/kv_cache.md). An
        int8 cache keeps the per-layer view."""
        from deepspeed_tpu.inference.kv_cache import KVCache
        cfg = self.cfg
        dims = (cfg.num_hidden_layers, batch, max_len,
                cfg.num_key_value_heads, cfg.head_dim)
        if quantized:
            return KVCache.create(*dims, dtype=dtype or cfg.dtype,
                                  quantized=True)
        return KVCache.create_stacked(*dims, dtype=dtype or cfg.dtype)

    def _lm_head(self, h, embed):
        cfg = self.cfg
        if not cfg.tie_word_embeddings:
            lm_head = self.param("lm_head", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "vocab")),
                (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        # `head`: the scope the program map reads (docs/telemetry.md)
        with jax.named_scope("head"):
            if cfg.tie_word_embeddings:
                return jnp.einsum("bsd,vd->bsv", h, embed.astype(cfg.dtype))
            return h @ lm_head.astype(cfg.dtype)


def init_params_and_specs(cfg: LlamaConfig, rng=None, seq_len: int = 8):
    """Abstract-init → (param ShapeDtypeStructs or arrays, PartitionSpec tree)."""
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    model = LlamaForCausalLM(cfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ids = jnp.zeros((1, seq_len), jnp.int32)
    variables = jax.eval_shape(model.init, rng, ids)
    _, specs = extract_params_and_specs(variables)
    return model, specs


def materialize_params(cfg: LlamaConfig, rng=None, seq_len: int = 8,
                       shardings=None, param_dtype=None):
    """Initialize real parameters (optionally directly into shardings).

    The modules declare fp32 params (the training master form). A serving
    caller passes `param_dtype` to get the tree in that dtype from the SAME
    program, the cast fused into each initializer — at 3B the fp32 tree
    (12.4 GB) beside its bf16 copy does not fit one 16 GB chip."""
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    model = LlamaForCausalLM(cfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ids = jnp.zeros((1, seq_len), jnp.int32)

    def init_fn(rng):
        variables = model.init(rng, ids)
        raw, _ = extract_params_and_specs(variables)
        if param_dtype is not None:
            raw = jax.tree_util.tree_map(
                lambda x: x.astype(param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, raw)
        return raw

    if shardings is not None:
        return model, jax.jit(init_fn, out_shardings=shardings)(rng)
    # Always trace under jit: activation sharding constraints are lenient
    # inside jit (padding), but error eagerly outside it when a topology is
    # installed whose data axis doesn't divide the tiny trace batch.
    return model, jax.jit(init_fn)(rng)


def llama_pipeline_fns(model: LlamaForCausalLM):
    """Functional (embed, aux, chunk, head) pieces for the pipeline engine.

    The block stack stays the `LlamaBlock` module (applied per layer inside
    the stage rotation); embed/head replicate `__call__`'s exact math on the
    raw param tree so pp=1 and pp>1 trajectories agree bit-for-bit.
    """
    cfg = model.cfg

    def embed_fn(params, ids):
        return jnp.take(params["embed_tokens"].astype(cfg.dtype), ids, axis=0)

    def aux_fn(params, ids):
        positions = jnp.arange(ids.shape[-1])
        return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, cfg.dtype)

    def chunk_fn(local_layers, x, aux):
        def body(h, layer_params):
            h, _ = LlamaBlock(cfg).apply({"params": layer_params}, h, aux)
            return h, None
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False,
                                  policy=_remat_policy(cfg.remat_policy))
        return jax.lax.scan(body, x, local_layers)[0]

    def head_fn(params, h, ids, labels):
        w = params["norm"]["weight"]
        x32 = h.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        h = ((x32 * jax.lax.rsqrt(var + cfg.rms_norm_eps)) * w).astype(cfg.dtype)
        if cfg.tie_word_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h,
                                params["embed_tokens"].astype(cfg.dtype))
        else:
            logits = h @ params["lm_head"].astype(cfg.dtype)
        return causal_lm_loss(logits, ids, labels)

    return embed_fn, aux_fn, chunk_fn, head_fn, "layers"


def llama_loss_fn(model: LlamaForCausalLM):
    from deepspeed_tpu.models.common import shift_labels

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = shift_labels(ids)
        return model.apply({"params": params}, ids, labels=labels)
    return loss_fn
