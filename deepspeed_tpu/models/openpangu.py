"""openPangu-Ultra-MoE (HF `pangu_ultra_moe` config keys): SANDWICH-normed
layers of DENSE latent attention (MLA: every query attends every cached row
up to its own, at all heads) over ungrouped sigmoid-routed SwiGLU experts.
With each `N` an RMSNorm of its own weight (eps `rms_norm_eps`), a layer is

    a = Attn(N_in(x));          x1 = x  + N_post_attn(a)
    f = FFN(N_pre_mlp(x1));     x2 = x1 + N_post_mlp(f)

FOUR norms a layer: the post norms act on the sub-layer's OUTPUT before it
joins the residual stream (`sandwich_norm`). For a token with `u = N_in(x)`:

- the query's compression and the one cached row `[c | kr]` a token are
  `models/latent.py`'s (`q_lora_rank` 1536, `kv_lora_rank` 512, heads of
  `[128 | 64]`, rotary at `rope_theta` with no scaling on the 64);
- `o[t, h] = (sum_{s <= t} softmax_s((q_nope_h W_uk^h . c_s + q_rope_h .
  kr_s) * 192^-0.5) c_s) W_uv^h`, then `W_o`; no bias anywhere;
- the FFN is a dense SwiGLU for the first `first_k_dense_replace` layers and
  after them `router_experts` sigmoid-scored SwiGLU experts: the
  `num_experts_per_tok` largest of ALL the scores at once (no groups, no
  selection bias), weights over their sum times `routed_scaling_factor`,
  beside one shared expert, unweighted (`moe/layer.MoE` as
  `hybrid.held_experts` builds it, `n_group` 1);

then a final RMSNorm and an untied head. The multi-token-prediction block
(`num_nextn_predict_layers`) is a drafter of its own and is not built.

THE CACHE (`make_cache`; `inference/kv_cache.HybridCache`): `latent` alone,
the rows `[c | kr]` of every layer, `(L, B, 1, M, 576)`; no K, V or index
keys. A DECODE step is the ABSORBED form over the row's whole live slab
(`ops.attention.latent_decode` -> `ops/pallas/mla.mla_latent_decode`, its
token staged and landed once after the layers). A PREFILL walks the batch a
ROW and a CHUNK of queries at a time through all the layers
(`hybrid.prefill_walk`): the chunk's rows are written into the
row's slab first, then `ops.attention.latent_dense_prefill` attends, in the
EXPANDED form, every block of the slab up to the chunk's own diagonal
(`ops/pallas/mla_sparse.mla_dense_prefill`: no chunk x cache bias exists).

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(the grouped expert GEMM under a scan would copy a layer's experts every
step). The chip may hold a SHARE of the model: `num_experts` of
`router_experts` from `expert_offset` on, a slice of the vocabulary, and of
the depth as many layers as `num_hidden_layers` says
(`perfbench/configs/openpangu-ultra-l5-ep16.json` has the deployment; its
`assumed` lists what the published config does not settle).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid, latent
from deepspeed_tpu.models.llama import RMSNorm, _dense

F32 = jnp.float32
# Queries of one row that walk the layers together in a prefill
# (`models/keye_sparse.py` has the readings its 2,048 was chosen by)
PREFILL_CHUNK = 2048


@dataclasses.dataclass(frozen=True)
class OpenPanguConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    intermediate_size: int = 18432
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    # latent attention
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    # experts: `num_experts` are HELD here, of the `router_experts` the router
    # scores (None: all of them are held), from `expert_offset` on
    num_experts: int = 256
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    dispatch_impl: str = "auto"

    # the family's ONE router, as `hybrid.held_experts` reads it: the best of
    # ALL the scores at once and no selection bias. Constants of the class,
    # not fields: no caller can ask for another form
    n_group = 1
    topk_group = 1
    router_bias_scale = None

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe_shared_expert_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    cache_slots = staticmethod(latent.cache_slots)

    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """The one kind this family holds, as `make_cache` holds it
        (`cache_slots`): the latent rows in K and V's place."""
        from deepspeed_tpu.inference.kv_cache import LatentCache
        return {"latent_kv_bytes": LatentCache.nbytes(
            self.num_hidden_layers, batch, self.cache_slots(max_len),
            self.latent_width, dtype or self.dtype)}


class DenseLatentAttention(nn.Module):
    cfg: OpenPanguConfig

    @nn.compact
    def __call__(self, x, cache=None, slot=None, row=None):
        """x (B, S, hidden). `cache` None: a plain causal pass over the
        tokens themselves. With the model's `HybridCache` and this layer's
        `slot`: S == 1 is a decode step of every row, its token staged,
        returning (out, the token's latent row); S > 1 is a CHUNK of
        sequence `row` alone (B == 1) from that row's cursor on, written
        into the slab and attended against it, returning (out, cache)."""
        from deepspeed_tpu.ops import attention as ops
        from deepspeed_tpu.ops.pallas import mla_sparse as ms
        cfg = self.cfg
        nh, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.v_head_dim)
        b, s, _ = x.shape
        start = latent.chunk_start(cache, b, s, row)
        _, q_nope, q_rope, lat, w_kvb, _, _ = latent.project(self, x, start)
        scale = cfg.softmax_scale

        made = None
        if cache is None:
            bias = ms.causal_bias(0, s, s)
            o = jax.vmap(lambda qn, qr, rows: ms.mla_sparse_attention_plain(
                qn, qr, w_kvb, bias, rows, scale))(q_nope, q_rope, lat)
        elif s == 1:
            o_lat = ops.latent_decode(
                latent.absorbed(q_nope[:, 0], w_kvb), q_rope[:, 0],
                cache.latent.c.replace(layer=slot), cache.index + 1, scale,
                new=lat[:, 0], slots=cache.index)
            o = latent.through_values(o_lat, w_kvb, dn, cfg.dtype)[:, None]
            made = lat[:, 0]
        else:
            made = cache = latent.write_chunk(cache, slot, row, start[0],
                                              latent=lat[0])
            o = ops.latent_dense_prefill(
                q_nope[0], q_rope[0], w_kvb,
                cache.latent.c.replace(layer=slot), row, start[0],
                scale)[None]
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj")(o.astype(cfg.dtype).reshape(b, s, nh * dv))
        return out, made


class Layers(nn.Module):
    """The walk over the layers: `layer_<i>` the attention between
    `layer_<i>_norm` and `layer_<i>_post_attn_norm`, `layer_<i>_mlp` the
    dense FFN or the experts between `layer_<i>_mlp_norm` and
    `layer_<i>_post_mlp_norm`."""
    cfg: OpenPanguConfig

    @nn.compact
    def __call__(self, h, cache=None, row=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        staged = []     # a decode step's new latent row a layer
        for i in range(cfg.num_hidden_layers):
            out, made = DenseLatentAttention(cfg, name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(h), cache, i, row)
            if cache is not None and h.shape[1] == 1:
                staged.append(made)
            elif made is not None:
                cache = made
            h = h + norm(f"layer_{i}_post_attn_norm")(out)
            x = norm(f"layer_{i}_mlp_norm")(h)
            if i < cfg.first_k_dense_replace:
                out = hybrid.DenseFFN(cfg, name=f"layer_{i}_mlp")(x)
            else:
                out = hybrid.held_experts(
                    cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                    activation="silu", score_fn="sigmoid",
                    shared=cfg.moe_shared_expert_intermediate_size)(
                        x, train=False)
            h = h + norm(f"layer_{i}_post_mlp_norm")(out)
        if staged:      # the step's one write, every layer's token
            cache = cache.replace(latent=cache.latent.land(jnp.stack(staged)))
        return h, cache


class OpenPanguForCausalLM(nn.Module):
    cfg: OpenPanguConfig
    # what the layers count inside a serving program, summed over the call by
    # the engine (`serving` event)
    program_counters = hybrid.EXPERT_COUNTERS

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        return hybrid.causal_lm(self, Layers, input_ids, labels, cache,
                                eps=self.cfg.rms_norm_eps,
                                prefill_chunk=PREFILL_CHUNK)

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions (`cfg.cache_slots` of them): every layer's latent
        rows; no K, V or index keys."""
        from deepspeed_tpu.inference.kv_cache import HybridCache, LatentCache
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        return HybridCache(kv=None, latent=LatentCache.create(
            cfg.num_hidden_layers, batch, cfg.cache_slots(max_len),
            cfg.latent_width, dtype=dtype or cfg.dtype))


init_params_and_specs, materialize_params, openpangu_loss_fn = \
    hybrid.entry_points(OpenPanguForCausalLM)
