"""DeepSeek-V3.2's language model (HF `deepseek_v32` config keys): LATENT
attention (MLA) whose every query attends only the `index_topk` cached rows
a small learned INDEXER picks for it (DeepSeek Sparse Attention), over
sigmoid-routed SwiGLU experts. Every layer is `h += Attn(RMSNorm(h)); h +=
FFN(RMSNorm(h))`; for a token t with `u = RMSNorm(h)`:

(the query's compression, the cached row, absorption and a chunk's write are
`models/latent.py`'s, shared with `models/openpangu.py`)

- query compression: `cq = RMSNorm(u W_qa)` (`q_lora_rank`); `q = cq W_qb`,
  H heads of `[q_nope (128) | q_rope (64)]`, rotary on the 64;
- the cached row: `[ckv | kr] = u W_kva` (512 | 64), `c = RMSNorm(ckv)`, `kr
  = rope(kr)`: ONE row `[c | kr]` of 576 a token that all heads share; a
  head's key is `[c W_uk^h | kr]` and its value `c W_uv^h`;
- the indexer, fed by the SAME `cq`: `qI[t, j] = cq_t W_Iq` (`index_n_heads`
  heads of `index_head_dim`), `kI[s] = LayerNorm(u_s W_Ik)` (ONE key head),
  rotary on the first 64 of both, `w[t] = u_t W_Iw`;

      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t, float32

  `S_t` = the `index_topk` positions s <= t of largest `I[t, s]` (every s <=
  t while t < index_topk; ties to the lower position);
- `o[t, h] = (sum_{s in S_t} softmax_{s in S_t}((q_nope_h W_uk^h . c_s +
  q_rope_h . kr_s) * 192^-0.5 * m^2) c_s) W_uv^h`, the same `S_t` for every
  head, then `W_o`. The rotary is YaRN's (`rope_scaling`:
  `ops.attention.yarn_inv_freq`) and `m = yarn_mscale(factor,
  mscale_all_dim)` the temperature that goes with it;
- the FFN is a dense SwiGLU for the first `first_k_dense_replace` layers and
  after them `router_experts` sigmoid-routed SwiGLU experts (top 8, the
  choice limited to `topk_group` of `n_group` groups, a selection bias,
  weights over their sum times `routed_scaling_factor`) beside one shared
  expert: `moe/layer.MoE` as `hybrid.held_experts` builds it.

then a final RMSNorm and an untied head. The multi-token-prediction block
(`num_nextn_predict_layers`) is a drafter of its own and is not built.

THE CACHE (`make_cache`; `inference/kv_cache.HybridCache`): `latent`, the
rows `[c | kr]` of every layer, `(L, B, 1, M, 576)`, and `index_keys`, one
key a token a layer, `(L, B, 1, M, 128)`; no K or V (`kv` None). A DECODE
step stages its token in both (`ops.attention.sparse_select`, the scores over
a row's live index keys and its choice; `latent_sparse_decode`, the ABSORBED
attention over the chosen rows) and lands each kind once after the layers. A
PREFILL walks the batch a ROW and a CHUNK of queries at a time through all
the layers (`hybrid.prefill_walk`): the chunk's rows and index
keys are written into the row's slabs first, then `latent_sparse_prefill`
scores, chooses and attends, in the EXPANDED form, against the slabs up to
each query's own position.

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(the grouped expert GEMM under a scan would copy a layer's experts every
step). The chip may hold a SHARE of the model: `num_experts` of
`router_experts` from `expert_offset` on (the limit by groups is computed
over every score, so a share may cut a group), a slice of the vocabulary,
and of the depth as many layers as `num_hidden_layers` says
(`perfbench/configs/deepseek-v3.2-l5-ep16.json` has the deployment; its
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
class YarnScaling:
    """`rope_scaling` of type `yarn`, as `ops.attention.rope_cos_sin` reads
    it."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class DeepseekSparseConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
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
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = YarnScaling()
    # the indexer
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
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
    n_group: int = 8
    topk_group: int = 4
    # seeded router: the scale of the selection bias drawn at init (a zero
    # one would hide a dropped term; `models/nemotron_h.py` has the readings)
    router_bias_scale: float = 0.01
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    dtype: Any = jnp.bfloat16
    dispatch_impl: str = "auto"

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
        """`qk_head_dim^-0.5`, times the square of YaRN's temperature where
        the rotary is scaled."""
        from deepspeed_tpu.ops.attention import yarn_mscale
        rs = self.rope_scaling
        m = 1.0 if rs is None else yarn_mscale(rs.factor, rs.mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    # the slots a row of the cache is given for a length (whole tiles of
    # the choice's widest block)
    cache_slots = staticmethod(latent.cache_slots)

    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """Both kinds this family holds (`capacity_scan.kv_cache_bytes` sums
        them), as `make_cache` holds them (`cache_slots`): the latent rows
        in K and V's place and the index keys beside them, a whole lane row
        a key (nothing is padded)."""
        from deepspeed_tpu.inference.kv_cache import LatentCache
        of = lambda width: LatentCache.nbytes(  # noqa: E731
            self.num_hidden_layers, batch, self.cache_slots(max_len), width,
            dtype or self.dtype)
        return {"latent_kv_bytes": of(self.latent_width),
                "index_kv_bytes": of(self.index_head_dim)}


class SparseLatentAttention(nn.Module):
    cfg: DeepseekSparseConfig

    @nn.compact
    def __call__(self, x, cache=None, slot=None, row=None):
        """x (B, S, hidden). `cache` None: a plain causal pass over the
        tokens themselves. With the model's `HybridCache` and this layer's
        `slot`: S == 1 is a decode step of every row, its token staged,
        returning (out, (latent row, index key) of the token); S > 1 is a
        CHUNK of sequence `row` alone (B == 1) from that row's cursor on,
        written into the slabs and attended against them, returning (out,
        cache)."""
        from deepspeed_tpu.ops import attention as ops
        from deepspeed_tpu.ops.pallas import mla_sparse as ms
        from deepspeed_tpu.ops.pallas import sparse_select as ss
        cfg = self.cfg
        nh, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.v_head_dim)
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        b, s, _ = x.shape
        start = latent.chunk_start(cache, b, s, row)
        cq, q_nope, q_rope, lat, w_kvb, positions, rotated = latent.project(
            self, x, start, cfg.rope_scaling)
        # the indexer's queries come out of the query's own compression
        q_i = _dense(hi * di, ("embed", "heads"), cfg.dtype,
                     "index_q_proj")(cq).reshape(b, s, hi, di)
        k_i = nn.LayerNorm(epsilon=hybrid.INDEX_NORM_EPS, dtype=cfg.dtype,
                           param_dtype=F32, name="index_k_norm")(
            _dense(di, ("embed", None), cfg.dtype, "index_k_proj")(x))
        w = _dense(hi, ("embed", None), cfg.dtype,
                   "index_w_proj")(x).astype(F32)               # (B, S, Hi)
        q_i, k_i = rotated(q_i, 0), rotated(k_i[:, :, None], 0)[:, :, 0]
        scale = cfg.softmax_scale

        made = None
        if cache is None:
            def one(q_nope, q_rope, q_i, w, lat, k_i):
                bias, kept = ss.choice_plain(q_i, w, k_i, jnp.arange(s),
                                             cfg.index_topk)
                return ms.mla_sparse_attention_plain(
                    q_nope, q_rope, w_kvb, bias, lat, scale), kept
            o, kept = jax.vmap(one)(q_nope, q_rope, q_i, w, lat, k_i)
        elif s == 1:
            lengths = cache.index + 1
            bias, kept = ops.sparse_select(
                q_i[:, 0], w[:, 0], cache.index_keys.c.replace(layer=slot),
                lengths, cfg.index_topk, k_i[:, 0])
            o_lat = ops.latent_sparse_decode(
                latent.absorbed(q_nope[:, 0], w_kvb), q_rope[:, 0],
                cache.latent.c.replace(layer=slot), lengths, bias, kept,
                cfg.index_topk, scale, lat[:, 0])
            o = latent.through_values(o_lat, w_kvb, dn, cfg.dtype)[:, None]
            made = (lat[:, 0], k_i[:, 0])
        else:
            made = cache = latent.write_chunk(
                cache, slot, row, start[0], latent=lat[0], index_keys=k_i[0])
            o, kept = ops.latent_sparse_prefill(
                q_nope[0], q_rope[0], w_kvb, q_i[0], w[0],
                cache.latent.c.replace(layer=slot),
                cache.index_keys.c.replace(layer=slot), row, start[0],
                cfg.index_topk, scale)
            o = o[None]
        # what a dense read would walk (every query's positions up to its
        # own) and what the selection DID walk: the slots the choice kept, as
        # the kernel counted the zeros of the bias it wrote
        # (`models/keye_sparse.py`)
        for name, value in (("kv_positions_live", positions + 1),
                            ("kv_positions_selected", kept)):
            self.sow("counters", name, jnp.sum(value, dtype=jnp.int32),
                     init_fn=lambda: jnp.zeros([], jnp.int32),
                     reduce_fn=lambda a, b_: a + b_)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj")(o.astype(cfg.dtype).reshape(b, s, nh * dv))
        return out, made


class Layers(nn.Module):
    """The walk over the layers: `layer_<i>` the attention, `layer_<i>_mlp`
    the dense FFN or the experts, each behind its norm."""
    cfg: DeepseekSparseConfig

    @nn.compact
    def __call__(self, h, cache=None, row=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        staged = []     # a decode step's new (latent row, index key) a layer
        for i in range(cfg.num_hidden_layers):
            out, made = SparseLatentAttention(cfg, name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(h), cache, i, row)
            if isinstance(made, tuple):
                staged.append(made)
            elif made is not None:
                cache = made
            h = h + out
            x = norm(f"layer_{i}_mlp_norm")(h)
            if i < cfg.first_k_dense_replace:
                h = h + hybrid.DenseFFN(cfg, name=f"layer_{i}_mlp")(x)
            else:
                h = h + hybrid.held_experts(
                    cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                    activation="silu", score_fn="sigmoid",
                    shared=cfg.moe_shared_expert_intermediate_size)(
                        x, train=False)
        if staged:      # the step's one write a kind, every layer's token
            lat, k_i = (jnp.stack(t) for t in zip(*staged))
            cache = cache.replace(latent=cache.latent.land(lat),
                                  index_keys=cache.index_keys.land(k_i))
        return h, cache


class DeepseekSparseForCausalLM(nn.Module):
    cfg: DeepseekSparseConfig
    # what the layers count inside a serving program, summed over the call by
    # the engine (`serving` event)
    program_counters = hybrid.EXPERT_COUNTERS + (
        "kv_positions_live", "kv_positions_selected")

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None):
        return hybrid.causal_lm(self, Layers, input_ids, labels, cache,
                                eps=self.cfg.rms_norm_eps,
                                prefill_chunk=PREFILL_CHUNK)

    def make_cache(self, batch: int, max_len: int, dtype: Any = None,
                   quantized: bool = False):
        """The cache a serving program carries for `batch` sequences of up to
        `max_len` positions (`cfg.cache_slots` of them): every layer's latent
        rows and, beside them, its index keys; no K or V."""
        from deepspeed_tpu.inference.kv_cache import HybridCache, LatentCache
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        make = lambda width: LatentCache.create(  # noqa: E731
            cfg.num_hidden_layers, batch, cfg.cache_slots(max_len), width,
            dtype=dtype or cfg.dtype)
        return HybridCache(kv=None, latent=make(cfg.latent_width),
                           index_keys=make(cfg.index_head_dim))


init_params_and_specs, materialize_params, deepseek_sparse_loss_fn = \
    hybrid.entry_points(DeepseekSparseForCausalLM)
