"""Keye-VL-2.0's language model (HF `KeyeVL2` config keys; Kwai-Keye):
grouped-query attention whose every query attends only the `topk` cached
tokens a small learned INDEXER picks for it (DeepSeek-Sparse-Attention's
lightning indexer, on GQA instead of MLA), over softmax-routed SwiGLU
experts. Every layer is the same; for a token t with `u = RMSNorm(h)`:

- `q = u W_q` (H heads of D), `k = u W_k`, `v = u W_v` (Hkv heads); RMSNorm
  with a weight over each head's D of `q` and of `k`; rotary over all D at
  `rope_theta` (for text the three mrope position ids are equal and the
  sections collapse to plain rotary);
- the indexer, `Hi` heads of `Di` on ONE key head: `qI[t, j] = rope(u_t
  W_qI)[j]`, `kI[s] = rope(LayerNorm(u_s W_kI))`, `w[t] = u_t W_w`;

      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t, float32

  `S_t` = the `index_topk` positions s <= t of largest `I[t, s]` (every
  s <= t while t < index_topk; ties to the lower position, the set
  `jax.lax.top_k` gives);
- `o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)] /
  sqrt(D)) v[s, g(h)]`, the same `S_t` for every head; then `W_o`;
- `y = RMSNorm(h + attn)`; `p = softmax(y W_r)` over all `router_experts`;
  the `num_experts_per_tok` largest, weights `p_e / sum of those taken`;
  expert e is `W_d(silu(y W_g) * (y W_u))`; `h' = h + attn + sum`.

then a final RMSNorm and an untied head. ASSUMED, where the catalog's config
settles nothing (`perfbench/configs/keye-vl2-30b-l12-ep8.json` lists each):
the q/k RMSNorm (the Qwen3-MoE lineage these sizes are those of), the index
key's LayerNorm, rotary over the indexer's whole `Di` at the model's theta,
projections in the compute type with float32 scores, and that
`q_chunk_size` / `kv_chunk_size` are the published kernel's tiling of `I`
and change no result.

THE CACHE (`make_cache`; `inference/kv_cache.HybridCache`): K and V of every
layer at full length, stacked `(L, B, Hkv, M, D)`, and BESIDE them the index
keys, one row a token a layer that all heads share, `(L, B, 1, M, 128)`: the
key's `Di` values and zeros up to a whole lane row (`index_key_lanes`).
A DECODE step stages its token in both (`ops.attention.sparse_select`, the
scores over a row's live index keys and its choice; `sparse_decode`, the
attention over the chosen slots) and lands each kind once. A PREFILL walks
the batch a ROW and a CHUNK of `PREFILL_CHUNK` queries at a time through all
the layers: the chunk's K, V and index keys are written into the row's slabs
first, then `sparse_prefill` scores, chooses and attends against the slabs up
to each query's own position. The walk carries the whole cache and names the
row by index; no row is cut out of the stacks. ANY length is walked in chunks
of whole 128-query tiles, the kernels' shape: a prompt that is no multiple
of the chunk has its last chunk DRAWN BACK to end at the row's end, and the
positions it overlaps are computed and written a second time (a prompt under
128 tokens is one chunk of its own length, in the plain form on the chip
too: 127 queries at most).

The layers are NOT stacked and scanned, for `models/nemotron_h.py`'s reason
(under a scan the grouped expert GEMM, a Pallas call a slice cannot fuse
into, is handed a copy of the layer's held experts every pass). Both walks
were read on the chip at the cell's shapes (`tools/keye_scanned_walk.py`;
PERF.md, PR 51): scanned, a batch of 8 x 32,768 + 512 takes 20.78 s against
19.79 (1.5 ms a pass over the layers, 5% of the throughput) and the program
is made 39 s sooner (8.6 s of tracing against 49, 4.9 s of compiling against
23); a deployment pays the second once and the first every batch. The chip
may hold a SHARE of the model: `num_experts` of `router_experts` from
`expert_offset` on, a slice of the vocabulary, and of the depth as many
layers as `num_hidden_layers` says.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.llama import RMSNorm, _dense

F32 = jnp.float32
# Queries of one row that walk the layers together in a prefill: at 2,048 the
# choice's bias is 136 MB a layer call, the experts' sorted rows 0.13 GB, and
# the weights are read 16 times a 32,768-token row.
PREFILL_CHUNK = 2048


@dataclasses.dataclass(frozen=True)
class KeyeSparseConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    # the indexer (`sa_config`)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    # experts: `num_experts` are HELD here, of the `router_experts` the router
    # scores (None: all of them are held), from `expert_offset` on
    num_experts: int = 128
    router_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    dispatch_impl: str = "auto"

    @property
    def index_key_lanes(self) -> int:
        """The width an index key is STORED at: whole lane rows, the key's
        `indexer_head_dim` values and zeros after them. At its own 64 the
        compiler re-lays the whole slab around every kernel that reads it
        (it keeps a 64-wide buffer transposed at rest: 1.9 ms a layer a
        prefill chunk, 5.6 s of a 34 s batch; PERF.md, PR 51); at 128 the
        chip holds what it would have held padded."""
        return -(-self.indexer_head_dim // 128) * 128

    def kv_bytes_by_kind(self, batch: int, max_len: int, dtype=None) -> dict:
        """What lies BESIDE K and V (`capacity_scan.kv_cache_bytes` adds it
        to theirs): the index keys AS HELD, `index_key_lanes` a key (the
        zeros are this program's own choice and fill the chip like any
        other byte; the roofline's `index_read_bytes` counts the key's own
        `indexer_head_dim`)."""
        from deepspeed_tpu.inference.kv_cache import LatentCache
        return {"index_kv_bytes": LatentCache.nbytes(
            self.num_hidden_layers, batch, max_len, self.index_key_lanes,
            dtype or self.dtype)}


def _rotated(x, positions, theta, dtype):
    """x (B, S, heads, width) rotated over its whole width at `positions`
    (B, S)."""
    from deepspeed_tpu.ops.attention import apply_rotary_emb, rope_cos_sin
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta, dtype)
    return apply_rotary_emb(x, cos, sin)


class SparseAttention(nn.Module):
    cfg: KeyeSparseConfig

    @nn.compact
    def __call__(self, x, cache=None, slot=None, row=None):
        """x (B, S, hidden). `cache` None: a plain causal pass over the
        tokens themselves. With the model's `HybridCache` and this layer's
        `slot`: S == 1 is a decode step of every row, its token staged,
        returning (out, (k, v, index key) of the token); S > 1 is a CHUNK of
        sequence `row` alone (B == 1) from that row's cursor on, written
        into the slabs and attended against them, returning (out, cache)."""
        from deepspeed_tpu.ops import attention as ops
        from deepspeed_tpu.ops.pallas.sparse_select import \
            sparse_attention_plain
        cfg = self.cfg
        nh, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        b, s, _ = x.shape
        heads = lambda t, n, w: t.reshape(b, s, n, w)  # noqa: E731
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        proj = lambda n, name: _dense(n, ("embed", "heads"), cfg.dtype, name)  # noqa: E731
        q = norm("q_norm")(heads(proj(nh * d, "q_proj")(x), nh, d))
        k = norm("k_norm")(heads(proj(hkv * d, "k_proj")(x), hkv, d))
        v = heads(proj(hkv * d, "v_proj")(x), hkv, d)
        q_i = heads(proj(hi * di, "index_q_proj")(x), hi, di)
        k_i = nn.LayerNorm(epsilon=hybrid.INDEX_NORM_EPS, dtype=cfg.dtype,
                           param_dtype=F32, name="index_k_norm")(
            _dense(di, ("embed", None), cfg.dtype, "index_k_proj")(x))
        w = _dense(hi, ("embed", None), cfg.dtype,
                   "index_w_proj")(x).astype(F32)               # (B, S, Hi)

        if cache is None:
            start = jnp.zeros((b,), jnp.int32)
        elif s == 1:
            start = cache.index
        else:
            start = jax.lax.dynamic_slice(cache.index, (row,), (1,))
        positions = start[:, None] + jnp.arange(s)[None, :]
        q, k = (_rotated(t, positions, cfg.rope_theta, cfg.dtype)
                for t in (q, k))
        q_i = _rotated(q_i, positions, cfg.rope_theta, cfg.dtype)
        k_i = _rotated(k_i[:, :, None], positions, cfg.rope_theta,
                       cfg.dtype)[:, :, 0]
        # zeros up to whole lane rows: they add nothing to a score
        q_i, k_i = (jnp.pad(t, ((0, 0),) * (t.ndim - 1)
                            + ((0, cfg.index_key_lanes - di),))
                    for t in (q_i, k_i))
        scale = d ** -0.5

        made = None
        if cache is None:
            o, kept = jax.vmap(
                lambda *t: sparse_attention_plain(
                    *t, jnp.arange(s), cfg.index_topk, scale))(
                q, q_i, w, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), k_i)
        elif s == 1:
            k_view, v_view = cache.kv.layer_views(slot, staged=True)
            keys = cache.index_keys.c.replace(layer=slot)
            lengths = cache.index + 1
            bias, kept = ops.sparse_select(q_i[:, 0], w[:, 0], keys, lengths,
                                           cfg.index_topk, k_i[:, 0])
            o = ops.sparse_decode(q[:, 0], k_view, v_view, lengths, bias,
                                  scale, k[:, 0], v[:, 0])[:, None]
            made = (k[:, 0], v[:, 0], k_i[:, 0])
        else:
            made = cache = _write_chunk(cache, slot, row, start[0], k[0], v[0],
                                        k_i[0])
            k_view, v_view = cache.kv.layer_views(slot, staged=False)
            o, kept = ops.sparse_prefill(
                q[0], q_i[0], w[0], k_view, v_view,
                cache.index_keys.c.replace(layer=slot), row, start[0],
                cfg.index_topk, scale)
            o = o[None]
        # what a dense read would walk (every query's positions up to its
        # own) and what the selection DID walk: the slots the choice kept, as
        # the kernel counted the zeros of the bias it wrote. A call's sums
        # fit int32 (2,048 queries at the published context: 5e8); the
        # engine's sum over a whole generate is wide
        for name, value in (("kv_positions_live", positions + 1),
                            ("kv_positions_selected", kept)):
            self.sow("counters", name, jnp.sum(value, dtype=jnp.int32),
                     init_fn=lambda: jnp.zeros([], jnp.int32),
                     reduce_fn=lambda a, b_: a + b_)
        out = _dense(cfg.hidden_size, ("heads_in", "embed"), cfg.dtype,
                     "o_proj")(o.astype(cfg.dtype).reshape(b, s, nh * d))
        return out, made


def _write_chunk(cache, slot, row, start, k, v, k_i):
    """The cache with a chunk of sequence `row`, k/v (C, Hkv, D) and index
    keys (C, Di), written into layer `slot`'s slabs at positions `start ..`:
    dynamic slices written whole, which keep the stacks' tiling."""
    from deepspeed_tpu.inference.kv_cache import DenseLayer
    kv = cache.kv

    def put(stack, new):            # new (heads, C, width)
        return DenseLayer(jax.lax.dynamic_update_slice(
            stack, new.astype(stack.dtype)[None, None],
            (slot, row, 0, start, 0)))
    return cache.replace(
        kv=kv.replace(k=put(kv.k.stack, jnp.swapaxes(k, 0, 1)),
                      v=put(kv.v.stack, jnp.swapaxes(v, 0, 1))),
        index_keys=cache.index_keys.replace(
            c=put(cache.index_keys.c.stack, k_i[None])))


class Layers(nn.Module):
    """The walk over the layers: `layer_<i>` the attention, `layer_<i>_mlp`
    the experts, each behind its norm."""
    cfg: KeyeSparseConfig

    @nn.compact
    def __call__(self, h, cache=None, row=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        staged = []     # a decode step's new (k, v, index key) of each layer
        for i in range(cfg.num_hidden_layers):
            out, made = SparseAttention(cfg, name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(h), cache, i, row)
            if isinstance(made, tuple):
                staged.append(made)
            elif made is not None:
                cache = made
            h = h + out
            # softmax scores over every expert the router scores, the taken
            # ones' weights over their sum; no shared expert
            h = h + hybrid.held_experts(
                cfg, f"layer_{i}_mlp", held=cfg.num_experts,
                activation="silu", score_fn="softmax")(
                    norm(f"layer_{i}_mlp_norm")(h), train=False)
        if staged:      # the step's one write a kind, every layer's token
            k, v, k_i = (jnp.stack(t) for t in zip(*staged))
            cache = cache.replace(kv=cache.kv.land(k, v),
                                  index_keys=cache.index_keys.land(k_i))
        return h, cache


class KeyeSparseForCausalLM(nn.Module):
    cfg: KeyeSparseConfig
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
        `max_len` positions: every layer's K and V in the stacked view and,
        beside them, its index keys."""
        from deepspeed_tpu.inference.kv_cache import (HybridCache, KVCache,
                                                      LatentCache)
        hybrid.refuse_int8(self, quantized)
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        return HybridCache(
            kv=KVCache.create_stacked(
                cfg.num_hidden_layers, batch, max_len,
                cfg.num_key_value_heads, cfg.head_dim, dtype=dtype),
            index_keys=LatentCache.create(
                cfg.num_hidden_layers, batch, max_len, cfg.index_key_lanes,
                dtype=dtype))


init_params_and_specs, materialize_params, keye_sparse_loss_fn = \
    hybrid.entry_points(KeyeSparseForCausalLM)
