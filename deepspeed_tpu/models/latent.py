"""What the latent-attention (MLA) families with a COMPRESSED query share
(`models/deepseek_sparse.py`, `models/openpangu.py`), written once: the
projections of a layer's input into queries and the one cached row a token,
the absorption of a decode step's query and output through the two halves of
the up-projection, where a chunk of a prefill starts and how it is written
into the row's slabs, and the slots a row of the cache is given. What a
family does BETWEEN those (a learned choice of rows, or every row) is its
own file's.

For a token with `u` the layer's normed input (`ops/pallas/mla.py` has the
cache's layout and the absorbed form):

- `cq = RMSNorm(u W_qa)` (`q_lora_rank`); `q = cq W_qb`, H heads of `[q_nope
  (dn) | q_rope (dr)]`, rotary on the dr;
- `[ckv | kr] = u W_kva` (`kv_lora_rank` | dr), `c = RMSNorm(ckv)`, `kr =
  rope(kr)`: ONE row `[c | kr]` a token that all heads share; a head's key
  is `[c W_uk^h | kr]` and its value `c W_uv^h`, `W_kvb = [W_uk | W_uv]`.

The parameters are created in the CALLING module's scope under the names the
published checkpoints use (`q_a_proj`, `q_a_norm`, `q_b_proj`, `kv_a_proj`,
`kv_a_norm`, `kv_b_proj`), so a family's tree does not say who wrote them.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm, _dense

F32 = jnp.float32


class Projected(NamedTuple):
    cq: Any             # (B, S, q_lora_rank): the query's compression, normed
    q_nope: Any         # (B, S, H, dn)
    q_rope: Any         # (B, S, H, dr), rotated
    row: Any            # (B, S, rank + dr): what the token caches, `[c | kr]`
    w_kvb: Any          # (rank, H, dn + dv) in the compute type
    positions: Any      # (B, S)
    rotated: Callable   # (t (B, S, heads, width), at) -> t with dr values rotated


def cache_slots(max_len: int) -> int:
    """The slots a row of the cache is GIVEN for `max_len` positions: whole
    tiles of the widest block any kernel walks a row by (`sparse_select.
    CHOICE_BLOCK`, 2,560 slots, which the narrower blocks of every kernel
    divide) once a row is longer than one. The engine rounds a length to
    128, and 24,832 = 128 x 2 x 97 would leave every kernel tiles of 256
    slots; 33,280 is 13 such blocks as it stands."""
    from deepspeed_tpu.ops.pallas.sparse_select import CHOICE_BLOCK
    return max_len if max_len < CHOICE_BLOCK \
        else -(-max_len // CHOICE_BLOCK) * CHOICE_BLOCK


def chunk_start(cache, b: int, s: int, row):
    """(B,) int32: the position of the first of `s` tokens a row. No cache:
    0. A decode step (s == 1): every row's cursor. A chunk of a prefill:
    sequence `row`'s cursor (B == 1)."""
    if cache is None:
        return jnp.zeros((b,), jnp.int32)
    if s == 1:
        return cache.index
    return jax.lax.dynamic_slice(cache.index, (row,), (1,))


def project(mod: nn.Module, x, start, rope_scaling=None) -> Projected:
    """The layer's input `x` (B, S, hidden), already normed, through the
    query's compression and the latent's, `mod.cfg` giving the sizes and
    `mod`'s scope the parameters; the tokens stand at positions `start[b]
    ..`."""
    from deepspeed_tpu.ops import attention as ops
    cfg = mod.cfg
    nh, dn, dr, dv, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    b, s, _ = x.shape
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
    cq = norm("q_a_norm")(_dense(cfg.q_lora_rank, ("embed", None), cfg.dtype,
                                 "q_a_proj")(x))
    q = _dense(nh * (dn + dr), ("embed", "heads"), cfg.dtype,
               "q_b_proj")(cq).reshape(b, s, nh, dn + dr)
    c, k_r = jnp.split(_dense(rank + dr, ("embed", None), cfg.dtype,
                              "kv_a_proj")(x), [rank], axis=-1)
    c = norm("kv_a_norm")(c)
    w_kvb = mod.param("kv_b_proj", nn.with_logical_partitioning(
        nn.initializers.normal(0.02), (None, "heads")),
        (rank, nh * (dn + dv)), F32).astype(cfg.dtype).reshape(
            rank, nh, dn + dv)
    positions = start[:, None] + jnp.arange(s)[None, :]
    cos, sin = ops.rope_cos_sin(positions, dr, cfg.rope_theta, cfg.dtype,
                                rope_scaling)

    def rotated(t, at):
        """`t` (B, S, heads, width) with its `dr` values from `at` on
        rotated."""
        return jnp.concatenate(
            [t[..., :at], ops.apply_rotary_emb(t[..., at:at + dr], cos, sin),
             t[..., at + dr:]], axis=-1)

    q = rotated(q, dn)
    k_r = rotated(k_r[:, :, None], 0)[:, :, 0]
    return Projected(cq, q[..., :dn], q[..., dn:],
                     jnp.concatenate([c, k_r], axis=-1), w_kvb, positions,
                     rotated)


def absorbed(q_nope, w_kvb):
    """A decode step's queries (B, H, dn) taken through the KEY half of the
    up-projection: (B, H, rank), what scores a cached latent directly."""
    return jnp.einsum("bhn,rhn->bhr", q_nope, w_kvb[..., :q_nope.shape[-1]])


def through_values(o_lat, w_kvb, dn: int, dtype):
    """The weighted sum of the cached LATENTS (B, H, rank) float32 taken
    through the VALUE half of the up-projection: (B, H, dv)."""
    return jnp.einsum("bhr,rhv->bhv", o_lat.astype(dtype), w_kvb[..., dn:])


def write_chunk(cache, slot, row, start, **kinds):
    """The cache with a chunk of sequence `row` written into layer `slot`'s
    slabs at positions `start ..`: `kinds` names each `LatentCache` of the
    `HybridCache` (`latent`, `index_keys`) with its rows (C, width). Dynamic
    slices written whole, which keep the stacks' tiling."""
    from deepspeed_tpu.inference.kv_cache import DenseLayer

    def put(kind, new):
        stack = kind.c.stack
        return kind.replace(c=DenseLayer(jax.lax.dynamic_update_slice(
            stack, new.astype(stack.dtype)[None, None, None],
            (slot, row, 0, start, 0))))
    return cache.replace(**{name: put(getattr(cache, name), new)
                            for name, new in kinds.items()})
