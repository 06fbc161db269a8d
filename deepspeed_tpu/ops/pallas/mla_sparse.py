"""Pallas TPU kernels for latent attention (MLA) over the rows a LEARNED
INDEXER chose (DeepSeek-Sparse-Attention as DeepSeek-V3.2 has it;
`models/deepseek_sparse.py`).

A layer caches, a token, the latent row `[c | k_r]` (512 + 64,
`HybridCache.latent`; `ops/pallas/mla.py` has the layout) and ONE index key
(128, `HybridCache.index_keys`). The choice is `ops/pallas/sparse_select.py`'s
(`sparse_index_select` a decode step, `sparse_attn_prefill_select` a chunk of
a prefill): a BIAS a slot, 0 where kept and `NEG_INF` elsewhere. What reads
the latent rows under that choice is here, each kernel under its own name in
the device trace:

- `mla_sparse_decode`: a decode step in the ABSORBED form (`mla.py`: the
  query's nope part taken through the key half of the up-projection, the
  weighted sum of the LATENTS returned), all `H` heads of a row over one
  block of slots a grid step. Two forms of the read, one kernel:
  `mla_sparse_decode` walks the row's whole live slab under the bias;
  `mla_sparse_decode_gathered` first GATHERS the chosen rows (an index list
  sorted out of the bias, one XLA gather of `topk` rows of 1,152 bytes a
  sequence, the staged token put in its place) and walks those `topk` slots
  alone. One list serves all 128 heads, so the gather moves 2.4 MB a row a
  layer where the slab is 38 MB; PERF.md (PR 54) has both readings and
  `ops.attention.latent_sparse_decode` takes the faster.
- `mla_sparse_prefill`: a chunk of ONE row's queries in the EXPANDED form (a
  head's key `[c W_uk | k_r]`, 192 wide, its value `c W_uv`, 128: the
  absorbed form is 3.4 x the operations a (query, key) pair), a flash pass
  under the bias over the tiles up to the chunk's causal edge. The nope keys
  and the values arrive expanded for a GROUP of heads (the caller makes them
  a block of keys at a time, live blocks only); the rope key is read from
  the latent slab's lanes 512.., once for all heads, so no operand is padded
  to 256.
- `mla_dense_prefill`: the SAME kernel with no bias, for a model that
  attends EVERY cached row up to the query's own (`models/openpangu.py`): no
  chunk x cache array exists; tiles wholly above the diagonal are neither
  fetched nor computed (as under a bias), and a live tile is masked by
  position (an iota compare in the kernel, 1% over the bare walk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF
from deepspeed_tpu.ops.pallas.sparse_select import (_live_tile, _scalar,
                                                    block_of, row_of)

DECODE_NAME = "mla_sparse_decode"
PREFILL_NAME = "mla_sparse_prefill"
DENSE_PREFILL_NAME = "mla_dense_prefill"
F32 = jnp.float32
_LANES = 128
DECODE_BLOCK = 512      # slots a block: 0.66 MB of latent (576 -> 640 lanes)
# which form of the decode read `ops.attention.latent_sparse_decode` takes
# where the choice drops rows: the chosen rows gathered, or the slab whole
# under the bias (PERF.md, PR 54, has both readings on the chip)
DECODE_GATHERS = True
# the prefill's tiles: `PREFILL_HEADS` heads' (queries x keys) a grid step.
# Read on the chip at the cell's shapes, a chunk of 2,048 queries at the end
# of a row of 24,576 in 25,600 slots, expansion and flash pass together
# (PERF.md, PR 54; heads, queries, slots): 49.5 ms at (8, 512, 512), 45.3 at
# (8, 512, 1,280), 44.5 at (8, 256, 1,280), 52.0 at (16, 256, 512), 67.5 at
# (8, 1,024, 512), 42.5 at (4, 1,024, 1,280)
PREFILL_HEADS = 4
PREFILL_QUERIES = 1024
PREFILL_BLOCK = 1280
# heads whose keys and values are expanded together (0.55 GB at a row of
# 33,280 slots; 64 and 128 heads read 50.1 and 51.4 ms where 32 read 49.5),
# and the slots a pass of the expansion makes
EXPAND_HEADS = 32
EXPAND_BLOCK = 2048
_NT = (((1,), (1,)), ((), ()))


# ----------------------------------------------------------------- decode


def _decode_kernel(lengths_ref, layer_ref, qc_ref, qr_ref, lat_ref, *rest,
                   scale, blk, nk, rank, biased, staged):
    del layer_ref  # the index maps read it
    rest = list(rest)
    bias_ref = rest.pop(0) if biased else None
    new_ref = rest.pop(0) if staged else None
    o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(1)
    heads = qc_ref.shape[0]
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * blk < length)
    def _block():
        c = lat_ref[:, :rank]                        # (blk, rank) latent
        kr = lat_ref[:, rank:]                       # (blk, rope) rope key
        if staged:      # the step's own token takes its slot's place
            hit = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0) \
                == length - 1
            c = jnp.where(hit, new_ref[:, :rank], c)
            kr = jnp.where(hit, new_ref[:, rank:], kr)
        s = (jax.lax.dot_general(qc_ref[...], c, _NT,
                                 preferred_element_type=F32)
             + jax.lax.dot_general(qr_ref[...], kr, _NT,
                                   preferred_element_type=F32)) * scale
        cols = j * blk + jax.lax.broadcasted_iota(jnp.int32, (heads, blk), 1)
        kept = cols < length
        if biased:
            kept = kept & (bias_ref[...] > -1.0)                # (1, blk)
        s = jnp.where(kept, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a block with no kept slot leaves m at NEG_INF, where exp(s - m) is
        # 1 in every column: zeros, not probabilities
        p = jnp.where(kept, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[...] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


def _decode_call(q_lat, q_rope, stack, layer, lengths, softmax_scale, bias,
                 new):
    b, h, rank = q_lat.shape
    m, width = stack.shape[3:]
    blk = block_of(m, DECODE_BLOCK)
    nk = m // blk
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)

    def row(b_, j, L, Ly):
        return (b_, 0, 0)

    def block(b_, j, L):
        # clamped to the row's last live block: steps past it revisit that
        # block and Pallas elides their copies
        return jnp.minimum(j, jnp.maximum((L[b_] + blk - 1) // blk - 1, 0))

    in_specs = [pl.BlockSpec((None, h, rank), row),
                pl.BlockSpec((None, h, width - rank), row),
                pl.BlockSpec((None, None, None, blk, width),
                             lambda b_, j, L, Ly: (Ly[0], b_, 0,
                                                   block(b_, j, L), 0))]
    args = [lengths, _scalar(layer), q_lat.astype(stack.dtype),
            q_rope.astype(stack.dtype), stack]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (None, 1, blk), lambda b_, j, L, Ly: (b_, 0, block(b_, j, L))))
        args.append(bias.reshape(b, 1, m))
    if new is not None:
        in_specs.append(pl.BlockSpec((None, 1, width), row))
        args.append(new.astype(stack.dtype)[:, None])
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=softmax_scale, blk=blk, nk=nk,
                          rank=rank, biased=bias is not None,
                          staged=new is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nk), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, rank), row),
            scratch_shapes=[pltpu.VMEM((h, _LANES), F32),
                            pltpu.VMEM((h, _LANES), F32),
                            pltpu.VMEM((h, rank), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=DECODE_NAME,
    )(*args)


def mla_sparse_decode(q_lat: jnp.ndarray, q_rope: jnp.ndarray,
                      stack: jnp.ndarray, layer, lengths: jnp.ndarray,
                      bias: jnp.ndarray, softmax_scale: float,
                      new: jnp.ndarray) -> jnp.ndarray:
    """One decode step over the CHOSEN rows, the slab read whole under the
    bias. q_lat (B, H, rank), the queries' nope parts absorbed through the
    key half of the up-projection; q_rope (B, H, rope), rotated; stack (L, B,
    1, M, rank + rope) and `layer`; lengths (B,) live slots, the LAST the
    step's own token, staged as `new` (B, rank + rope); bias (B, M) float32
    from `sparse_index_select`, 0 at the chosen slots. Returns (B, H, rank)
    float32: the softmax-weighted sum of the chosen LATENTS."""
    return _decode_call(q_lat, q_rope, stack, layer, lengths, softmax_scale,
                        bias, new)


def chosen_slots(bias: jnp.ndarray, topk: int) -> jnp.ndarray:
    """(B, min(topk, M)) int32: the slots `bias` (B, M) keeps (0 there), in
    ascending order, then M for what a row kept fewer."""
    m = bias.shape[-1]
    at = jnp.where(bias > -1.0, jnp.arange(m, dtype=jnp.int32), m)
    return jnp.sort(at, axis=-1)[:, :min(topk, m)]


def gather_chosen(stack, layer, lengths, bias, topk: int, new):
    """The chosen rows of every sequence as a slab of their own, (1, B, 1,
    min(topk, M), W), the staged token `new` (B, W) in its place (slot
    `lengths - 1`, if chosen)."""
    b, m = bias.shape
    idx = chosen_slots(bias, topk)                              # (B, K)
    rows = stack[jnp.asarray(layer, jnp.int32),
                 jnp.arange(b)[:, None], 0, jnp.minimum(idx, m - 1)]
    own = idx == (jnp.minimum(lengths.astype(jnp.int32), m) - 1)[:, None]
    rows = jnp.where(own[..., None], new.astype(stack.dtype)[:, None], rows)
    return rows[None, :, None]


def mla_sparse_decode_gathered(q_lat, q_rope, stack, layer, lengths, bias,
                               kept, topk: int, softmax_scale: float, new):
    """`mla_sparse_decode` with the chosen rows GATHERED first: `kept` (B,)
    is the count of them a row (`sparse_index_select`'s second result), and
    the kernel walks `min(topk, M)` slots a row, not the slab."""
    # `choose`: the sort that lists the chosen slots and the gather are the
    # choice's cost, not the kernel's (docs/telemetry.md, scopes)
    with jax.named_scope("choose"):
        rows = gather_chosen(stack, layer, lengths, bias, topk, new)
    return _decode_call(q_lat, q_rope, rows, 0, kept, softmax_scale, None,
                        None)


def mla_sparse_decode_reference(q_lat, q_rope, stack, layer, lengths, bias,
                                softmax_scale, new):
    """The same in plain `jax.numpy`, float32."""
    b, _, rank = q_lat.shape
    m = stack.shape[3]
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)
    lat = jax.lax.dynamic_index_in_dim(
        stack, jnp.asarray(layer, jnp.int32), 0, keepdims=False)[:, 0]
    lat = lat.at[jnp.arange(b), lengths - 1].set(
        new.astype(lat.dtype), mode="drop").astype(F32)
    # the kernel's operands are the cache's type: round the queries as it does
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(stack.dtype)
    s = jnp.einsum("bhw,bmw->bhm", q.astype(F32), lat,
                   precision="highest") * softmax_scale
    kept = (jnp.arange(m)[None, :] < lengths[:, None]) & (bias > -1.0)
    s = jnp.where(kept[:, None, :], s, NEG_INF)
    return jnp.einsum("bhm,bmr->bhr", jax.nn.softmax(s, axis=-1),
                      lat[..., :rank], precision="highest")


# ---------------------------------------------------------------- prefill


def _prefill_kernel(start_ref, layer_ref, row_ref, qn_ref, qr_ref, *rest,
                    tq, tk, nk, rank, dn, dv, biased):
    del layer_ref, row_ref
    rest = list(rest)
    bias_ref = rest.pop(0) if biased else None
    kn_ref, v_ref, lat_ref, o_ref, m_scr, l_scr, acc_scr = rest
    i, j = pl.program_id(1), pl.program_id(2)
    heads = qn_ref.shape[0]
    first = start_ref[0] + i * tq           # the tile's first query's position

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * tk <= first + tq - 1)      # some key a query may see
    def _block():
        if biased:
            bias = bias_ref[...].astype(F32)                    # (tq, tk)
        else:
            # EVERY live tile is masked by position, the ones wholly below
            # the diagonal too: a second body for those (no mask) read 53.6
            # ms a chunk on the chip where this one reads 32.6 and the bare
            # walk without any mask 32.3 (PERF.md, PR 58)
            seen = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1) \
                <= first + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kr = lat_ref[:, rank:]                                  # (tk, rope)
        for g in range(heads):      # a head's keys and values: whole lanes
            # the queries come scaled. No `where` on the probabilities: a
            # query whose tiles so far hold no kept slot has m = NEG_INF and
            # gathers ones, and its first kept slot (every query keeps its
            # `min(topk, t + 1)` >= 1; with no bias slot 0, in the first
            # tile) wipes them with alpha = exp(-1e30) = 0
            s = jax.lax.dot_general(qn_ref[g], kn_ref[:, g * dn:(g + 1) * dn],
                                    _NT, preferred_element_type=F32) \
                + jax.lax.dot_general(qr_ref[g], kr, _NT,
                                      preferred_element_type=F32)
            s = s + bias if biased else jnp.where(seen, s, NEG_INF)
            m_prev = m_scr[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g, :, :1] = l_scr[g][:, :1] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[:, g * dv:(g + 1) * dv],
                (((1,), (0,)), ((), ())), preferred_element_type=F32)
            m_scr[g, :, :1] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def mla_sparse_prefill_attend(q_nope, q_rope, bias, k_nope, v, stack, layer,
                              row, start):
    """Attention of a chunk of ONE row's queries, a group of `G` heads:
    q_nope (G, C, dn) and q_rope (G, C, rope), SCALED, at positions `start
    ..` of sequence `row`; bias (C, M) from `sparse_prefill_choice`, or None:
    every position up to the query's own (`mla_dense_prefill`); k_nope
    (M, G * dn) and v (M, G * dv), the heads' expanded keys and values of
    that row's latents, a token's heads side by side as the expansion's
    matmul leaves them (slots past the chunk's end are never read); the rope
    key from the latent `stack` (L, B, 1, M, rank + rope) itself. Returns
    (G, C, dv) in v's type."""
    g, c, dn = q_nope.shape
    m, dv = v.shape[0], v.shape[1] // g
    width = stack.shape[-1]
    rank = width - q_rope.shape[-1]
    hb = block_of(g, PREFILL_HEADS)
    tq, tk = block_of(c, PREFILL_QUERIES), block_of(m, PREFILL_BLOCK)
    nq, nk = c // tq, m // tk
    block = _live_tile(tq, tk)

    def queries(w):
        return pl.BlockSpec((hb, tq, w), lambda h, i, j, St, Ly, Rw: (h, i, 0))

    def keys(w):
        return pl.BlockSpec((tk, hb * w), lambda h, i, j, St, Ly, Rw: (
            block(i, j, St), h))

    biased = bias is not None
    chosen = [pl.BlockSpec((tq, tk), lambda h, i, j, St, Ly, Rw: (
        i, block(i, j, St)))] if biased else []
    return pl.pallas_call(
        functools.partial(_prefill_kernel, tq=tq, tk=tk, nk=nk, rank=rank,
                          dn=dn, dv=dv, biased=biased),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(g // hb, nq, nk),
            in_specs=[queries(dn), queries(width - rank), *chosen,
                      keys(dn), keys(dv),
                      pl.BlockSpec((None, None, None, tk, width),
                                   lambda h, i, j, St, Ly, Rw: (
                                       Ly[0], Rw[0], 0, block(i, j, St), 0))],
            out_specs=queries(dv),
            scratch_shapes=[pltpu.VMEM((hb, tq, _LANES), F32),
                            pltpu.VMEM((hb, tq, _LANES), F32),
                            pltpu.VMEM((hb, tq, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((g, c, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
        name=PREFILL_NAME if biased else DENSE_PREFILL_NAME,
    )(_scalar(start), _scalar(layer), _scalar(row), q_nope, q_rope,
      *([bias] if biased else []), k_nope, v, stack)


def mla_sparse_prefill(q_nope, q_rope, w_kvb, bias, stack, layer, row, start,
                       softmax_scale: float):
    """A chunk of ONE row's queries against that row's latent cache, which
    already holds the chunk, under the choice's `bias` (C, M), or with
    `bias` None against every row up to the query's own: q_nope (C, H,
    dn), q_rope (C, H, rope) rotated, w_kvb (rank, H, dn + dv) the
    up-projection. A GROUP of `EXPAND_HEADS` heads at a time: their keys'
    nope parts and their values are expanded from the row's latents a block
    of `EXPAND_BLOCK` slots a pass, the LIVE blocks only (up to the chunk's
    end), into two buffers the groups share (M, heads x width: a matmul's
    result as it stands), and `mla_sparse_prefill_attend` walks them. Returns
    (C, H, dv)."""
    c, h, dn = q_nope.shape
    m = stack.shape[3]
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - dn
    dt = stack.dtype
    hg = block_of(h, EXPAND_HEADS)
    kb = block_of(m, EXPAND_BLOCK)
    layer, row, start = (jnp.asarray(t, jnp.int32) for t in (layer, row, start))
    live = (start + c + kb - 1) // kb

    def grouped(t):             # (C, H, w) -> (H / hg, hg, C, w)
        return jnp.swapaxes(t, 0, 1).reshape(h // hg, hg, c, t.shape[-1])

    def weights(w):             # (rank, H, w) -> (H / hg, rank, hg * w)
        return jnp.moveaxis(w.astype(dt).reshape(rank, h // hg, -1), 1, 0)

    def group(bufs, xs):
        qn, qr, ws = xs

        def expand(i, bufs):
            lat = jax.lax.dynamic_slice(
                stack, (layer, row, 0, i * kb, 0), (1, 1, 1, kb, rank))
            return tuple(jax.lax.dynamic_update_slice(
                buf, jnp.dot(lat[0, 0, 0], w,
                             preferred_element_type=F32).astype(dt),
                (i * kb, 0)) for buf, w in zip(bufs, ws))

        with jax.named_scope("expand_kv"):   # for the program map
            bufs = jax.lax.fori_loop(0, live, expand, bufs)
        return bufs, mla_sparse_prefill_attend(qn, qr, bias, *bufs, stack,
                                               layer, row, start)

    _, out = jax.lax.scan(
        group, (jnp.zeros((m, hg * dn), dt), jnp.zeros((m, hg * dv), dt)),
        (grouped((q_nope * softmax_scale).astype(dt)),
         grouped((q_rope * softmax_scale).astype(dt)),
         (weights(w_kvb[..., :dn]), weights(w_kvb[..., dn:]))))
    return jnp.swapaxes(out.reshape(h, c, dv), 0, 1)


def mla_sparse_attention_plain(q_nope, q_rope, w_kvb, bias, latents,
                               softmax_scale: float):
    """The layer's attention under a bias in plain `jax.numpy`, float32, in
    the expanded form: q_nope (C, H, dn), q_rope (C, H, rope), w_kvb (rank,
    H, dn + dv), bias (C, M), one sequence's latent rows (M, rank + rope).
    The operands are rounded to the latents' type, as the kernels' are.
    Returns (C, H, dv) float32."""
    dt = latents.dtype
    rank, dn = w_kvb.shape[0], q_nope.shape[-1]
    r = lambda t: t.astype(dt).astype(F32)  # noqa: E731
    lat = latents.astype(F32)
    kv = r(jnp.einsum("mr,rhn->mhn", lat[:, :rank], r(w_kvb),
                      precision="highest"))
    s = jnp.einsum("chn,mhn->hcm", r(q_nope * softmax_scale), kv[..., :dn],
                   precision="highest") \
        + jnp.einsum("chr,mr->hcm", r(q_rope * softmax_scale), lat[:, rank:],
                     precision="highest") + bias.astype(F32)[None]
    return jnp.einsum("hcm,mhv->chv", jax.nn.softmax(s, axis=-1),
                      kv[..., dn:], precision="highest")


def mla_sparse_prefill_reference(q_nope, q_rope, w_kvb, bias, stack, layer,
                                 row, start, softmax_scale):
    """`mla_sparse_prefill` in plain `jax.numpy`, float32 (`start` is in the
    bias already)."""
    del start
    return mla_sparse_attention_plain(
        q_nope, q_rope, w_kvb, bias, row_of(stack, layer, row)[0],
        softmax_scale).astype(stack.dtype)


def mla_dense_prefill(q_nope, q_rope, w_kvb, stack, layer, row, start,
                      softmax_scale: float):
    """`mla_sparse_prefill` with no choice: a chunk of ONE row's queries
    (positions `start ..`) against EVERY row of that sequence's latent cache
    up to each query's own, the cache already holding the chunk. The same
    expansion, the same kernel without its bias operand, under its own name
    in the device trace. Returns (C, H, dv)."""
    return mla_sparse_prefill(q_nope, q_rope, w_kvb, None, stack, layer, row,
                              start, softmax_scale)


def causal_bias(start, c: int, m: int):
    """(C, M) float32: 0 where slot s <= start + t, `NEG_INF` above the
    diagonal. The plain forms' mask; the kernel makes no such array."""
    at = jnp.asarray(start, jnp.int32) + jnp.arange(c)[:, None]
    return jnp.where(jnp.arange(m)[None, :] <= at, 0.0, NEG_INF).astype(F32)


def mla_dense_prefill_reference(q_nope, q_rope, w_kvb, stack, layer, row,
                                start, softmax_scale):
    """`mla_dense_prefill` in plain `jax.numpy`, float32."""
    return mla_sparse_prefill_reference(
        q_nope, q_rope, w_kvb,
        causal_bias(start, q_nope.shape[0], stack.shape[3]), stack, layer,
        row, start, softmax_scale)
