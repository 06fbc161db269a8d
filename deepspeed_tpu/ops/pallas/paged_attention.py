"""Pallas TPU paged decode attention (single-query flash over block tables).

The blocked-flash slot of the reference's FastGen kernel set
(`inference/v2/kernels/ragged_ops/blocked_flash/`, driven by the block
tables of `inference/v2/ragged/blocked_allocator.py` /
`sequence_descriptor.py`): one new query token per sequence attends only the
physical KV blocks its block table names. The block table, the per-row
lengths and the layer arrive via scalar prefetch; the pools stay in HBM
(`memory_space=pl.ANY`) and the kernel copies logical block j of row b, pool
block `tables[b, j]`, into VMEM itself. It reads exactly the live blocks,
which is what makes cache HBM (and decode bandwidth) scale with tokens in
flight instead of max_batch·max_seq.

Grid (B,), the block loop inside (PR 46): a step is one row. It reads the
row's pool length from the prefetched scalars, derives the span of logical
blocks that hold a column the query attends (`lo`: the window's first
block, else 0; `n`: the blocks below the length) and walks `lo..n-1` with
two VMEM slots a pool: start block j+1, wait for block j, one online-softmax
update over it, in ascending block order, float32 accumulators. On a row's
LAST block the copy it starts is the first block of the next row that has
one (found by a scalar scan over the lengths), so a row boundary exposes no
DMA latency; the steps therefore run one after another ("arbitrary"), and
the slot that copy lands in is handed over in SMEM. Work is the live
(row, block) pairs plus one small constant a row (the step, the q / staged /
output tiles); NOTHING is proportional to the table's length T. Before PR
46 the grid was (B, T), one BlockSpec-pipelined block a step with the dead
steps clamped onto a repeated block: 48 x 18 steps a layer at the serving
cell's shape, of which five rows' worth computed, at about 0.19 us a dead
step (PERF.md, PR 46: 182 -> 39 us a call at one row in six live, 262 ->
140 at all rows live).

WHOLE-HEAD tiles: each copy brings one physical block for ALL Hkv KV heads,
an (Hkv, BS, D) slab against the full (Hkv·n_rep, D) query tile. The r3
layout ran grid (B, Hkv, T) with one (n_rep, D) query sliver per step; at
MHA (n_rep=1) that is B·Hkv·T programs of (1, D) work each, and per-step
grid overhead dominated the whole serving loop (measured 3.3 ms/layer at
B=64, Hkv=8, T=4 on v5e). Folding Hkv into the tile cut grid steps by Hkv
and made every DMA Hkv× larger (≈20×).

Layout: q (B, 1, H, D); pools (L, Hkv, NB, BS, D) as stored by
`inference/kv_cache.py:PagedKVCache`, with the layer to read as a third
scalar-prefetch operand, so that a block is fetched from `(layer, :, phys)`
of the stacked pool and no program cuts a layer out of it first (or one
layer's own (Hkv, NB, BS, D), a stack of one); tables (B, T) int32;
lengths (B,).

PARKED ROWS (docs/kv_cache.md, "A row that holds nothing"): a cursor at or
past the table's capacity `T * BS` means the row has no place to write,
which only a row that holds no request has (the v2 engine parks an unused
row at `cache.max_len`, which is `T * BS`). Both attention kernels read that
off the lengths / starts they prefetch as scalars and run NO compute step
for such a row: decode for `lengths > T * BS` (its caller passes cursor +
1), prefill for `starts >= T * BS`. A row that holds a request can never
meet the test (it decodes at a cursor of at most `T * BS - 1` and prefills
with `start + valid <= T * BS`, `valid >= 1`), and runs exactly the steps
it ran. In decode a parked row has no live block: it copies nothing, and
its step writes zeros, or its staged value. The writer `paged_kv_write`
drops the same rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF


def _paged_kernel(lengths_ref, tables_ref, layer_ref, q_ref, *refs, scale, bs,
                  nb, nrows, hkv, n_rep, d, window, quantized, staged,
                  has_alibi):
    """One grid step: one row of the batch, walking ITS OWN live blocks.
    Refs after `q_ref`, in order: the pools as they lie in HBM (K, V, then
    their scales when `quantized`), the staged pair, the alibi slopes, the
    output; then scratch: a two-slot VMEM buffer a pool, the DMA semaphores
    (pool, slot), the slot the next first block lands in (SMEM), and the
    online-softmax state m / l / acc."""
    refs = list(refs)
    npool = 4 if quantized else 2
    hbm = [refs.pop(0) for _ in range(npool)]
    kn_ref, vn_ref = (refs.pop(0), refs.pop(0)) if staged else (None, None)
    alibi_ref = refs.pop(0) if has_alibi else None
    o_ref = refs.pop(0)
    bufs = [refs.pop(0) for _ in range(npool)]
    sems, slot_ref, m_scr, l_scr, acc_scr = refs
    h = hkv * n_rep
    qoff = 1 if staged else 0
    layer = layer_ref[0]
    b = pl.program_id(0)

    def span(r):
        """Row r's live logical blocks [lo, n): below its pool length and,
        with a window, not wholly below the band. A parked row arrives with
        a pool length of 0 (the wrapper): it has none."""
        length = lengths_ref[r]
        n = (length + bs - 1) // bs
        if window is None:
            return jnp.int32(0), n
        # lowest valid col = (L-1+qoff) - window + 1
        return jnp.maximum(length + qoff - window, 0) // bs, n

    def next_live(r):
        """The first row at or after r with a block to read, or nrows."""
        def dead(r):
            lo, n = span(jnp.minimum(r, nrows - 1))
            return jnp.logical_and(r < nrows, lo >= n)
        return jax.lax.while_loop(dead, lambda r: r + 1, r)

    def copies(r, j, slot):
        """Logical block j of row r -> slot, every pool's; the table entry
        is clamped so a stale row can never index out of pool."""
        phys = jnp.clip(tables_ref[r, j], 0, nb - 1)
        return [pltpu.make_async_copy(pool.at[layer, :, phys], buf.at[slot],
                                      sems.at[i, slot])
                for i, (pool, buf) in enumerate(zip(hbm, bufs))]

    def start(r, j, slot):
        for c in copies(r, j, slot):
            c.start()

    @pl.when(b == 0)
    def _prime():
        # nobody runs before the first live row to fetch its first block
        slot_ref[0] = 0
        r = next_live(jnp.int32(0))

        @pl.when(r < nrows)
        def _():
            start(r, span(r)[0], 0)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    # the query's absolute position: last pool slot, or one past it when
    # the new token is staged in-register
    qpos = length - 1 + qoff
    lo, n = span(b)

    def attend(j, slot):
        q = q_ref[0].reshape(hkv, n_rep, d)  # the full head set, grouped
        k = bufs[0][slot]                    # (Hkv, BS, D) — one block, all heads
        v = bufs[1][slot]
        if quantized:
            # int8 pool: the r6 scale-into-activation fold, attention
            # form — per-(head, slot) scales ride the LOGIT columns
            # (`(q·k_q)·s_j`, token scales live along lanes exactly like
            # the logits' key axis) and the PROBABILITY columns on the V
            # side; a dense dequantized (BS, D) tile never materializes
            s3 = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            s3 = s3 * bufs[2][slot][:, 0][:, None, :]    # (Hkv, n_rep, BS)
            s = s3.reshape(h, bs) * scale
        else:
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(h, bs) * scale
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (h, bs), 1)
        if alibi_ref is not None:  # slopes[h]·key_position logits bias
            s = s + alibi_ref[:, :bs] * cols.astype(jnp.float32)
        keep = cols < length
        if window is not None:
            keep = jnp.logical_and(keep, cols > qpos - window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p3 = p.reshape(hkv, n_rep, bs) * bufs[3][slot][:, 0][:, None, :]
            pv = jax.lax.dot_general(
                p3, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(h, d)
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype).reshape(hkv, n_rep, bs), v,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(h, d)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :1] = m_new

    @pl.when(lo < n)
    def _walk():
        first = slot_ref[0]      # where this row's block `lo` is landing
        # what follows this row's last block: the next live row's first, in
        # flight while this row finishes, so that a row boundary exposes no
        # DMA latency (the steps run one after another)
        nxt = next_live(b + 1)
        nxt_row = jnp.minimum(nxt, nrows - 1)
        nxt_lo = span(nxt_row)[0]

        def visit(j, _):
            slot = (first + j - lo) & 1
            more = j + 1 < n

            @pl.when(jnp.logical_or(more, nxt < nrows))
            def _():
                start(jnp.where(more, b, nxt_row),
                      jnp.where(more, j + 1, nxt_lo), 1 - slot)

            for c in copies(b, j, slot):
                c.wait()
            attend(j, slot)
            return 0

        jax.lax.fori_loop(lo, n, visit, 0)
        slot_ref[0] = (first + n - lo) & 1

    if staged:
        # staged append (see kv_cache.PagedLayer.stage): the row's NEW
        # token is not in the pool yet — fold its single key/value
        # column (at position qpos, always inside its own window) into
        # the online-softmax state in-register
        q = q_ref[0].reshape(hkv, n_rep, d)
        kn = kn_ref[0]                   # (Hkv, D)
        vn = vn_ref[0].astype(jnp.float32)
        sn = (jnp.sum(q.astype(jnp.float32) *
                      kn.astype(jnp.float32)[:, None, :], axis=-1)
              .reshape(h, 1) * scale)    # (H, 1)
        if alibi_ref is not None:
            sn = sn + alibi_ref[:, :1] * qpos.astype(jnp.float32)  # (H,1)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, sn)
        alpha = jnp.exp(m_prev - m_new)
        pn = jnp.exp(sn - m_new)
        l_scr[:, :1] = l_scr[:, :1] * alpha + pn
        vb = jnp.broadcast_to(vn[:, None, :], (hkv, n_rep, d)).reshape(h, d)
        acc_scr[:] = acc_scr[:] * alpha + pn * vb
        m_scr[:, :1] = m_new
    l = l_scr[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _stacked_pools(k_pool, v_pool, k_scales, v_scales, layer):
    """The pools as the kernels take them: stacked (L, Hkv, NB, BS, D) with
    `layer` a (1,) int32 for the scalar prefetch. Without a `layer` the
    pools are one layer's own, and go in as a stack of one."""
    if layer is None:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    return (k_pool, v_pool, k_scales, v_scales,
            jnp.asarray(layer, jnp.int32).reshape(1))


def _scale_operand(scales: jnp.ndarray) -> jnp.ndarray:
    """(L, Hkv, NB, BS) pool scales as the kernels take them:
    (L, Hkv, NB, 1, BS). Mosaic wants a block's second-to-last dim a
    multiple of 8 or the whole array dim, and ONE physical block of the NB
    axis is neither; the unit dim makes a block's (1, BS) tail span its
    array's."""
    l, hkv, nb, bs = scales.shape
    return scales.reshape(l, hkv, nb, 1, bs)


def _pool_block_spec(hkv: int, bs: int, d: int, index_map) -> pl.BlockSpec:
    """One physical block of one layer for every KV head; the layer dim is
    squeezed, so the kernels read a (Hkv, 1, BS, D) ref."""
    return pl.BlockSpec((None, hkv, 1, bs, d), index_map)


def _scale_block_spec(hkv: int, bs: int, index_map) -> pl.BlockSpec:
    """That block's scales, riding the pools' own 5-D index map; the layer
    and NB dims are squeezed, so the kernels read a (Hkv, 1, BS) ref."""
    return pl.BlockSpec((None, hkv, None, 1, bs), index_map)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           lengths: jnp.ndarray,
                           softmax_scale: Optional[float] = None,
                           k_new: Optional[jnp.ndarray] = None,
                           v_new: Optional[jnp.ndarray] = None,
                           window: Optional[int] = None,
                           alibi: Optional[jnp.ndarray] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None,
                           layer: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_pool: (L, Hkv, NB, BS, D) with `layer` () int32
    the layer to read, or (Hkv, NB, BS, D) without one; tables: (B, T)
    int32 block tables; lengths: (B,) valid tokens per row — with
    `k_new`/`v_new` (B, Hkv, D) the LAST valid token is the staged one
    (not yet in the pool) and is folded in-register; without them the new
    token's slot must already be written. A row with `lengths > T * BS`
    is parked (its cursor stands at capacity, it holds nothing): it copies
    no pool block and computes over none, and comes back as zeros, or as
    its staged value when one is folded.

    `k_scales`/`v_scales` ([L,] Hkv, NB, BS) f32: int8-at-rest pools — the
    per-(kv-head, slot) dequant scales, copied beside their blocks (same
    pool block) and folded into logit/probability columns in-register
    (docs/kv_cache.md); staged tokens arrive in the compute dtype and are
    folded exactly. With unit scales the output is bitwise identical to
    the unquantized kernel on the same values (the interpret-parity test).

    `window`: sliding-window attention (mistral) — only the last `window`
    positions attend; blocks below the band are not walked (neither
    copied nor computed). `alibi`: (H,) per-head slopes added as
    slopes[h]·key_position (bloom). These remove the r3 engine's silent
    dense fallback for masked-decode families. Returns (B, 1, H, D)."""
    b, s, h, d = q.shape
    assert s == 1, "paged decode kernel is single-query"
    k_pool, v_pool, k_scales, v_scales, layer = _stacked_pools(
        k_pool, v_pool, k_scales, v_scales, layer)
    _, hkv, nb, bs, _ = k_pool.shape
    t = tables.shape[1]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    staged = k_new is not None
    quantized = k_scales is not None

    # (B, H, D): head g·n_rep+r of the HF layout is group g, member r —
    # repeat_kv's grouping; the kernel re-splits (H, D) → (Hkv, n_rep, D)
    qt = jnp.swapaxes(q, 1, 2).reshape(b, h, d)
    # staged: pool holds lengths-1 valid tokens (the last is in-register);
    # a parked row (cursor at capacity) holds none: with a pool length of 0
    # it has no live block and fetches nothing, in every variant below
    pool_len = jnp.where(lengths > t * bs, 0,
                         lengths - 1 if staged else lengths)

    def row(*tail):  # this step's row of a per-row operand
        return pl.BlockSpec((1,) + tail,
                            lambda b_, L, Tb, Ly: (b_,) + (0,) * len(tail))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = [k_pool, v_pool]
    slots = [pltpu.VMEM((2, hkv, bs, d), k_pool.dtype),
             pltpu.VMEM((2, hkv, bs, d), v_pool.dtype)]
    if quantized:
        pools += [_scale_operand(k_scales), _scale_operand(v_scales)]
        slots += [pltpu.VMEM((2, hkv, 1, bs), jnp.float32)] * 2
    in_specs = [row(h, d)] + [hbm] * len(pools)
    args = [pool_len.astype(jnp.int32), tables.astype(jnp.int32), layer,
            qt] + pools
    if staged:
        in_specs += [row(hkv, d)] * 2
        args += [k_new, v_new]
    if alibi is not None:
        # (H, max(BS,128)) broadcast: Mosaic supports lane SLICES of a 2D
        # tile but not reshaping a lane vector into sublanes; the kernel
        # reads [:, :bs] ([:, :1] for the staged column)
        lw = max(bs, 128)
        in_specs += [pl.BlockSpec((h, lw), lambda b_, L, Tb, Ly: (0, 0))]
        args += [jnp.broadcast_to(
            jnp.asarray(alibi, jnp.float32).reshape(h, 1), (h, lw))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row(h, d),
        scratch_shapes=slots + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, nb=nb, nrows=b,
                          hkv=hkv, n_rep=n_rep, d=d, window=window,
                          quantized=quantized, staged=staged,
                          has_alibi=alibi is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # one after another: a step starts the first copy of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="self_attn_paged_decode",
    )(*args)
    return out.reshape(b, 1, h, d)


def _paged_prefill_kernel(starts_ref, tables_ref, layer_ref, q_ref, k_ref,
                          v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, scale, bs, nt, cq, hkv,
                          n_rep, d, window=None, alibi_ref=None,
                          ks_ref=None, vs_ref=None):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]
    # this q tile's max key position: its last query attends start+qi·cq+cq−1
    hi = start + (qi + 1) * cq

    # blocks entirely above the causal frontier: skip; and every block of a
    # parked row (start at capacity: it holds nothing, nobody reads it)
    live = jnp.logical_and(j * bs < hi, start < nt * bs)
    if window is not None:
        # blocks entirely below the tile's FIRST query's window: skip
        # (their DMAs are elided by the index-map lo clamp)
        live = jnp.logical_and(live, (j + 1) * bs > start + qi * cq - window)

    @pl.when(live)
    def _compute():
        # (Hkv, cq·n_rep, D): query row r of group g is chunk position
        # (r // n_rep), member (r % n_rep)
        q = q_ref[0, 0]
        k = k_ref[:, 0]                      # (Hkv, BS, D)
        v = v_ref[:, 0]
        if ks_ref is not None:
            # int8 pool: fold the per-token K scale into the LOGIT columns —
            # (q·k_q)·s_j — token scales ride the lane (key) axis, so no
            # sublane reshuffle (the r6 scale-into-activation trick)
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            s = s * ks_ref[:, 0][:, None, :] * scale     # (Hkv, cq·nr, BS)
        else:
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # (Hkv, cq·nr, BS)
        # causal-by-position: key col ≤ this query's absolute position
        qpos = start + qi * cq + jax.lax.broadcasted_iota(
            jnp.int32, (hkv, cq * n_rep, bs), 1) // n_rep
        cols = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (hkv, cq * n_rep, bs), 2)
        if alibi_ref is not None:  # slopes[h]·key_position logits bias
            s = s + alibi_ref[:, :, :1] * cols.astype(jnp.float32)
        keep = cols <= qpos
        if window is not None:  # sliding band: cols in (qpos−window, qpos]
            keep = jnp.logical_and(keep, cols > qpos - window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1)
        if vs_ref is not None:
            # fold the per-token V scale into the PROBABILITY columns:
            # (p·s_j)·v_q — same lane-axis locality as the K fold
            pv = jax.lax.dot_general(
                p * vs_ref[:, 0][:, None, :], v.astype(jnp.float32),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha[..., None] + pv
        m_scr[:] = m_new

    @pl.when(j == nt - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l[..., None]).astype(o_ref.dtype)


def _mk_paged_prefill_kernel(quantized: bool, has_alibi: bool):
    """Positional-arg adapter: the optional refs (K/V scale tiles, alibi
    slopes) arrive as extra positional inputs between the pools and the
    output; route them to the matching kwargs (same scheme as
    _mk_paged_kernel on the decode side)."""
    def wrapper(starts_ref, tables_ref, layer_ref, q_ref, k_ref, v_ref,
                *rest, **kw):
        extra = list(rest[:-4])
        o_ref, m_scr, l_scr, acc_scr = rest[-4:]
        if quantized:
            kw["ks_ref"] = extra.pop(0)
            kw["vs_ref"] = extra.pop(0)
        if has_alibi:
            kw["alibi_ref"] = extra.pop(0)
        _paged_prefill_kernel(starts_ref, tables_ref, layer_ref, q_ref,
                              k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                              **kw)
    return wrapper


def paged_prefill_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, tables: jnp.ndarray,
                            starts: jnp.ndarray,
                            softmax_scale: Optional[float] = None,
                            block_q: int = 256,
                            window: Optional[int] = None,
                            alibi: Optional[jnp.ndarray] = None,
                            k_scales: Optional[jnp.ndarray] = None,
                            v_scales: Optional[jnp.ndarray] = None,
                            layer: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """Chunked-prefill flash attention over the paged cache: q (B, S, H, D)
    are the S new tokens of each row (already written to the pool at
    logical positions starts[b]..starts[b]+S−1); each query attends every
    cached position ≤ its own (per-row prefix-causal — the mask
    `kv_cache.decode_mask` builds, evaluated in-kernel). The FastGen
    blocked-flash slot for MIXED prefill: replaces the r3 fallback
    (dense-view gather + f32 (B,H,S,M) logits) that measured ~140 ms/layer
    at serving shape. Returns (B, S, H, D). The pools are stacked
    (L, Hkv, NB, BS, D) with `layer` () int32 the layer to read, or one
    layer's own (Hkv, NB, BS, D) without one, as in the decode kernel. A
    row with `starts >= T * BS` is parked (it holds nothing): none of its
    query tiles runs a compute step, and it comes back as zeros.

    k_scales/v_scales ([L,] Hkv, NB, BS) f32 mark an int8 pool: the kernel
    dequantizes by folding the per-token scale into the logit / probability
    columns (never materializing a dense bf16 cache). With unit scales the
    quantized path is bitwise-identical to the unquantized kernel on the
    same pool values."""
    b, s, h, d = q.shape
    k_pool, v_pool, k_scales, v_scales, layer = _stacked_pools(
        k_pool, v_pool, k_scales, v_scales, layer)
    _, hkv, nb, bs, _ = k_pool.shape
    t = tables.shape[1]
    n_rep = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)

    cq = min(block_q, s)
    while s % cq:
        cq -= 1
    nq = s // cq

    # (B, S, H, D) → (B, NQ, Hkv, cq·n_rep, D): group heads, tile queries
    qt = q.reshape(b, nq, cq, hkv, n_rep, d)
    qt = jnp.moveaxis(qt, 3, 2).reshape(b, nq, hkv, cq * n_rep, d)

    def kv_index(b_, qi, j, S_, Tb, Ly):
        # clamp to the row's last block live by the END of this prefill
        # (start + S tokens written); repeated ids elide the DMA — and,
        # with a window, blocks below the tile's band elide too
        # a parked row stays on block 0 of its table
        last = jnp.where(S_[b_] >= t * bs, 0,
                         jnp.maximum((S_[b_] + s + bs - 1) // bs - 1, 0))
        jj = jnp.minimum(j, last)
        if window is not None:
            lo = jnp.maximum((S_[b_] + qi * cq - window + 1) // bs, 0)
            jj = jnp.maximum(jj, jnp.minimum(lo, last))
        phys = Tb[b_, jj]
        return (Ly[0], 0, jnp.clip(phys, 0, nb - 1), 0, 0)

    def tile(b_, qi, j, S_, Tb, Ly):
        return (b_, qi, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, hkv, cq * n_rep, d), tile),
        _pool_block_spec(hkv, bs, d, kv_index),
        _pool_block_spec(hkv, bs, d, kv_index),
    ]
    args = [starts.astype(jnp.int32), tables.astype(jnp.int32), layer,
            qt, k_pool, v_pool]
    quantized = k_scales is not None

    if quantized:
        in_specs += [_scale_block_spec(hkv, bs, kv_index)] * 2
        args += [_scale_operand(k_scales), _scale_operand(v_scales)]
    if alibi is not None:
        # per-s-row slope layout (row r of group g = head g·n_rep + r%n_rep),
        # 128-lane padded: the kernel lane-slices [:, :, :1] (see decode)
        rows = jnp.asarray(alibi, jnp.float32).reshape(hkv, 1, n_rep, 1)
        rows = jnp.broadcast_to(rows, (hkv, cq, n_rep, 1)).reshape(
            hkv, cq * n_rep, 1)
        in_specs += [pl.BlockSpec((hkv, cq * n_rep, 128),
                                  lambda b_, qi, j, S_, Tb, Ly: (0, 0, 0))]
        args += [jnp.broadcast_to(rows, (hkv, cq * n_rep, 128))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hkv, cq * n_rep, d), tile),
        scratch_shapes=[pltpu.VMEM((hkv, cq * n_rep), jnp.float32),
                        pltpu.VMEM((hkv, cq * n_rep), jnp.float32),
                        pltpu.VMEM((hkv, cq * n_rep, d), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(
            _mk_paged_prefill_kernel(quantized, alibi is not None),
            scale=scale, bs=bs, nt=t, cq=cq, hkv=hkv, n_rep=n_rep, d=d,
            window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, hkv, cq * n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="self_attn_paged_prefill",
    )(*args)
    # (B, NQ, Hkv, cq·n_rep, D) → (B, S, H, D)
    out = out.reshape(b, nq, hkv, cq, n_rep, d)
    out = jnp.moveaxis(out, 2, 3).reshape(b, s, h, d)
    return out


# ---- the write path: new tokens into the stacked pool, in place ----
#
# XLA's scatter wants the pool tiled with the KV-head dim second-minor (one
# token's (Hkv, D) window is then one tile); the two kernels above read it
# tiled over (BS, D). A program that scatters and attends therefore re-lays
# the WHOLE pool before and after every scatter. This kernel writes in the
# layout the attention kernels read: it takes the pools as they lie in HBM,
# aliased to its outputs, and read-modify-writes only the blocks that the
# new tokens fall in.


def _kv_write_kernel(starts_ref, tables_ref, layer_ref, *refs, bs, t, nb, s,
                     g, quantized):
    n = 4 if quantized else 2          # K, V (and their scales)
    news, pools, bufs, sem = refs[:n], refs[2 * n:3 * n], refs[3 * n:4 * n], \
        refs[-1]                       # refs[n:2n]: the pools as inputs
    layer = layer_ref[0] + pl.program_id(0)
    b = pl.program_id(1)
    start = starts_ref[b]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)

    def piece(p, _):
        lb = jnp.maximum(start, 0) // bs + p     # logical block
        off = start - lb * bs                    # token 0's slot in it
        lo, hi = jnp.maximum(off, 0), jnp.minimum(off + s, bs)
        phys = tables_ref[b, jnp.minimum(lb, t - 1)]
        # drop: parked rows and slots past capacity (lb >= t), unowned
        # table entries (phys < 0), a stage with nothing before it
        live = (start >= 0) & (lb < t) & (phys >= 0) & (phys < nb) & (hi > lo)

        @pl.when(live)
        def _piece():
            # a block's scales are ONE row of the (NB, BS) tiles: they move
            # with the `g` rows around them (Mosaic slices whole tiles)
            first = jnp.minimum(phys // g * g, nb - g)
            there = [pool.at[layer, :, phys] for pool in pools[:2]] + [
                pool.at[layer, :, pl.ds(first, g)] for pool in pools[2:]]

            def move(pairs):   # all in flight together, then all awaited
                copies = [pltpu.make_async_copy(src, dst, sem.at[i])
                          for i, (src, dst) in enumerate(pairs)]
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()

            move(zip(there, bufs))
            if s == 1:
                hot = None
            else:  # hot[r, c]: slot r of this block takes token c
                hot = (jax.lax.broadcasted_iota(jnp.int32, (bs, s), 0) - off
                       == jax.lax.broadcasted_iota(jnp.int32, (bs, s), 1))
            keep = (rows >= lo) & (rows < hi)                    # (BS, 1)
            for buf, new_ref in zip(bufs[:2], news[:2]):
                new = new_ref[...]                               # (Hkv, S, D)
                if hot is None:
                    frame = new                  # broadcasts over the slots
                else:
                    # one term a slot, so exact in any dtype the MXU takes
                    wide = jnp.float32 if new.dtype == jnp.float32 \
                        else jnp.bfloat16
                    frame = jax.lax.dot_general(
                        jnp.broadcast_to(hot.astype(wide)[None],
                                         new.shape[:1] + hot.shape),
                        new.astype(wide), (((2,), (1,)), ((0,), (0,))),
                        precision=jax.lax.Precision.HIGHEST
                        if wide == jnp.float32 else None,
                        preferred_element_type=jnp.float32)
                buf[...] = jnp.where(keep[None], frame.astype(buf.dtype),
                                     buf[...])
            skeep = ((lanes >= lo) & (lanes < hi))[None] & (
                jax.lax.broadcasted_iota(jnp.int32, (1, g, 1), 1)
                == phys - first)                                 # (1, g, BS)
            for buf, new_ref in zip(bufs[2:], news[2:]):
                new = new_ref[...]                               # (Hkv, S)
                if hot is None:
                    frame = new[:, :, None]                      # (Hkv,1,1)
                else:
                    frame = jnp.sum(jnp.where(hot[None], new[:, None, :],
                                              0.0), axis=-1)[:, None, :]
                buf[...] = jnp.where(skeep, frame, buf[...])     # (Hkv,g,BS)
            move(zip(bufs, there))
        return 0

    # the row's S tokens fall in at most this many of its blocks; the loop
    # keeps the kernel one piece long however many they are (a prefill
    # bucket spans seventeen)
    pieces = (s + bs - 2) // bs + 1
    if pieces == 1:
        piece(0, 0)
    else:
        jax.lax.fori_loop(0, pieces, piece, 0)


def paged_kv_write(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                   k_new: jnp.ndarray, v_new: jnp.ndarray,
                   tables: jnp.ndarray, starts: jnp.ndarray, layer=0,
                   k_scales: Optional[jnp.ndarray] = None,
                   v_scales: Optional[jnp.ndarray] = None,
                   k_new_scales: Optional[jnp.ndarray] = None,
                   v_new_scales: Optional[jnp.ndarray] = None):
    """Write `k_new`/`v_new` (NL, B, S, Hkv, D), the S new tokens of each
    row for layers `layer..layer+NL-1`, into the stacked pools
    (L, Hkv, NB, BS, D) at logical positions `starts[b]..starts[b]+S-1`
    through `tables` (B, T), IN PLACE: the pools are aliased to the
    results, nothing else of them is read or written, and their tiling is
    the one the attention kernels read. Returns `(k_pool, v_pool,
    k_scales, v_scales)`.

    What drops is what the XLA scatters of `kv_cache.py` drop: slots at or
    past a row's capacity (parked rows), unowned table entries (< 0), and
    a negative start. int8 pools take the new tokens already quantized,
    with `k_new_scales`/`v_new_scales` (NL, B, S, Hkv) for the pools'
    `k_scales`/`v_scales` (L, Hkv, NB, BS).

    The grid's steps run one after another and each waits for its own
    writes, so two rows that wrote one block would see each other's."""
    nl, b, s, hkv, d = k_new.shape
    _, _, nb, bs, _ = k_pool.shape
    t = tables.shape[1]
    quantized = k_scales is not None
    g = min(8, nb)  # rows of a scale tile

    def tokens(x):  # (NL, B, S, Hkv, ...) -> (NL, B, Hkv, S, ...)
        return jnp.swapaxes(x, 2, 3)

    def row_spec(*tail):  # one row's new tokens of one layer
        return pl.BlockSpec((None, None) + tail,
                            lambda li, b_, St, Tb, Ly: (li, b_) + (0,) * len(tail))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    args = [tokens(k_new.astype(k_pool.dtype)),
            tokens(v_new.astype(v_pool.dtype))]
    in_specs = [row_spec(hkv, s, d)] * 2
    pools = [k_pool, v_pool]
    scratch = [pltpu.VMEM((hkv, bs, d), k_pool.dtype),
               pltpu.VMEM((hkv, bs, d), v_pool.dtype)]
    if quantized:
        args += [tokens(k_new_scales), tokens(v_new_scales)]
        in_specs += [row_spec(hkv, s)] * 2
        pools += [k_scales, v_scales]
        scratch += [pltpu.VMEM((hkv, g, bs), jnp.float32)] * 2
    first = 3 + len(args)  # scalar prefetch operands count as inputs
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, bs=bs, t=t, nb=nb, s=s, g=g,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nl, b),
            in_specs=in_specs + [hbm] * len(pools),
            out_specs=[hbm] * len(pools),
            scratch_shapes=scratch + [
                pltpu.SemaphoreType.DMA((len(pools),))]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={first + i: i for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="kv_write_paged",
    )(starts.astype(jnp.int32), tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *args, *pools)
    return tuple(out) + (None,) * (4 - len(out))
