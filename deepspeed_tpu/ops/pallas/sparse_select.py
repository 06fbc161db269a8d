"""Pallas TPU kernels for LEARNED sparse attention: DeepSeek-Sparse-
Attention's lightning indexer, whose CHOICE is this file's for every family
that has one, and the attention under the choice over the stacked dense
cache (grouped-query attention: Keye-VL-2.0, `models/keye_sparse.py`; over a
latent cache it is `ops/pallas/mla_sparse.py`'s, DeepSeek-V3.2,
`models/deepseek_sparse.py`).

Beside what the attention reads (K and V, `(L, B, Hkv, M, D)` each, or the
latent rows), a layer caches ONE index key a token that all heads share,
`(L, B, 1, M, Di)` (`inference/kv_cache.HybridCache.index_keys`), a whole
lane row wide (Keye's 64 values and zeros; DeepSeek's 128). A query t scores
every cached position s <= t with `Hi` small index heads (16 of 64, or 64 of
128; the kernels take either),

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

keeps the `topk` positions of largest `I` (all of them while t < topk; ties
to the LOWER position, the set `jax.lax.top_k` gives), and attends only
those: `softmax over the kept s of q . k / sqrt(D)`, the same set for every
head.

THE CHOICE IS EXACT AND WITHOUT A SORT. The `topk`-th largest score of a row
is found by bisection over the scores' BITS: a float32 maps to an int32 key
of the same order (`sort_key`), and 32 counting passes `count(key >= T) >=
topk` fix the threshold bit by bit from the top. Slots above the threshold
are kept; of the slots AT it, the lowest positions up to the count still
owed, found by a second bisection over the position's bits, which runs only
when a row has more slots at its threshold than it is owed (float32 scores of
seeded or trained weights tie about never; exact zeros of `relu` can). What
comes out is a BIAS a slot, 0 where kept and `NEG_INF` elsewhere (and the
row's count of kept slots, from the same passes: the program's
`kv_positions_selected`), and the
attention over the selection is a dense read under that bias: on this chip a
gather of 2,048 scattered rows a (row, head) costs more than reading the
slab (PERF.md, PR 51), and a threshold mask over a dense read is exact.

Three kernels, each under its own name in the device trace:

- `sparse_index_select`: a decode step's scores over each row's live index
  keys and its choice; a grid step carries a group of rows over one block of
  slots (the rows lie on the sublanes of the key scratch, so a counting pass
  is full vector registers), the choice is made at the group's last block.
- `sparse_attn_decode`: the dense decode kernel's walk (every KV head of a
  row over one block of slots a step, online softmax a (row, head)) under
  the bias; blocks past a row's length are neither fetched nor computed.
- `sparse_attn_prefill_select` and `sparse_attn_prefill`: ONE row's chunk of
  queries against that row's cache, which already holds the chunk. The
  first scores a tile of queries against the live index keys and makes each
  query's choice (the key scratch is (queries, M): 17 MB at 128 queries and
  M 33,280, so its VMEM limit is raised); the second is a flash pass over K
  and V under the bias it wrote, a KV head's whole group of query heads as
  one operand. Tiles wholly past the causal edge are
  neither fetched nor computed, and nothing reads their bias.

A decode step's own token is STAGED (not in the cache yet): its index key
and its K and V take their slot's place in the block the kernel fetched, and
`KVCache.land` / `LatentCache.land` write them once after the layers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _interpret
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF

SELECT_NAME = "sparse_index_select"
DECODE_NAME = "sparse_attn_decode"
PREFILL_SELECT_NAME = "sparse_attn_prefill_select"
PREFILL_NAME = "sparse_attn_prefill"

INT_MIN = jnp.iinfo(jnp.int32).min
F32 = jnp.float32
_LANES = 128
# slots a block at most: the index keys of a group of rows (a lane row a
# key: 2.6 MB a block of 8 rows), K and V of one row's every KV head (1.3 MB
# each at 4 heads of 128), a prefill's index keys, and its K, V and bias
# tiles. Read on the chip at the cell's shapes, a chunk of 2,048 queries at
# the row's end (PERF.md, PR 51): the choice 4.02 ms at (64 queries, 640
# slots), 3.42 at (64, 2,560), 3.25 at (128, 2,560); the attention 9.72 ms
# at (128, 640), 8.71 at (128, 1,280), 8.45 at (256, 1,280). At 64 index
# heads of 128 and a row of 25,600 slots (PERF.md, PR 54) the choice reads
# 5.99 ms at (128 queries, 2,560 slots), 6.12 at (64, 2,560), 5.90 at (128,
# 1,280), and 256 queries pass the kernel's VMEM; a decode step's choice
# 0.117 ms a layer at (8 rows, 1,280 slots), 71% of its roofline: kept
SELECT_BLOCK = 1280
DECODE_BLOCK = 1280
CHOICE_BLOCK = 2560
PREFILL_BLOCK = 1280
SELECT_ROWS = 8          # rows a grid step of the decode choice: the sublanes
SELECT_QUERIES = 128     # queries a tile of the prefill choice
PREFILL_QUERIES = 128    # queries a tile of the prefill attention
_NT = (((1,), (1,)), ((), ()))


def block_of(m: int, cap: int) -> int:
    """The largest divisor of `m` up to `cap`, in whole lane tiles where `m`
    has such a divisor."""
    divisors = [x for x in range(min(m, cap), 0, -1) if m % x == 0]
    return next((x for x in divisors if x % _LANES == 0), divisors[0])


def _relu(s):
    """The index scores' nonlinearity, in ONE place (kernels and plain
    forms): the builder's decode-logits tool replaces it to read what a
    program without it would serve."""
    return jnp.maximum(s, 0.0)


def sort_key(x):
    """float32 -> int32 whose signed order is the floats' own (-0.0 as 0.0:
    `jax.lax.top_k` holds them equal)."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.int32)
    bits = jnp.where(bits == INT_MIN, 0, bits)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


# ------------------------------------------------------------- the choice


def _count(key_ref, tiles, tw, pred):
    """(R, 1) int32: the slots of the first `tiles` tiles (`tw` wide) of
    `key_ref` (R, W) for which `pred(keys (R, tw), the tile's first slot)`
    holds. Partial counts stay a lane tile wide; one reduction at the end."""
    rows = key_ref.shape[0]
    fold = math.gcd(tw, _LANES)

    def tile(j, acc):
        at = pl.multiple_of(j * tw, tw)
        hit = jnp.where(pred(key_ref[:, pl.ds(at, tw)], at), 1, 0)
        return acc + functools.reduce(
            jnp.add, [hit[:, c:c + fold] for c in range(0, tw, fold)])

    acc = jax.lax.fori_loop(0, tiles, tile, jnp.zeros((rows, fold), jnp.int32))
    return jnp.sum(acc, axis=-1, keepdims=True)


def _choose(key_ref, cut_ref, kept_ref, tiles, tw, k):
    """The choice of the `k` (R, 1) largest keys a row of `key_ref` (R, W)
    among its first `tiles` tiles: returns the threshold `thr` (R, 1), and
    leaves in `cut_ref` (R, 1) the highest slot kept AT the threshold. Kept:
    `key > thr`, or `key == thr` and `slot <= cut`; `kept_ref` (R, 1) gets
    the COUNT of those, by the passes that made the choice (it is `k` while
    the choice is right, and no arithmetic of `k`)."""
    width = key_ref.shape[1]

    def at_least(cand):
        return _count(key_ref, tiles, tw, lambda t, at: t >= cand)

    def bit(i, thr):
        cand = thr | (jnp.int32(1) << (30 - i))
        return jnp.where(at_least(cand) >= k, cand, thr)

    # the sign first, then the 31 bits below it: setting a lower bit raises
    # an int32 of either sign
    thr = jax.lax.fori_loop(
        0, 31, bit, jnp.where(at_least(jnp.int32(0)) >= k, 0, INT_MIN))
    above = _count(key_ref, tiles, tw, lambda t, at: t > thr)
    equal = _count(key_ref, tiles, tw, lambda t, at: t == thr)
    owed = k - above          # of the slots AT the threshold, the lowest
    cut_ref[...] = jnp.full(cut_ref.shape, width, jnp.int32)
    kept_ref[...] = above + equal

    @pl.when(jnp.max(equal - owed) > 0)
    def _ties():
        bits = max(1, (width - 1).bit_length())

        def bit(i, cut):
            cand = cut | (jnp.int32(1) << (bits - 1 - i))
            below = _count(
                key_ref, tiles, tw, lambda t, at: (t == thr) & (
                    at + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
                    < cand))
            return jnp.where(below < owed, cand, cut)

        # the largest slot with fewer than `owed` threshold slots before it
        # IS the `owed`-th of them
        cut = jax.lax.fori_loop(0, bits, bit,
                                jnp.zeros(cut_ref.shape, jnp.int32))
        cut_ref[...] = cut
        kept_ref[...] = above + _count(
            key_ref, tiles, tw, lambda t, at: (t == thr) & (
                at + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) <= cut))
    return thr


def _write_bias(bias_ref, key_ref, cut_ref, thr, tiles, tw, nk):
    """`bias_ref` (R, W): 0 at the kept slots, `NEG_INF` elsewhere (and in
    every tile past the live ones)."""
    cut = cut_ref[...]

    def tile(j, _):
        at = pl.multiple_of(j * tw, tw)
        keys = key_ref[:, pl.ds(at, tw)]
        slot = at + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        kept = (j < tiles) & ((keys > thr) | ((keys == thr) & (slot <= cut)))
        bias_ref[:, pl.ds(at, tw)] = jnp.where(kept, 0.0, NEG_INF).astype(
            bias_ref.dtype)
        return _

    jax.lax.fori_loop(0, nk, tile, None)


def _rows_vector(values, rows: int):
    """(rows, 1) int32 from `rows` scalars."""
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return functools.reduce(
        jnp.add, [jnp.where(at == r, v, 0) for r, v in enumerate(values)])


# ------------------------------------------------- decode: scores and choice


def _select_kernel(lengths_ref, layer_ref, q_ref, w_ref, new_ref, k_ref,
                   bias_ref, kept_ref, key_scr, cut_scr, *, tk, nk, rb, topk):
    del layer_ref  # the index maps read it
    i, j = pl.program_id(0), pl.program_id(1)
    lengths = [lengths_ref[i * rb + r] for r in range(rb)]
    longest = functools.reduce(jnp.maximum, lengths)

    @pl.when(j * tk < longest)
    def _scores():
        cols = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        slots = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
        for r in range(rb):
            # the step's own key is staged: it takes its slot's place
            k = jnp.where(slots == lengths[r] - 1, new_ref[r], k_ref[r])
            s = jax.lax.dot_general(q_ref[r], k, _NT,
                                    preferred_element_type=F32)   # (Hi, tk)
            score = jnp.sum(w_ref[r] * _relu(s), axis=0, keepdims=True)
            key_scr[r:r + 1, pl.ds(pl.multiple_of(j * tk, tk), tk)] = \
                jnp.where(cols < lengths[r], sort_key(score), INT_MIN)

    @pl.when(j == nk - 1)
    def _choice():
        tiles = (longest + tk - 1) // tk
        k = _rows_vector([jnp.minimum(n, topk) for n in lengths], rb)
        thr = _choose(key_scr, cut_scr, kept_ref, tiles, tk, k)
        _write_bias(bias_ref, key_scr, cut_scr, thr, tiles, tk, nk)


def sparse_index_select(q: jnp.ndarray, w: jnp.ndarray, stack: jnp.ndarray,
                        layer, lengths: jnp.ndarray, topk: int,
                        new: jnp.ndarray):
    """A decode step's choice. q (B, Hi, Di) the index queries, rotated; w
    (B, Hi) the heads' weights; stack (L, B, 1, M, Di) the index keys and
    `layer` the layer to read; lengths (B,) live slots a row, the LAST of
    them the step's own token, whose key `new` (B, Di) is staged. Returns the
    bias (B, M) float32, 0 at the `min(topk, length)` slots of largest
    score and `NEG_INF` elsewhere, and (B,) int32, the slots it kept a row
    as the choice's own passes counted them."""
    b, hi, di = q.shape
    m = stack.shape[3]
    tk = block_of(m, SELECT_BLOCK)
    nk = m // tk
    rb = SELECT_ROWS if b % SELECT_ROWS == 0 else b
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)

    def rows(i, j, L, Ly):
        return (i, 0, 0)

    def keys(i, j, L, Ly):
        longest = functools.reduce(jnp.maximum,
                                   [L[i * rb + r] for r in range(rb)])
        last = jnp.maximum((longest + tk - 1) // tk - 1, 0)
        return (Ly[0], i, 0, jnp.minimum(j, last), 0)

    bias, kept = pl.pallas_call(
        functools.partial(_select_kernel, tk=tk, nk=nk, rb=rb, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b // rb, nk),
            in_specs=[pl.BlockSpec((rb, hi, di), rows),
                      pl.BlockSpec((rb, hi, 1), rows),
                      pl.BlockSpec((rb, 1, di), rows),
                      pl.BlockSpec((None, rb, None, tk, di), keys)],
            out_specs=[pl.BlockSpec((rb, m), lambda i, j, L, Ly: (i, 0)),
                       pl.BlockSpec((rb, 1), lambda i, j, L, Ly: (i, 0))],
            scratch_shapes=[pltpu.VMEM((rb, m), jnp.int32),
                            pltpu.VMEM((rb, 1), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((b, m), F32),
                   jax.ShapeDtypeStruct((b, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=SELECT_NAME,
    )(lengths, jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(stack.dtype), w.astype(F32)[..., None],
      new.astype(stack.dtype)[:, None], stack)
    return bias, kept[:, 0]


def index_scores(q, w, keys):
    """`I` in plain `jax.numpy`: q (..., Q, Hi, Di), w (..., Q, Hi), keys
    (..., M, Di) -> (..., Q, M) float32. The operands are rounded to the
    keys' type, as the kernels' are."""
    dt = keys.dtype
    s = jnp.einsum("...qhd,...md->...qhm", q.astype(dt).astype(F32),
                   keys.astype(F32), precision="highest")
    return jnp.einsum("...qh,...qhm->...qm", w.astype(F32), _relu(s),
                      precision="highest")


def chosen(scores, live, topk: int):
    """The choice as `jax.lax.top_k` makes it: scores (..., M) float32, live
    (..., M) bool the slots a query may see -> (..., M) bool, the `min(topk,
    live slots)` of largest score, ties to the lower slot."""
    m = scores.shape[-1]
    k = min(topk, m)
    _, idx = jax.lax.top_k(jnp.where(live, scores + 0.0, -jnp.inf), k)
    owed = jnp.minimum(jnp.sum(live, axis=-1, keepdims=True), topk)
    ranked = jax.lax.broadcasted_iota(jnp.int32, idx.shape, idx.ndim - 1)
    # a (..., k, M) comparison would be 2,048 x 33,280 a query: scatter
    flat = idx.reshape(-1, k)
    kept = jnp.zeros((flat.shape[0], m), bool).at[
        jnp.arange(flat.shape[0])[:, None], flat].set(
            (ranked < owed).reshape(-1, k))
    return kept.reshape(scores.shape)


def choice_plain(q_index, w, index_keys, positions, topk: int):
    """A chunk's choice in plain `jax.numpy`: the queries q_index (C, Hi,
    Di), w (C, Hi) at `positions` (C,) against one sequence's index keys (M,
    Di), whose slot IS the position. Returns the bias (C, M) float32, 0 at
    the kept slots and `NEG_INF` elsewhere, and (C,) int32, the slots each
    query kept."""
    live = jnp.arange(index_keys.shape[0])[None, :] <= positions[:, None]
    kept = chosen(index_scores(q_index, w, index_keys), live, topk)
    return jnp.where(kept, 0.0, NEG_INF).astype(F32), \
        jnp.sum(kept, axis=-1, dtype=jnp.int32)


def _layer_of(stack, layer):
    return jax.lax.dynamic_index_in_dim(stack, jnp.asarray(layer, jnp.int32),
                                        0, keepdims=False)


def row_of(stack, layer, row):
    """Sequence `row`'s slab of layer `layer` of a stack (L, B, heads, M,
    width): (heads, M, width)."""
    return jax.lax.dynamic_index_in_dim(
        _layer_of(stack, layer), jnp.asarray(row, jnp.int32), 0,
        keepdims=False)


def sparse_index_select_reference(q, w, stack, layer, lengths, topk, new):
    """The same in plain `jax.numpy` with `jax.lax.top_k` (tests,
    `chip_smoke`, and the model's own path off the chip)."""
    b = q.shape[0]
    m = stack.shape[3]
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)
    keys = _layer_of(stack, layer)[:, 0]                       # (B, M, Di)
    keys = keys.at[jnp.arange(b), lengths - 1].set(new.astype(keys.dtype),
                                                   mode="drop")
    scores = index_scores(q[:, None], w[:, None], keys)[:, 0]
    live = jnp.arange(m)[None, :] < lengths[:, None]
    kept = chosen(scores, live, topk)
    return jnp.where(kept, 0.0, NEG_INF).astype(F32), \
        jnp.sum(kept, axis=-1, dtype=jnp.int32)


# --------------------------------------- decode: attention under the choice


def _decode_kernel(lengths_ref, layer_ref, q_ref, kn_ref, vn_ref, bias_ref,
                   k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, tk,
                   nk, hkv):
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * tk < length)
    def _block():
        bias = bias_ref[...]                                    # (1, tk)
        kept = bias > -1.0
        hit = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0) \
            == length - 1

        def head(g, _):
            k = jnp.where(hit, kn_ref[g], k_ref[g])             # (tk, D)
            v = jnp.where(hit, vn_ref[g], v_ref[g])
            s = jax.lax.dot_general(q_ref[g], k, _NT,
                                    preferred_element_type=F32) * scale + bias
            m_prev = m_scr[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a head with no kept slot yet has m = NEG_INF, where exp(s - m)
            # is 1 in every masked column: zeros, not probabilities
            p = jnp.where(kept, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g, :, :1] = l_scr[g][:, :1] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            m_scr[g, :, :1] = m_new
            return _

        jax.lax.fori_loop(0, hkv, head, None, unroll=True)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def sparse_attn_decode(q: jnp.ndarray, k_stack: jnp.ndarray,
                       v_stack: jnp.ndarray, layer, lengths: jnp.ndarray,
                       bias: jnp.ndarray, softmax_scale: float,
                       k_new: jnp.ndarray, v_new: jnp.ndarray) -> jnp.ndarray:
    """One decode step's attention over the CHOSEN slots. q (B, H, D); the
    stacks (L, B, Hkv, M, D) and `layer`; lengths (B,) live slots, the last
    the step's own token, staged as `k_new`/`v_new` (B, Hkv, D); bias (B, M)
    from `sparse_index_select`, 0 at the chosen slots. Returns (B, H, D)."""
    b, h, d = q.shape
    hkv, m = k_stack.shape[2], k_stack.shape[3]
    n_rep = h // hkv
    tk = block_of(m, DECODE_BLOCK)
    nk = m // tk
    lengths = jnp.minimum(lengths.astype(jnp.int32), m)

    def row(b_, j, L, Ly):
        return (b_, 0, 0)

    def block(b_, j, L):
        return jnp.minimum(j, jnp.maximum((L[b_] + tk - 1) // tk - 1, 0))

    kv = pl.BlockSpec((None, None, hkv, tk, d),
                      lambda b_, j, L, Ly: (Ly[0], b_, 0, block(b_, j, L), 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=softmax_scale, tk=tk, nk=nk,
                          hkv=hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nk),
            in_specs=[pl.BlockSpec((hkv, n_rep, d), row),
                      pl.BlockSpec((hkv, 1, d), row),
                      pl.BlockSpec((hkv, 1, d), row),
                      pl.BlockSpec((None, 1, tk),
                                   lambda b_, j, L, Ly: (b_, 0, block(b_, j, L))),
                      kv, kv],
            out_specs=pl.BlockSpec((hkv, n_rep, d), row),
            scratch_shapes=[pltpu.VMEM((hkv, n_rep, _LANES), F32),
                            pltpu.VMEM((hkv, n_rep, _LANES), F32),
                            pltpu.VMEM((hkv, n_rep, d), F32)]),
        out_shape=jax.ShapeDtypeStruct((b * hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=DECODE_NAME,
    )(lengths, jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b * hkv, n_rep, d),
      k_new.astype(k_stack.dtype).reshape(b * hkv, 1, d),
      v_new.astype(v_stack.dtype).reshape(b * hkv, 1, d),
      bias.reshape(b, 1, m), k_stack, v_stack)
    return out.reshape(b, h, d)


def attend_chosen(q, k, v, bias, scale):
    """Softmax attention under a bias, plain `jax.numpy`, float32: q (..., Q,
    H, D), k/v (..., Hkv, M, D), bias (..., Q, M) -> (..., Q, H, D)."""
    hkv = k.shape[-3]
    lead, (nq, h, d) = q.shape[:-3], q.shape[-3:]
    qg = q.astype(F32).reshape(lead + (nq, hkv, h // hkv, d))
    s = jnp.einsum("...qgrd,...gmd->...grqm", qg, k.astype(F32),
                   precision="highest") * scale + bias[..., None, None, :, :]
    o = jnp.einsum("...grqm,...gmd->...qgrd", jax.nn.softmax(s, axis=-1),
                   v.astype(F32), precision="highest")
    return o.reshape(lead + (nq, h, d))


def sparse_attn_decode_reference(q, k_stack, v_stack, layer, lengths, bias,
                                 softmax_scale, k_new, v_new):
    """The same in plain `jax.numpy`, float32."""
    b = q.shape[0]
    m = k_stack.shape[3]
    at = jnp.minimum(lengths.astype(jnp.int32), m) - 1
    k, v = (_layer_of(stack, layer).at[jnp.arange(b), :, at].set(
        new.astype(stack.dtype), mode="drop")
        for stack, new in ((k_stack, k_new), (v_stack, v_new)))
    # the kernel's operands are the cache's type: round the query as it does
    o = attend_chosen(q.astype(k_stack.dtype)[:, None], k, v, bias[:, None],
                      softmax_scale)
    return o[:, 0].astype(q.dtype)


# ---------------------------------------------------------------- prefill


def _prefill_select_kernel(start_ref, layer_ref, row_ref, q_ref, w_ref, k_ref,
                           bias_ref, kept_ref, key_scr, cut_scr, *, tq, tk, nk,
                           topk):
    del layer_ref, row_ref
    i, j = pl.program_id(0), pl.program_id(1)
    first = start_ref[0] + i * tq           # the tile's first query's position
    tiles = (first + tq - 1) // tk + 1      # tiles that hold a key it may see
    heads = q_ref.shape[0]

    @pl.when(j < tiles)
    def _scores():
        k = k_ref[...]                                          # (tk, Di)

        def head(n, acc):
            s = jax.lax.dot_general(q_ref[n], k, _NT,
                                    preferred_element_type=F32)  # (tq, tk)
            return acc + w_ref[n] * _relu(s)

        score = jax.lax.fori_loop(0, heads, head, jnp.zeros((tq, tk), F32),
                                  unroll=True)
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        key_scr[:, pl.ds(pl.multiple_of(j * tk, tk), tk)] = jnp.where(
            kpos <= qpos, sort_key(score), INT_MIN)

    @pl.when(j == nk - 1)
    def _choice():
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        thr = _choose(key_scr, cut_scr, kept_ref, tiles, tk,
                      jnp.minimum(qpos + 1, topk))
        _write_bias(bias_ref, key_scr, cut_scr, thr, tiles, tk, nk)


def _prefill_kernel(start_ref, layer_ref, row_ref, q_ref, bias_ref, k_ref,
                    v_ref, o_ref, m_scr, l_scr, acc_scr, *, tq, tk, nk, n_rep):
    """One (query tile, kv tile) step: every KV head's GROUP of `n_rep` query
    heads as ONE (n_rep * tq, D) operand against the head's K and V tiles
    (a head at a time paid the K tile's way into the MXU `n_rep` times and
    its own state's round trip a tile: 19.6 ms a chunk against 12.7 at twice
    the tile; PERF.md, PR 51)."""
    del layer_ref, row_ref
    i, j = pl.program_id(0), pl.program_id(1)
    hkv = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * tk <= start_ref[0] + (i + 1) * tq - 1)
    def _block():
        bias = bias_ref[...].astype(F32)                        # (tq, tk)

        def group(g, _):
            # the queries come scaled. No `where` on the probabilities: a
            # query whose tiles so far hold no kept slot has m = NEG_INF and
            # gathers ones, and its first kept slot (every query keeps its
            # `min(topk, t + 1)` >= 1) wipes them with alpha = exp(-1e30) = 0
            s = jax.lax.dot_general(q_ref[g], k_ref[g], _NT,
                                    preferred_element_type=F32)
            s = (s.reshape(n_rep, tq, tk) + bias[None]).reshape(n_rep * tq, tk)
            m_prev = m_scr[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g, :, :1] = l_scr[g][:, :1] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            m_scr[g, :, :1] = m_new
            return _

        jax.lax.fori_loop(0, hkv, group, None)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _scalar(x):
    return jnp.asarray(x, jnp.int32).reshape(1)


def _live_tile(tq: int, tk: int):
    """The kv tile a prefill step fetches: clamped to the last one that
    holds a key the query tile may see."""
    def block(i, j, St):
        return jnp.minimum(j, (St[0] + (i + 1) * tq - 1) // tk)
    return block


def sparse_prefill_choice(q_index: jnp.ndarray, w: jnp.ndarray,
                          index_stack: jnp.ndarray, layer, row, start,
                          topk: int):
    """The choice of a chunk of ONE row's queries: q_index (C, Hi, Di), w (C,
    Hi) at positions `start .. start + C - 1` of sequence `row`, against that
    row's index keys in `index_stack` (L, B, 1, M, Di), which already hold
    the chunk's own. Returns the bias (C, M) in the stack's type: 0 at each
    query's `min(topk, position + 1)` slots of largest score up to its own
    position, `NEG_INF` elsewhere, and (C,) int32, the slots each query
    kept as the choice's own passes counted them."""
    c, hi, di = q_index.shape
    m = index_stack.shape[3]
    tq, tk = block_of(c, SELECT_QUERIES), block_of(m, CHOICE_BLOCK)
    nk = m // tk
    block = _live_tile(tq, tk)
    bias, kept = pl.pallas_call(
        functools.partial(_prefill_select_kernel, tq=tq, tk=tk, nk=nk,
                          topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c // tq, nk),
            in_specs=[pl.BlockSpec((hi, tq, di),
                                   lambda i, j, St, Ly, Rw: (0, i, 0)),
                      pl.BlockSpec((hi, tq, 1),
                                   lambda i, j, St, Ly, Rw: (0, i, 0)),
                      pl.BlockSpec((None, None, None, tk, di),
                                   lambda i, j, St, Ly, Rw: (
                                       Ly[0], Rw[0], 0, block(i, j, St), 0))],
            out_specs=[
                pl.BlockSpec((tq, m), lambda i, j, St, Ly, Rw: (i, 0)),
                pl.BlockSpec((tq, 1), lambda i, j, St, Ly, Rw: (i, 0))],
            scratch_shapes=[pltpu.VMEM((tq, m), jnp.int32),
                            pltpu.VMEM((tq, 1), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((c, m), index_stack.dtype),
                   jax.ShapeDtypeStruct((c, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the key scratch and the double-buffered bias block, each a
            # tile of queries by the whole row: 34 MB at 128 x 33,280
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
        name=PREFILL_SELECT_NAME,
    )(_scalar(start), _scalar(layer), _scalar(row),
      jnp.swapaxes(q_index, 0, 1).astype(index_stack.dtype),
      jnp.swapaxes(w, 0, 1).astype(F32)[..., None], index_stack)
    return bias, kept[:, 0]


def sparse_prefill_attend(q: jnp.ndarray, bias: jnp.ndarray,
                          k_stack: jnp.ndarray, v_stack: jnp.ndarray, layer,
                          row, start, softmax_scale: float) -> jnp.ndarray:
    """Attention of a chunk of ONE row's queries q (C, H, D), at positions
    `start ..` of sequence `row`, over that row's K and V in the stacks (L,
    B, Hkv, M, D) under `bias` (C, M) from `sparse_prefill_choice`: a flash
    pass over the tiles up to the chunk's causal edge. Returns (C, H, D)."""
    c, h, d = q.shape
    hkv, m = k_stack.shape[2], k_stack.shape[3]
    n_rep = h // hkv
    tq, tk = block_of(c, PREFILL_QUERIES), block_of(m, PREFILL_BLOCK)
    nq, nk = c // tq, m // tk
    block = _live_tile(tq, tk)
    kv = pl.BlockSpec((None, None, hkv, tk, d),
                      lambda i, j, St, Ly, Rw: (Ly[0], Rw[0], 0,
                                                block(i, j, St), 0))
    # a query tile's rows a KV head: its n_rep query heads one after another
    groups = pl.BlockSpec((hkv, None, n_rep * tq, d),
                          lambda i, j, St, Ly, Rw: (0, i, 0, 0))

    def grouped(t):             # (C, H, D) -> (Hkv, nq, n_rep * tq, D)
        t = t.reshape(nq, tq, hkv, n_rep, d)
        return jnp.transpose(t, (2, 0, 3, 1, 4)).reshape(hkv, nq, n_rep * tq,
                                                         d)

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, tq=tq, tk=tk, nk=nk, n_rep=n_rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nq, nk),
            in_specs=[groups,
                      pl.BlockSpec((tq, tk), lambda i, j, St, Ly, Rw: (
                          i, block(i, j, St))),
                      kv, kv],
            out_specs=groups,
            scratch_shapes=[pltpu.VMEM((hkv, n_rep * tq, _LANES), F32),
                            pltpu.VMEM((hkv, n_rep * tq, _LANES), F32),
                            pltpu.VMEM((hkv, n_rep * tq, d), F32)]),
        out_shape=jax.ShapeDtypeStruct((hkv, nq, n_rep * tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=_interpret(),
        name=PREFILL_NAME,
    )(_scalar(start), _scalar(layer), _scalar(row),
      grouped((q * softmax_scale).astype(k_stack.dtype)), bias, k_stack,
      v_stack)
    out = out.reshape(hkv, nq, n_rep, tq, d)
    return jnp.transpose(out, (1, 3, 0, 2, 4)).reshape(c, h, d)


def sparse_attn_prefill(q: jnp.ndarray, q_index: jnp.ndarray, w: jnp.ndarray,
                        k_stack: jnp.ndarray, v_stack: jnp.ndarray,
                        index_stack: jnp.ndarray, layer, row, start,
                        topk: int, softmax_scale: float):
    """A chunk of ONE row's queries against that row's cache, which already
    holds the chunk's own tokens: q (C, H, D), q_index (C, Hi, Di), w (C, Hi)
    for the queries at positions `start .. start + C - 1` of sequence `row`;
    the stacks (L, B, Hkv, M, D) and (L, B, 1, M, Di), and `layer`. Each
    query keeps the `topk` positions up to its own of largest index score
    (`sparse_prefill_choice`) and attends those (`sparse_prefill_attend`).
    Returns (C, H, D) and (C,) int32, the slots each query kept."""
    with jax.named_scope("choose"):   # for the program map (docs/telemetry.md)
        bias, kept = sparse_prefill_choice(q_index, w, index_stack, layer,
                                           row, start, topk)
    return sparse_prefill_attend(q, bias, k_stack, v_stack, layer, row, start,
                                 softmax_scale), kept


def sparse_attention_plain(q, q_index, w, k, v, index_keys, positions,
                           topk: int, softmax_scale: float):
    """The layer's attention in plain `jax.numpy` with `jax.lax.top_k`: the
    queries q (C, H, D), q_index (C, Hi, Di), w (C, Hi) at `positions` (C,)
    against one sequence's k/v (Hkv, M, D) and index keys (M, Di), whose
    slot IS the position. Returns (C, H, D) float32 and (C,) int32, the
    slots each query kept."""
    m = k.shape[1]
    live = jnp.arange(m)[None, :] <= positions[:, None]
    kept = chosen(index_scores(q_index, w, index_keys), live, topk)
    return attend_chosen(q.astype(k.dtype), k, v,
                         jnp.where(kept, 0.0, NEG_INF), softmax_scale), \
        jnp.sum(kept, axis=-1, dtype=jnp.int32)


def sparse_attn_prefill_reference(q, q_index, w, k_stack, v_stack,
                                  index_stack, layer, row, start, topk,
                                  softmax_scale):
    """`sparse_attn_prefill` in plain `jax.numpy`, float32."""
    of = functools.partial(row_of, layer=layer, row=row)
    positions = jnp.asarray(start, jnp.int32) + jnp.arange(q.shape[0])
    o, kept = sparse_attention_plain(
        q, q_index, w, of(k_stack), of(v_stack), of(index_stack)[0],
        positions, topk, softmax_scale)
    return o.astype(q.dtype), kept
