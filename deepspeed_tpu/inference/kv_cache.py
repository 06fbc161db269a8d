"""Static-shape KV cache for autoregressive decode.

Fills the role of the reference's inference workspace / KV-cache management
(`csrc/transformer/inference/includes/inference_context.h`,
`csrc/transformer/inference/csrc/transform.cu:727` — the `softmax_context`
KV insert) — TPU-first: the cache is a pytree of fixed-shape arrays carried
through jit, inserts are `lax.dynamic_update_slice_in_dim`, and validity is a
position mask instead of a dynamic length. Static shapes keep XLA happy; the
mask costs nothing against HBM-bound decode.

Two layouts of the dense cache (docs/kv_cache.md):

- the PER-LAYER VIEW, (num_layers, batch, max_seq_len, kv_heads, head_dim):
  the layer axis lines up with `nn.scan`'s stacked block parameters, so a
  layer's cache is a scanned input/output of the block scan, a bare
  (B, M, Hkv, D) array (or a `QuantizedKVLayer`) inside it;
- the STACKED VIEW (`KVCache.create_stacked`, `DenseLayer`),
  (num_layers, batch, kv_heads, max_seq_len, head_dim), the decode kernel's
  own order: one buffer from a v1 program's first decode step to its last,
  which a layer addresses by index (`scan_dense_layers`) and a decode step
  writes one token a row into (`KVCache.land`).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct


def quantize_kv_tokens(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization of KV rows: one f32 scale per (token,
    kv-head) over the head dim — `(..., D) -> ((..., D) int8, (...) f32)`.

    Same convention as `ops.quantization.quantize_int8_blockwise` (scale =
    amax/127, 1.0 where the row is all-zero, clip to ±127) but with the
    group fixed to the head dim: every cache write touches only its own
    scale entry, so incremental appends never re-quantize neighbours and
    the staged-append batched scatter stays one scatter per pool."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(data: jnp.ndarray, scales: jnp.ndarray,
                  dtype: Any = jnp.float32) -> jnp.ndarray:
    """`(..., D) int8 × (...) f32 -> (..., D)` — the XLA fallback dequant
    (CPU tests, prefill chunks, masked families). The Pallas kernels never
    call this: they fold the scales into logits/probs in-register
    (`ops/pallas/paged_attention.py`), so the dense form this returns only
    ever exists as a per-layer transient on the non-kernel path."""
    return (data.astype(jnp.float32) * scales[..., None]).astype(dtype)


@struct.dataclass
class QuantizedKVLayer:
    """int8-at-rest form of one dense cache tensor (K or V): the int8 rows
    plus their per-(token, kv-head) f32 scales. Scales ride the pytree with
    the same leading axes as the data — stacked (L, B, M, Hkv) beside
    (L, B, M, Hkv, D) — so `nn.scan` slices both per layer exactly like the
    weight stacks, and the model zoo stays layout-agnostic (`update_layer`
    and `cached_attention` dispatch on the type)."""

    data: jnp.ndarray    # (..., M, Hkv, D) int8
    scales: jnp.ndarray  # (..., M, Hkv) f32

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype


@struct.dataclass
class DenseLayer:
    """The stacked dense cache tensor (K or V) where it lies,
    (L, B, Hkv, M, D), and with `layer` set one layer's view of it BY INDEX:
    the dense twin of `PagedLayer` with `layer=`. `update_layer` and
    `ops.attention.cached_attention` dispatch on the type: a write lands at
    `[layer, b, :, slot]` of the stack, the decode kernel fetches block
    `(layer, b, g, j)` of it, and nothing cuts a layer out of the stack or
    re-lays it (the axis order is the kernel's).

    `staged` (static): a single-token `update_layer` does not write; it
    parks the new K/V in `stage` (B, Hkv, D), attention puts it in its
    slot's place (in the tile the kernel fetched), and `KVCache.land` writes
    every layer's staged token with ONE write a step.

    `ring` (static): the view is of a RING (`KVCache.ring`): its M slots hold
    the last M positions, position p in slot p mod M. A softmax reader
    (`ops.attention.cached_attention`) is handed the cursors as for any
    cache and makes of them the two things a ring's reader needs: the COUNT
    of live slots, `min(index + 1, M)`, and the staged token's slot, `index
    mod M` (docs/kv_cache.md, "A ring's contract")."""

    stack: jnp.ndarray                      # (L, B, Hkv, M, D)
    layer: Optional[jnp.ndarray] = None     # () int32; None: the cache at rest
    stage: Optional[jnp.ndarray] = None     # (B, Hkv, D) this layer's new token
    staged: bool = struct.field(pytree_node=False, default=False)
    ring: bool = struct.field(pytree_node=False, default=False)


@struct.dataclass
class KVCache:
    """Per-model KV cache: stacked per-layer K/V plus per-sequence cursors.

    `index` (B,) is the number of valid tokens cached per sequence — rows
    advance independently, which is what lets the v2 engine run continuous
    batching (sequences join/leave/decode at different lengths) over one
    static-shape buffer.
    """

    # (L, B, M, Hkv, D) array or QuantizedKVLayer: the per-layer view; or a
    # DenseLayer over (L, B, Hkv, M, D): the stacked view
    k: Any
    v: Any
    index: jnp.ndarray  # (B,) int32
    # a stacked cache of WINDOW layers (static): `max_len` slots a row hold the
    # last `max_len` positions, position p in slot p mod max_len. A key's
    # place in the ring says nothing of its position: its reader must not
    # ask (no mask or bias by slot; docs/kv_cache.md)
    ring: bool = struct.field(pytree_node=False, default=False)

    @property
    def stacked(self) -> bool:
        return isinstance(self.k, DenseLayer)

    @property
    def max_len(self) -> int:
        return self.k.stack.shape[3] if self.stacked else self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return isinstance(self.k, QuantizedKVLayer)

    @classmethod
    def create(cls, num_layers: int, batch: int, max_len: int, kv_heads: int,
               head_dim: int, dtype: Any = jnp.bfloat16,
               quantized: bool = False) -> "KVCache":
        shape = (num_layers, batch, max_len, kv_heads, head_dim)
        if quantized:
            def side():
                return QuantizedKVLayer(
                    data=jnp.zeros(shape, jnp.int8),
                    scales=jnp.ones(shape[:-1], jnp.float32))
            return cls(k=side(), v=side(),
                       index=jnp.zeros((batch,), jnp.int32))
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   index=jnp.zeros((batch,), jnp.int32))

    @classmethod
    def create_stacked(cls, num_layers: int, batch: int, max_len: int,
                       kv_heads: int, head_dim: int,
                       dtype: Any = jnp.bfloat16,
                       ring: bool = False) -> "KVCache":
        """The cache in the stacked view (`DenseLayer`): for a model whose
        cached layers take `layer_views` of it (`scan_dense_layers`). With
        `ring`, `max_len` is the window: the slots a row keeps."""
        shape = (num_layers, batch, kv_heads, max_len, head_dim)
        return cls(k=DenseLayer(jnp.zeros(shape, dtype)),
                   v=DenseLayer(jnp.zeros(shape, dtype)),
                   index=jnp.zeros((batch,), jnp.int32), ring=ring)

    def layer_views(self, layer, staged: bool) -> Tuple[DenseLayer, DenseLayer]:
        """Layer `layer`'s `(k, v)` views of a stacked cache, for
        `update_layer` and `cached_attention`."""
        return (DenseLayer(self.k.stack, layer, staged=staged, ring=self.ring),
                DenseLayer(self.v.stack, layer, staged=staged, ring=self.ring))

    def write_prefill(self, layer, k_new: jnp.ndarray, v_new: jnp.ndarray,
                      row=None, start=0) -> "KVCache":
        """A stacked cache with layer `layer`'s K and V of a prefill FROM
        THE EMPTY CACHE, `k_new`/`v_new` (B, S, Hkv, D), the tokens of
        positions 0 .. S - 1 of every row (`write_prefill_rows`: a ring
        keeps its last `max_len`). With `row` (may be traced): a CHUNK of
        that one sequence (B == 1), positions `start .. start + S - 1`
        (full-length rows only; a prefill that walks a row a chunk at a
        time)."""
        k, v = (write_prefill_rows(side.stack, layer, new, self.ring, row,
                                   start)
                for side, new in ((self.k, k_new), (self.v, v_new)))
        return self.replace(k=DenseLayer(k), v=DenseLayer(v))

    def land(self, k_new: jnp.ndarray, v_new: jnp.ndarray) -> "KVCache":
        """A stacked cache with every layer's staged token, `k_new`/`v_new`
        (L, B, Hkv, D), written at the cursors `[:, b, :, index[b]]` (a ring:
        `index[b] mod max_len`): the one write of a decode step. A row whose
        cursor is at or past `max_len` (parked) is dropped. On the chip it goes through
        `kv_write_dense`, which aliases the stacks and keeps the tiling the
        decode kernel reads (`_pool_writer` tells why no XLA scatter)."""
        write = _dense_writer(self.k.stack.shape[2])
        # a ring keeps position p in slot p mod its length
        at = self.index % self.max_len if self.ring else self.index
        if write is not None:
            k, v = write(self.k.stack, self.v.stack, k_new, v_new, at)
        else:
            rows = jnp.arange(self.index.shape[0])
            # an index [:, rows, :, slot] puts the rows' axis first
            k, v = (stack.at[:, rows, :, at].set(
                jnp.moveaxis(new, 1, 0).astype(stack.dtype), mode="drop")
                for stack, new in ((self.k.stack, k_new),
                                   (self.v.stack, v_new)))
        return self.replace(k=DenseLayer(k), v=DenseLayer(v))

    def apply_stage(self) -> "KVCache":
        """Uniform surface with `PagedKVCache` (dense rows write in place;
        a stacked cache's staged tokens land inside the model's step)."""
        return self

    def truncate(self, index: jnp.ndarray) -> "KVCache":
        """Roll the per-row cursors back to `index` (B,) — stage
        truncation. The dense cache's cursor semantics make everything past
        `index` uncommitted by construction: `decode_mask` never lets a
        query attend past its own position, and the next `update_layer`
        write lands at the cursor, overwriting the abandoned region before
        anything can see it. Speculative decoding leans on exactly this —
        the k+1 verify forward writes the drafted window beyond the
        committed cursor, and acceptance commits a prefix of it by rolling
        the cursor to `committed + accepted + 1`; rejected tokens never
        become attendable. jit-safe (index replacement, no data movement)."""
        return self.replace(index=jnp.asarray(index, jnp.int32))


def write_prefill_rows(stack, layer, new, ring: bool, row=None, start=0):
    """`new` (B, S, G, W), the tokens of positions 0 .. S - 1, into layer
    `layer` of the stacked cache `(L, B, G, M, W)`, which held nothing:
    positions as slots, or for a ring its last M tokens, position p in slot
    p mod M. One dynamic-update-slice: it keeps the stack's tiling. With
    `row`: `new` (1, S, G, W) is sequence `row`'s positions `start ..`."""
    m = stack.shape[3]
    s = new.shape[1]
    new = jnp.swapaxes(new, 1, 2).astype(stack.dtype)        # (B, G, S, W)
    if row is not None:
        if ring:
            raise ValueError("a ring is written whole rows at a time")
        return jax.lax.dynamic_update_slice(stack, new[None],
                                            (layer, row, 0, start, 0))
    if ring and s > m:
        new = jnp.roll(new[:, :, s - m:], (s - m) % m, axis=2)
    return jax.lax.dynamic_update_slice(stack, new[None], (layer, 0, 0, 0, 0))


@struct.dataclass
class RecurrentState:
    """What the recurrent (state-space) layers of a model keep between
    tokens, stacked over THOSE layers only (docs/kv_cache.md): a fixed size a
    sequence, whatever its length.

    `ssm` (Lm, B, *state_shape) float32, the shape of one sequence's state
    being the family's: a Mamba-2 head's state is `P x N`, so `(H, P, N)`
    (`ops/pallas/ssm.ssm_state_update`); Mamba-1 decays every (channel,
    state) element on its own, `(N, C)` with the channels on the lanes
    (`ops/pallas/ssm.ssm_state_update_m1`). It is float32 at rest: the
    recurrence multiplies it by a decay just under 1 and adds a small term
    every token, and bf16's 8 bits lose that term over a few hundred steps.
    The kernel reads and writes one layer of it in place a decode step;
    prefill writes a layer's slab whole.
    `conv` (Lm, B, K - 1, C): the last K - 1 inputs of the causal depthwise
    convolution, in the compute dtype."""

    ssm: jnp.ndarray
    conv: jnp.ndarray

    @classmethod
    def create(cls, num_layers: int, batch: int, state_shape: Tuple[int, ...],
               conv_kernel: int, conv_dim: int,
               dtype: Any = jnp.bfloat16) -> "RecurrentState":
        return cls(
            ssm=jnp.zeros((num_layers, batch) + tuple(state_shape),
                          jnp.float32),
            conv=jnp.zeros((num_layers, batch, conv_kernel - 1, conv_dim),
                           dtype))

    @staticmethod
    def nbytes(num_layers: int, batch: int, state_shape: Tuple[int, ...],
               conv_kernel: int, conv_dim: int,
               dtype: Any = jnp.bfloat16) -> int:
        """Bytes `create` would hold: host arithmetic for the telemetry and
        the serve-mode accounting."""
        return num_layers * batch * (
            math.prod(state_shape) * 4
            + (conv_kernel - 1) * conv_dim * jnp.dtype(dtype).itemsize)


@struct.dataclass
class LatentCache:
    """What latent-attention (MLA) layers keep, stacked over THOSE layers:
    ONE array a token a layer that all heads share, the normalised KV latent
    and the rotated rope key side by side, `(L, B, 1, M, rank + rope)`
    (docs/kv_cache.md): the stacked dense layout with one "head", so it is a
    `DenseLayer` and the dense writer lands it. Keys and values are never
    stored: decode reads the latent through the absorbed projections
    (`ops/pallas/mla.mla_latent_decode`), prefill expands its own tokens."""

    c: DenseLayer                           # stack (L, B, 1, M, W)
    index: jnp.ndarray                      # (B,) int32

    @property
    def max_len(self) -> int:
        return self.c.stack.shape[3]

    @classmethod
    def create(cls, num_layers: int, batch: int, max_len: int, width: int,
               dtype: Any = jnp.bfloat16) -> "LatentCache":
        return cls(c=DenseLayer(jnp.zeros(
            (num_layers, batch, 1, max_len, width), dtype)),
            index=jnp.zeros((batch,), jnp.int32))

    @staticmethod
    def nbytes(num_layers: int, batch: int, max_len: int, width: int,
               dtype: Any = jnp.bfloat16) -> int:
        """Bytes `create` would hold, unpadded (host arithmetic)."""
        return num_layers * batch * max_len * width * jnp.dtype(dtype).itemsize

    def write_rows(self, layer, new: jnp.ndarray) -> "LatentCache":
        """`new` (B, S, W), the tokens of positions 0 .. S - 1 of every row,
        into layer `layer` of the EMPTY cache (a prefill): a dynamic slice
        written whole, which keeps the stack's tiling."""
        stack = self.c.stack
        return self.replace(c=DenseLayer(jax.lax.dynamic_update_slice(
            stack, new.astype(stack.dtype)[None, :, None],
            (layer, 0, 0, 0, 0))))

    def land(self, new: jnp.ndarray) -> "LatentCache":
        """Every layer's staged token, `new` (L, B, W), written at the
        cursors `[:, b, 0, index[b]]`: the one write of a decode step, on the
        chip through `latent_write_dense`, which aliases the stack and keeps
        the tiling the decode kernel reads (`_pool_writer` tells why no XLA
        scatter). A row whose cursor is at or past `max_len` is dropped."""
        from deepspeed_tpu.ops import attention
        stack = self.c.stack
        if attention._one_device_kernel("latent_write_dense"):
            from deepspeed_tpu.ops.pallas.mla import latent_write_dense
            stack = latent_write_dense(stack, new, self.index)
        else:
            rows = jnp.arange(self.index.shape[0])
            stack = stack.at[:, rows, 0, self.index].set(
                new.astype(stack.dtype), mode="drop")
        return self.replace(c=DenseLayer(stack))


@struct.dataclass
class HybridCache:
    """The cache of a model whose layers are of different kinds, by KIND
    (docs/kv_cache.md), one pytree that the decode scan carries:

    - `kv`: a `KVCache` over the layers that keep K and V at FULL length. A
      layer may be the only writer of its slab and later layers read it (a
      shared slab: the readers hold nothing of their own);
    - `window`: a ring `KVCache` (`KVCache.ring`) over the WINDOW layers;
    - `latent`: a `LatentCache` over the latent-attention layers, in K and
      V's place (`kv` None where every attention layer is latent);
    - `index_keys`: a `LatentCache` at the indexer's width BESIDE what the
      attention reads, `kv` (Keye-sparse) or `latent` (DeepSeek-sparse),
      over the same layers and at the same length: the one key a token that
      a learned selection scores the cache by
      (`ops/pallas/sparse_select.py`);
    - `state`: a `RecurrentState` over the recurrent layers.

    A kind the model has no layer of is None. Layers that keep nothing
    (expert, dense FFN, memory unit, a reader of a shared slab) have no row
    in any. The combinations served: `kv` alone or with `window` and `state`;
    `kv` with `window` and no `state` (window and full softmax layers mixed:
    rings of the window's slots beside full-length rows, one cursor a row for
    both); `latent` with `state`; `kv` with `index_keys`; `latent` with
    `index_keys`. The cursors are those of what the attention reads at full
    length (`kv` where there is one, else `latent`), and every kind's,
    `index_keys`' too, are kept equal to them; `index`, `max_len` and
    `replace` read as a `KVCache`'s do, so the engine handles it as it
    handles that."""

    kv: Optional[KVCache]
    state: Optional[RecurrentState] = None
    window: Optional[KVCache] = None
    latent: Optional[LatentCache] = None
    index_keys: Optional[LatentCache] = None

    @property
    def _full(self):
        # what the attention reads at full length; never `index_keys`, which
        # lies beside one of the two and follows its cursors
        return self.kv if self.kv is not None else self.latent

    @property
    def index(self) -> jnp.ndarray:
        return self._full.index

    @property
    def max_len(self) -> int:
        return self._full.max_len

    def _at(self, index) -> "HybridCache":
        return self.replace(**{
            kind: getattr(self, kind).replace(index=index)
            for kind in ("kv", "window", "latent", "index_keys")
            if getattr(self, kind) is not None})

    def advance(self, s: int) -> "HybridCache":
        return self._at(self.index + s)

    def advance_row(self, row, s: int) -> "HybridCache":
        """Sequence `row`'s cursors alone moved on by `s` (`row` may be
        traced): a prefill that walks one row's chunks."""
        return self._at(self.index.at[row].add(s))

    def rows(self, start, count: int) -> "HybridCache":
        """The cache of sequences `start .. start + count - 1` alone (`start`
        may be traced): what a prefill that walks the batch a few rows at a
        time hands the layers. Every buffer of every kind is stacked over
        its layers with the sequences second; the cursors are the only
        leaves of one axis."""
        return jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_slice_in_dim(
                t, start, count, int(t.ndim > 1)), self)

    def with_rows(self, part: "HybridCache", start) -> "HybridCache":
        """This cache with `part` (from `rows`) written back at `start`."""
        return jax.tree_util.tree_map(
            lambda t, new: jax.lax.dynamic_update_slice_in_dim(
                t, new, start, int(t.ndim > 1)), self, part)


@struct.dataclass
class PagedLayer:
    """One layer's view of the block-paged cache: a pool of physical blocks
    plus the per-sequence block tables that map logical positions onto them
    (reference `inference/v2/ragged/blocked_allocator.py` +
    `sequence_descriptor.py` block tables, carried on device).

    As a pytree node this rides `nn.scan` exactly like a dense (B, M, Hkv, D)
    layer cache rides it — models stay layout-agnostic; only `update_layer`
    and `ops.attention.cached_attention` dispatch on the type.

    `stage` (B, Hkv, D) or None: the STAGED-APPEND buffer. With staging on
    (the v2 engine's decode path), a single-token `update_layer` parks the
    new K/V here instead of scattering into the pool — the XLA token
    scatter costs ~0.3 ms *per layer per step* on v5e and dominated decode
    (2·L scatters/step). Attention folds the staged key in (in-register in
    the Pallas kernel); `PagedKVCache.apply_stage` then lands every layer's
    staged token with ONE batched scatter per step. A staged token is
    meaningful only between its `update_layer` and the next `apply_stage`;
    chunked prefill (S>1) bypasses staging and writes the pool directly.

    `scales` (Hkv, NB, BS) f32 or None: present iff the pool is int8 at
    rest (kv_cache_dtype="int8") — one scale per (kv-head, block, slot),
    written by the same scatters that write the pool (strictly local: an
    append never re-quantizes a neighbour). The stage buffer stays in the
    COMPUTE dtype — the staged token is folded into attention exactly and
    only quantized when `apply_stage` lands it.

    `layer` () int32 or None: set, `pool` and `scales` are the STACKED
    (L, ...) arrays of the whole cache and this view is layer `layer` of
    them, by index (`scan_paged_layers`): writes scatter at
    `[layer, :, slot]`, the kernels fetch block `(layer, :, phys)`, and
    nothing cuts a layer's pool out of the stack. None: `pool` is one
    layer's own array, as a block scan that scans over the pools sees it."""

    pool: jnp.ndarray    # ([L,] Hkv, NB, BS, D) — physical KV blocks
    tables: jnp.ndarray  # (B, T) int32 — logical block i of row b → pool id
    stage: Optional[jnp.ndarray] = None  # (B, Hkv, D) staged decode token
    scales: Optional[jnp.ndarray] = None  # ([L,] Hkv, NB, BS) f32 — int8 pools
    layer: Optional[jnp.ndarray] = None  # () int32 — pool/scales are stacked

    def stacked(self):
        """`(pool, scales, layer)` addressed by layer: the stacked arrays
        as they are, or one layer's own arrays as a stack of one."""
        if self.layer is not None:
            return self.pool, self.scales, self.layer
        return (self.pool[None],
                None if self.scales is None else self.scales[None], 0)

    def with_stacked(self, pool, scales) -> "PagedLayer":
        """This view over what `stacked()` gave, once written."""
        if self.layer is None:
            pool, scales = pool[0], None if scales is None else scales[0]
        return self.replace(pool=pool, scales=scales)


@struct.dataclass
class PagedKVCache:
    """Block-paged KV cache (the FastGen `BlockedAllocator` data structure,
    TPU-first). HBM scales with *blocks in flight* (`num_blocks · block_size`
    tokens), not `max_batch × max_seq` — a 10-token sequence pins one block,
    not a whole row.

    Duck-typed to `KVCache` (`k`/`v`/`index`/`max_len`/`replace`): the model
    zoo's cache path runs unmodified. `k.tables` and `v.tables` are kept as
    separate arrays (same values) so whole-cache donation aliases cleanly.
    """

    k: PagedLayer   # pool (L, Hkv, NB, BS, D), tables (L, B, T)
    v: PagedLayer
    index: jnp.ndarray  # (B,) int32

    @property
    def max_len(self) -> int:
        """Logical capacity per sequence: T · BS."""
        return self.k.tables.shape[-1] * self.k.pool.shape[-2]

    @property
    def block_size(self) -> int:
        return self.k.pool.shape[-2]

    @property
    def num_blocks(self) -> int:
        return self.k.pool.shape[-3]

    @property
    def quantized(self) -> bool:
        return self.k.scales is not None

    @classmethod
    def create(cls, num_layers: int, batch: int, max_len: int, kv_heads: int,
               head_dim: int, num_blocks: int, block_size: int = 256,
               dtype: Any = jnp.bfloat16,
               staged: bool = False, quantized: bool = False) -> "PagedKVCache":
        t = -(-max_len // block_size)  # blocks per sequence (logical)
        pool_shape = (num_layers, kv_heads, num_blocks, block_size, head_dim)
        # -1 marks an unowned table entry: writes through it DROP (padding
        # in a bucketed prefill reaches positions past the owned blocks —
        # without the sentinel that junk would land in block 0 of the pool)
        tables = jnp.full((num_layers, batch, t), -1, jnp.int32)
        def _stage():
            # the stage holds the COMPUTE dtype even for int8 pools: the
            # staged token folds into attention unquantized (exact) and is
            # quantized only when apply_stage lands it
            return (jnp.zeros((num_layers, batch, kv_heads, head_dim), dtype)
                    if staged else None)
        pool_dtype = jnp.int8 if quantized else dtype
        def _scales():
            return (jnp.ones(pool_shape[:-1], jnp.float32)
                    if quantized else None)
        return cls(
            k=PagedLayer(pool=jnp.zeros(pool_shape, pool_dtype), tables=tables,
                         stage=_stage(), scales=_scales()),
            v=PagedLayer(pool=jnp.zeros(pool_shape, pool_dtype),
                         tables=jnp.full((num_layers, batch, t), -1, jnp.int32),
                         stage=_stage(), scales=_scales()),
            index=jnp.zeros((batch,), jnp.int32))

    @jax.named_scope("kv_stage")
    def apply_stage(self) -> "PagedKVCache":
        """Land every layer's staged decode token in the pool with one
        batched scatter per pool (vs one per layer in unstaged decode).
        CONVENTION: call immediately after a staged single-token model
        step — each staged token belongs at position `index[b] − 1` (the
        model already advanced the cursors). Parked rows (position at or
        past capacity) and unowned table entries drop. No-op when the cache
        was created without staging."""
        if self.k.stage is None:
            return self
        l, hkv, nb, bs, d = self.k.pool.shape
        b, t = self.k.tables.shape[1:]
        pos = self.index - 1
        write = _pool_writer(hkv)
        if write is not None:  # the whole stack, from layer 0
            k, v = _write_pools(write, self.k.replace(layer=0),
                                self.v.replace(layer=0),
                                self.k.stage[:, :, None],
                                self.v.stage[:, :, None], self.k.tables[0], pos)
            return self.replace(k=k.replace(layer=None),
                                v=v.replace(layer=None))
        blk = jnp.clip(pos // bs, 0, t - 1)
        phys = self.k.tables[0, jnp.arange(b), blk]              # (B,)
        valid = jnp.logical_and(jnp.logical_and(pos >= 0, pos < t * bs),
                                phys >= 0)
        flat = jnp.where(valid, phys * bs + pos % bs, nb * bs)   # → drop

        def land(layer):
            pool_flat = layer.pool.reshape(l, hkv, nb * bs, d)
            if layer.scales is not None:
                # int8 at rest: THIS is where the cache quantizes — the
                # staged bf16 token becomes int8 rows + per-(head, slot)
                # scales inside the same once-per-step batched scatter
                qvals, sc = quantize_kv_tokens(layer.stage)  # (L,B,Hkv,*)
                vals = jnp.moveaxis(qvals, 1, 2)             # (L, Hkv, B, D)
                sflat = layer.scales.reshape(l, hkv, nb * bs)
                sflat = sflat.at[:, :, flat].set(
                    jnp.moveaxis(sc, 1, 2), mode="drop")
                pool_flat = pool_flat.at[:, :, flat].set(vals, mode="drop")
                return layer.replace(
                    pool=pool_flat.reshape(l, hkv, nb, bs, d),
                    scales=sflat.reshape(l, hkv, nb, bs))
            # (L, B, Hkv, D) → (L, Hkv, B, D): axis 2 lines up with `flat`
            vals = jnp.moveaxis(layer.stage.astype(layer.pool.dtype), 1, 2)
            pool_flat = pool_flat.at[:, :, flat].set(vals, mode="drop")
            return layer.replace(pool=pool_flat.reshape(l, hkv, nb, bs, d))

        return self.replace(k=land(self.k), v=land(self.v))

    def with_tables(self, tables: jnp.ndarray) -> "PagedKVCache":
        """Install new (B, T) block tables (broadcast over layers)."""
        l = self.k.pool.shape[0]
        tl = jnp.broadcast_to(tables[None], (l,) + tables.shape)
        # two materialized copies so k/v donation never aliases one buffer
        return self.replace(k=self.k.replace(tables=jnp.array(tl)),
                            v=self.v.replace(tables=jnp.array(tl)))


def _pool_writer(hkv: int):
    """The Pallas writer of new tokens into the pools (`ops/pallas/
    paged_attention.py:paged_kv_write`, or its head-sharded wrapper) where
    the paged attention kernels run, by their dispatcher's own rule: the
    chip, and one device or a pure-'model' mesh that divides the KV heads.
    The kernels read the pool tiled over (BS, D) and XLA's scatter wants
    the KV-head dim second-minor, so a program that used both re-laid the
    whole pool around every scatter. None elsewhere (CPU, other meshes):
    the XLA scatters below, in a program whose attention is XLA's too."""
    from deepspeed_tpu.ops import attention
    if not attention._use_pallas():
        return None
    mesh, fallback = attention._decode_tp_mesh(hkv, hkv, "paged_kv_write")
    if fallback:
        return None
    if mesh is None:
        from deepspeed_tpu.ops.pallas.paged_attention import paged_kv_write
        return paged_kv_write
    from deepspeed_tpu.ops.pallas.sharded import sharded_paged_kv_write
    return functools.partial(sharded_paged_kv_write, mesh=mesh)


def _write_pools(write, k: PagedLayer, v: PagedLayer, k_new, v_new, tables,
                 starts) -> Tuple[PagedLayer, PagedLayer]:
    """`k_new`/`v_new` (NL, B, S, Hkv, D) through the writer into the pools
    of `k`/`v`, for the NL layers from theirs on; int8 pools quantize
    here, as the scatters do."""
    (kp, ks, layer), (vp, vs, _) = k.stacked(), v.stacked()
    k_ns = v_ns = None
    if ks is not None:
        (k_new, k_ns), (v_new, v_ns) = (quantize_kv_tokens(k_new),
                                        quantize_kv_tokens(v_new))
    kp, vp, ks, vs = write(kp, vp, k_new, v_new, tables, starts, layer,
                           k_scales=ks, v_scales=vs, k_new_scales=k_ns,
                           v_new_scales=v_ns)
    return k.with_stacked(kp, ks), v.with_stacked(vp, vs)


def _update_paged_layer(layer: PagedLayer, new: jnp.ndarray,
                        index: jnp.ndarray) -> PagedLayer:
    """Scatter `new` (B, S, Hkv, D) into the pool at each row's logical
    positions `index[b]..index[b]+S` via its block table. Positions at or
    past the logical capacity (parked rows) drop.

    The target is `[layer, :, slot]` of the stacked pool (`PagedLayer.
    stacked`): where the stack is a loop's carry or a donated argument the
    scatter is in place, and no layer's pool is copied to be written.

    When S equals the block size and every cursor is block-aligned (the
    steady state of chunked prefill with chunk == block — each row's piece
    exactly fills one fresh block), the write is a B-index scatter of whole
    (Hkv, BS, D) slabs instead of a B·S-index token scatter; the XLA token
    scatter at S=256 measured tens of ms/layer on v5e and dominated FastGen
    prefill. Runtime `lax.cond` picks the path, so misaligned callers
    (prefill continuations, tests) keep exact semantics."""
    pool, scales, l = layer.stacked()
    hkv, nb, bs, d = pool.shape[1:]
    t = layer.tables.shape[1]
    b, s = new.shape[:2]
    if scales is not None:
        vals, svals = quantize_kv_tokens(new)       # (B,S,Hkv,D), (B,S,Hkv)
    else:
        vals, svals = new.astype(pool.dtype), None

    # an index of the form [l, :, i] puts i's axes first: the values go in
    # as (*i.shape, Hkv, ...), which for tokens is how `new` arrives
    def token_scatter(carry):
        pool, scales = carry
        pos = index[:, None] + jnp.arange(s)[None, :]        # (B, S) logical
        blk = jnp.clip(pos // bs, 0, t - 1)
        rows = jnp.arange(b)[:, None]
        phys = layer.tables[rows, blk]                       # (B, S)
        flat = phys * bs + pos % bs
        # drop: parked rows (pos past capacity) AND unowned entries
        # (phys < 0 — bucketed-prefill padding past the row's blocks)
        valid = jnp.logical_and(pos < t * bs, phys >= 0)
        flat = jnp.where(valid, flat, nb * bs)
        pool_flat = pool.reshape(-1, hkv, nb * bs, d)
        pool_flat = pool_flat.at[l, :, flat].set(vals, mode="drop")
        if scales is not None:
            sflat = scales.reshape(-1, hkv, nb * bs)
            scales = sflat.at[l, :, flat].set(
                svals, mode="drop").reshape(scales.shape)
        return pool_flat.reshape(pool.shape), scales

    def block_scatter(carry):
        pool, scales = carry
        blk = jnp.clip(index // bs, 0, t - 1)
        phys = layer.tables[jnp.arange(b), blk]              # (B,)
        ok = jnp.logical_and(index < t * bs, phys >= 0)
        phys = jnp.where(ok, phys, nb)                       # → drop
        if scales is not None:
            scales = scales.at[l, :, phys].set(
                jnp.moveaxis(svals, 2, 1), mode="drop")      # (B, Hkv, BS)
        return pool.at[l, :, phys].set(
            jnp.moveaxis(vals, 2, 1), mode="drop"), scales   # (B,Hkv,BS,D)

    if s != bs:
        pool, scales = token_scatter((pool, scales))
    else:
        aligned = jnp.all(index % bs == 0)
        pool, scales = jax.lax.cond(aligned, block_scatter, token_scatter,
                                    (pool, scales))
    return layer.with_stacked(pool, scales)


def gather_paged_layer(layer: PagedLayer, dtype: Any = None) -> jnp.ndarray:
    """Materialize the dense logical view (B, T·BS, Hkv, D) of a paged layer
    — the XLA fallback read path (CPU tests, prefill chunks, alibi/window
    models) and the golden reference for the Pallas paged kernel.

    Gathers WHOLE BLOCKS (B·T indices of (BS, D) slabs), not tokens: the r3
    token-granular form issued a B·T·BS-index gather per layer (~65k indices
    at serving shape) which measured ~140 ms/layer on v5e — the entire
    FastGen prefill cost. Block-granular is ~256 indices of 32 KB each and
    runs at HBM bandwidth. Unowned entries (-1) read block 0; callers mask
    by validity, exactly as before. The blocks are gathered by
    `(layer, phys)` out of the stacked pool, never out of a slice of it.

    int8 pools dequantize here (block-gathered values × their scales, f32
    unless `dtype` says otherwise) — the only place the dense form of a
    quantized cache materializes, and only as this fallback's per-layer
    transient; the kernels fold the scales in-register instead."""
    pool, scales, l = layer.stacked()
    hkv, nb, bs, d = pool.shape[1:]
    b, t = layer.tables.shape
    phys = jnp.maximum(layer.tables, 0).reshape(-1)         # (B·T,) unowned
    blocks = pool[l, :, phys]                # → masked reads; (B·T,Hkv,BS,D)
    if scales is not None:
        blocks = dequantize_kv(blocks, scales[l, :, phys],
                               dtype or jnp.float32)
    elif dtype is not None:
        blocks = blocks.astype(dtype)
    dense = blocks.reshape(b, t, hkv, bs, d)
    return jnp.moveaxis(dense, 2, 3).reshape(b, t * bs, hkv, d)


def _dense_writer(hkv: int):
    """`_pool_writer` for the stacked dense cache: `kv_write_dense` (or its
    head-sharded wrapper) where the dense decode kernel can run."""
    from deepspeed_tpu.ops import attention
    if not attention._use_pallas():
        return None
    mesh, fallback = attention._decode_tp_mesh(hkv, hkv, "kv_write_dense")
    if fallback:
        return None
    if mesh is None:
        from deepspeed_tpu.ops.pallas.decode_attention import kv_write_dense
        return kv_write_dense
    from deepspeed_tpu.ops.pallas.sharded import sharded_kv_write_dense
    return functools.partial(sharded_kv_write_dense, mesh=mesh)


def _write_dense_rows(k: DenseLayer, v: DenseLayer, k_new: jnp.ndarray,
                      v_new: jnp.ndarray, index: jnp.ndarray):
    """`k_new`/`v_new` (B, S, Hkv, D) into layer `k.layer` of the stacks at
    `[layer, b, :, index[b] .. index[b] + S - 1]`, slots at or past M
    dropped: the write of a pass that CARRIES the stacks (prefill). A row
    at a time, a slice read and written back in place: a dynamic slice
    keeps whatever tiling the stack has, where a scatter along M asks XLA
    for another and re-lays the whole stack around itself (`_pool_writer`)."""
    m = k.stack.shape[3]
    b, s, hkv, d = k_new.shape

    def tokens(new, stack):  # (B, Hkv, 2S, D): S slots of nothing, then new
        new = jnp.swapaxes(new, 1, 2).astype(stack.dtype)
        return jnp.concatenate([jnp.zeros_like(new), new], axis=2)

    news = (tokens(k_new, k.stack), tokens(v_new, v.stack))
    keep_from = jnp.arange(s)[None, None, :, None]

    def row(i, stacks):
        # the window [start, start + S) lies inside the row; a cursor past
        # M - S pushes its first `shift` slots onto tokens the row already
        # holds (kept) and its last `shift` tokens past M (dropped)
        start = jnp.clip(index[i], 0, m - s)
        shift = jnp.clip(index[i] - start, 0, s)
        at = (k.layer, i, 0, start, 0)
        out = []
        for stack, new in zip(stacks, news):
            old = jax.lax.dynamic_slice(stack, at, (1, 1, hkv, s, d))
            cand = jax.lax.dynamic_slice(new, (i, 0, s - shift, 0),
                                         (1, hkv, s, d))
            out.append(jax.lax.dynamic_update_slice(
                stack, jnp.where(keep_from >= shift, cand[None], old), at))
        return tuple(out)

    k_stack, v_stack = jax.lax.fori_loop(0, b, row, (k.stack, v.stack))
    return k.replace(stack=k_stack), v.replace(stack=v_stack)


@jax.named_scope("kv_write")
def update_layer(k_cache, v_cache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 index: jnp.ndarray) -> Tuple[Any, Any]:
    """Insert `k_new`/`v_new` (B, S, Hkv, D) at per-row positions
    `index` (B,) of one layer's cache — dense (B, M, Hkv, D) arrays,
    `DenseLayer` views of the stacked dense cache or `PagedLayer` views (the
    model zoo calls this without knowing which).
    Out-of-range rows (slot parked at max_len) are dropped — the v2 engine
    uses that to mask inactive slots."""
    if isinstance(k_cache, DenseLayer):
        if k_cache.staged and k_new.shape[1] == 1:
            # staged decode: no write inside the layer loop; attention folds
            # the token in and `KVCache.land` writes every layer's at once
            return (k_cache.replace(stage=k_new[:, 0].astype(k_cache.stack.dtype)),
                    v_cache.replace(stage=v_new[:, 0].astype(v_cache.stack.dtype)))
        return _write_dense_rows(k_cache, v_cache, k_new, v_new, index)
    if isinstance(k_cache, PagedLayer):
        if k_cache.stage is not None and k_new.shape[1] == 1:
            # staged decode append: no pool scatter here — attention folds
            # the staged token in, `apply_stage` lands it once per step.
            # The stage keeps ITS OWN dtype (the compute dtype): int8
            # pools quantize at apply_stage, not here
            return (k_cache.replace(stage=k_new[:, 0].astype(k_cache.stage.dtype)),
                    v_cache.replace(stage=v_new[:, 0].astype(v_cache.stage.dtype)))
        write = _pool_writer(k_cache.pool.shape[-4])
        if write is None:
            return (_update_paged_layer(k_cache, k_new, index),
                    _update_paged_layer(v_cache, v_new, index))
        return _write_pools(write, k_cache, v_cache, k_new[None], v_new[None],
                            k_cache.tables, index)
    b, s = k_new.shape[:2]
    rows = jnp.arange(b)[:, None]                      # (B, 1)
    cols = index[:, None] + jnp.arange(s)[None, :]     # (B, S)
    if isinstance(k_cache, QuantizedKVLayer):
        qk, sk = quantize_kv_tokens(k_new)
        qv, sv = quantize_kv_tokens(v_new)
        k_cache = k_cache.replace(
            data=k_cache.data.at[rows, cols].set(qk, mode="drop"),
            scales=k_cache.scales.at[rows, cols].set(sk, mode="drop"))
        v_cache = v_cache.replace(
            data=v_cache.data.at[rows, cols].set(qv, mode="drop"),
            scales=v_cache.scales.at[rows, cols].set(sv, mode="drop"))
        return k_cache, v_cache
    k_cache = k_cache.at[rows, cols].set(k_new.astype(k_cache.dtype),
                                         mode="drop")
    v_cache = v_cache.at[rows, cols].set(v_new.astype(v_cache.dtype),
                                         mode="drop")
    return k_cache, v_cache


class _PagedStep(nn.Module):
    """One layer of `scan_paged_layers`: the family's block under this
    module's own scope (so the parameter tree is the block's), handed the
    stacked pools with this layer's index instead of a slice of them."""

    block: Any  # () -> the block module, called (h, aux, (k, v))

    @nn.compact
    def __call__(self, carry, consts, xs):
        h, carried = carry
        aux, const = consts
        k_pool, v_pool, k_scales, v_scales = (
            const if carried is None else carried)
        k, v, layer = xs
        inner = self.block()
        nn.share_scope(self, inner)
        h, (k, v) = inner(
            h, aux, (k.replace(pool=k_pool, scales=k_scales, layer=layer),
                     v.replace(pool=v_pool, scales=v_scales, layer=layer)))
        if carried is not None:
            carried = (k.pool, v.pool, k.scales, v.scales)
        return (h, carried), (k.stage, v.stage)


def scan_paged_layers(block, h, aux, cache: PagedKVCache, s: int, *,
                      name: str, **scan_kw):
    """A zoo model's cached block scan over a `PagedKVCache`, in place of
    its `nn.scan(Block, in_axes=(nn.broadcast, 0), out_axes=0, ...)` over
    `(cache.k, cache.v)`; call it from the model's compact method. `block`
    builds the block module (`functools.partial(Block, cfg)`), which is
    called `(h, aux, (k, v))` and returns `(h, (k, v))` as before; `s` is
    the number of new tokens a row; `name` and `scan_kw` (`variable_axes`,
    `split_rngs`, `metadata_params`) are the family's own. Returns
    `(h, new_cache)`, the cursors advanced by `s`.

    From the program's argument to its result there is ONE buffer per pool:
    the scan runs over the layer INDEX and the small per-layer leaves
    (tables, stage) only. A pass that writes the pools (chunks, prefill,
    unstaged decode) carries them, and each layer scatters into the carry
    at `[layer, :, slot]`, which XLA does in place. A pass that cannot
    write them (staged decode: the condition `update_layer` tests) closes
    over them as constants of the loop. Scanned over instead, each layer's
    pool was cut out of the stack and written back, twice a round, and
    copied once more to be scattered into: 36 ms of an 80 ms round at
    Qwen2.5-3B with a 3.77 GB pool (PERF.md, PR 29).

    A dense `KVCache` in the stacked view takes the same answer:
    `scan_dense_layers`, below."""
    pools = (cache.k.pool, cache.v.pool, cache.k.scales, cache.v.scales)
    layers = cache.k.pool.shape[0]
    writes = not (cache.k.stage is not None and s == 1)
    scan = nn.scan(_PagedStep, in_axes=(nn.broadcast, 0), out_axes=0,
                   length=layers, **scan_kw)
    (h, carried), (k_stage, v_stage) = scan(block, name=name)(
        (h, pools if writes else None), (aux, None if writes else pools),
        (cache.k.replace(pool=None, scales=None),
         cache.v.replace(pool=None, scales=None),
         jnp.arange(layers, dtype=jnp.int32)))
    k_pool, v_pool, k_scales, v_scales = carried if writes else pools
    return h, cache.replace(
        k=cache.k.replace(pool=k_pool, scales=k_scales, stage=k_stage),
        v=cache.v.replace(pool=v_pool, scales=v_scales, stage=v_stage),
        index=cache.index + s)


class _DenseStep(nn.Module):
    """One layer of `scan_dense_layers`: `_PagedStep` for the stacked dense
    cache. The block is handed `DenseLayer` views that name the stacks and
    this layer's index."""

    block: Any  # () -> the block module, called (h, aux, (k, v))
    staged: bool

    @nn.compact
    def __call__(self, carry, consts, layer):
        h, carried = carry
        aux, const = consts
        k_stack, v_stack = const if carried is None else carried
        inner = self.block()
        nn.share_scope(self, inner)
        h, (k, v) = inner(
            h, aux, (DenseLayer(k_stack, layer, staged=self.staged),
                     DenseLayer(v_stack, layer, staged=self.staged)))
        if carried is not None:
            carried = (k.stack, v.stack)
        return (h, carried), (k.stage, v.stage)


def scan_dense_layers(block, h, aux, cache: KVCache, s: int, *,
                      name: str, **scan_kw):
    """`scan_paged_layers` for a dense `KVCache` in the stacked view
    (`create_stacked`), in place of a zoo model's `nn.scan` over
    `(cache.k, cache.v)`: same arguments, same result.

    From a v1 program's first decode step to its last there is ONE buffer
    for K and one for V. The scan runs over the layer INDEX alone. A pass
    of many tokens a row (prefill) carries the stacks and each layer writes
    its rows' slots into the carry (`_write_dense_rows`). Single-token
    decode closes over the stacks as constants of the loop: a layer STAGES
    its token (`DenseLayer.stage`), the decode kernel reads it into its
    slot's place, and after the loop `KVCache.land` writes the step's L x B
    tokens at once. Scanned over instead, each layer's whole K and V was cut out of the
    stack, re-laid for the kernel and written back, every step: 3.1 ms of
    a 17 ms step at Qwen2.5-3B, 32 rows (PERF.md, PR 42)."""
    stacks = (cache.k.stack, cache.v.stack)
    layers = stacks[0].shape[0]
    staged = s == 1
    scan = nn.scan(_DenseStep, in_axes=(nn.broadcast, 0), out_axes=0,
                   length=layers, **scan_kw)
    (h, carried), (k_new, v_new) = scan(block, staged, name=name)(
        (h, None if staged else stacks), (aux, stacks if staged else None),
        jnp.arange(layers, dtype=jnp.int32))
    if staged:
        cache = cache.land(k_new, v_new)
    else:
        cache = cache.replace(k=DenseLayer(carried[0]),
                              v=DenseLayer(carried[1]))
    return h, cache.replace(index=cache.index + s)


def scan_cache_layers(block, h, aux, cache, s: int, **kw):
    """The cached block scan over the layer index, for either cache that
    stays whole (a `PagedKVCache`, a stacked `KVCache`)."""
    scan = scan_paged_layers if isinstance(cache, PagedKVCache) \
        else scan_dense_layers
    return scan(block, h, aux, cache, s, **kw)


def decode_mask(q_positions: jnp.ndarray, max_len: int,
                window=None) -> jnp.ndarray:
    """Causal validity mask (B, Sq, M) over the full static cache: key slot j
    is attendable iff j <= position of the query token (and, with a sliding
    `window`, j > position − window)."""
    kj = jnp.arange(max_len)[None, None, :]
    keep = kj <= q_positions[:, :, None]
    if window is not None:
        keep = jnp.logical_and(keep, kj > q_positions[:, :, None] - window)
    return keep


def tp_cache_shardings(cache, mesh, axis: str = "model"):
    """Pytree of NamedShardings pinning a KVCache/PagedKVCache with the
    KV-head dim sharded over the mesh `axis` — the at-rest layout the
    sharded decode kernels (ops/pallas/sharded.py) expect, so serving on
    a pure-TP mesh never reshards the pools per step. Falls back to fully
    replicated pins when the mesh doesn't head-shard this cache (`axis`
    trivial, other axes nontrivial, or KV heads not divisible). Cursors,
    block tables and the decode mask stay replicated either way."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    def all_repl():
        return jax.tree_util.tree_map(lambda _: repl, cache)

    try:
        from deepspeed_tpu.ops.pallas.sharded import (
            nontrivial_axes, sharded_kernels_supported)
        if not sharded_kernels_supported():
            return all_repl()
        nt = nontrivial_axes(mesh)
    except Exception:
        return all_repl()
    tp = nt.get(axis, 1)
    if tp <= 1 or set(nt) != {axis}:
        return all_repl()
    if isinstance(cache, PagedKVCache):
        if cache.k.pool.shape[1] % tp:
            return all_repl()

        def layer(pl):
            # scales shard on the SAME head axis as the pool (one scale
            # per (kv-head, block, slot) row) — replicating them would
            # force a per-step all-gather beside a sharded pool
            return PagedLayer(
                pool=NamedSharding(mesh, P(None, axis, None, None, None)),
                tables=repl,
                stage=None if pl.stage is None else NamedSharding(
                    mesh, P(None, None, axis, None)),
                scales=None if pl.scales is None else NamedSharding(
                    mesh, P(None, axis, None, None)))

        return PagedKVCache(k=layer(cache.k), v=layer(cache.v), index=repl)
    if isinstance(cache, KVCache) and cache.stacked:
        if cache.k.stack.shape[2] % tp:
            return all_repl()
        s = DenseLayer(NamedSharding(mesh, P(None, None, axis, None, None)))
        return KVCache(k=s, v=s, index=repl)
    if isinstance(cache, KVCache):
        if cache.k.shape[3] % tp:
            return all_repl()
        s = NamedSharding(mesh, P(None, None, None, axis, None))
        if cache.quantized:
            ql = QuantizedKVLayer(
                data=s, scales=NamedSharding(mesh, P(None, None, None, axis)))
            return KVCache(k=ql, v=ql, index=repl)
        return KVCache(k=s, v=s, index=repl)
    return all_repl()


def scatter_target_shapes(cache) -> frozenset:
    """The (shape, dtype) pairs a scatter into this cache can produce —
    every KV buffer leaf's full stacked shape AND its per-layer slice
    (models update one layer inside `nn.scan`, where the leading L axis is
    gone). Used by tools/tpuverify's kv-scatter-discipline contract to tell
    cache scatters apart from unrelated scatters in a decode jaxpr. Cursors
    and 1-D leaves are excluded — their updates are cheap and legion.

    Paged pools scatter through a token-flat view — (..., NB, BS, D)
    writes appear in the jaxpr as (..., NB*BS, D) — so for every 4-D+
    shape the merged-block-axes variant is included too.

    Accepts a live cache, a ShapeDtypeStruct tree (eval_shape output), or
    any pytree of shaped leaves.
    """
    shapes = set()

    def add(shp, dt):
        shapes.add((shp, dt))
        if len(shp) >= 4:
            merged = shp[:-3] + (shp[-3] * shp[-2],) + shp[-1:]
            shapes.add((merged, dt))

    for leaf in jax.tree_util.tree_leaves(cache):
        shp = tuple(getattr(leaf, "shape", ()))
        if len(shp) < 2:
            continue
        dt = str(getattr(leaf, "dtype", ""))
        add(shp, dt)
        if len(shp) >= 3:
            add(shp[1:], dt)  # per-layer slice under nn.scan
    return frozenset(shapes)
