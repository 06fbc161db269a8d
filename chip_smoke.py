"""chip_smoke.py — the main path once, on the chip, in one process.

Drives training (`deepspeed_tpu.initialize` + `train_batch`), v1 serving
(`init_inference(...).generate`) and v2 continuous batching
(`InferenceEngineV2.generate`) at the full width of Qwen2.5-3B (hidden 2048,
FFN 11008, 16 heads / 2 KV heads, vocab 151936; weights from `--seed`), and
every Pallas kernel an engine can select against its `jax.numpy` reference
at those shapes. Serving runs all 36 layers; training cuts depth to what one
16 GB chip holds with fp32 master + Adam moments.

    python chip_smoke.py                       # one chip (what the driver runs)
    python chip_smoke.py --chips 4             # dp2 x tp2 train and tp2 generate,
                                               # each against one chip, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny --rehearsal   # control flow only

One JSON object per phase, then the last line:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
Exit code 0 only if every phase passed on a TPU (or, with `--rehearsal`, on
whatever backend there is — the last line still names it truthfully).
`--tiny` changes sizes and nothing else. Without `--rehearsal` a non-TPU
backend prints no result and exits 2.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# bf16 inputs round on the MXU, so kernel and XLA reference accumulate
# differently on the chip (CLAUDE.md, tests/unit/ops/test_flash_attention.py)
FWD_TOL, BWD_TOL = 2e-2, 1e-1
DELTA_TOL = 2e-5    # float32 products at `highest` on both sides


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything `--tiny` changes. Widths come from the preset; the rest
    is batch, length and depth."""
    preset: str
    train_layers: int          # depth cut, training only
    seq: int                   # training sequence length
    loss_chunk: int            # chunked cross-entropy rows
    micro_batch: int
    global_batch: int          # sequences per step, whatever the mesh
    prompt_lens: Tuple[int, int]   # v1 runs one batch per length; v2 mixes them
    prompts_per_len: int
    new_tokens: int
    v2_slots: int              # fewer than the requests: sequences join and leave
    v2_max_seq: int
    v2_chunk: int              # split-fuse chunk; the long prompts span two
    block: int                 # KV block size
    # kernel-case shapes beyond the model's own widths
    flash_long: int
    # flash attention at Qwen2.5-0.5B's heads: query heads, KV heads, head
    # width (the benchmark's train-2k cell's; the preset's own width is 128)
    flash_narrow: Tuple[int, int, int]
    decode_batch: int
    decode_ctx: int
    # the stacked dense cache of a v1 program, as the benchmark's two
    # generate cells hold it: (layers, rows, n_rep, M) each
    dense_stack: Tuple[Tuple[int, int, int, int], ...]
    paged_batch: int
    paged_blocks: int
    prefill_batch: int
    # a mostly-empty serving batch: rows, table blocks a row, chunk tokens
    parked: Tuple[int, int, int]
    gmm_rows: int
    gmm_experts: int
    gmm_width: int
    qmm_group: int
    # a state-space decode step: recurrent layers, rows, heads, head dim,
    # state size, groups (Nemotron-3-Nano's at the benchmark cell's batch)
    ssm: Tuple[int, int, int, int, int, int]
    # the grouped GEMM at DECODE shapes, (name, rows, experts, K, N) each:
    # 1 to 7 rows an expert, so that groups straddle the 16-row tiles, the
    # rest of the rows no expert's (absent assignments); Nemotron-3-Nano's
    # up projection, Ling's, DeepSeek-V3.2's and openPangu's (the longest K
    # a cell has: too long for one K tile, `held_tiling` keeps its K tiles)
    gmm_decode: Tuple[Tuple[str, int, int, int, int], ...]
    # a held expert layer at a PREFILL chunk's shape: tokens, top k, hidden,
    # expert width, held experts, the router's experts (DeepSeek-V3.2's chunk
    # of 2,048 tokens on a chip that holds 16 of 256)
    held_rows: Tuple[int, int, int, int, int, int]
    # the same layer at a LONG prefill's shape, the kernel's way back
    # (`ops/pallas/held_combine.py`) over many token tiles (Trinity-Mini's
    # two prompts of 8,192 on a chip that holds 16 of 128)
    held_rows_long: Tuple[int, int, int, int, int, int]
    # differential decode attention over a stack of paired heads: layers,
    # rows, groups, slots, pair width (Phi-4-mini-flash's eight rings and its
    # one shared slab at the benchmark cell's batch and length)
    diff_stack: Tuple[Tuple[int, int, int, int, int], ...]
    # a Mamba-1 decode step: recurrent layers, rows, state size, channels
    ssm_m1: Tuple[int, int, int, int]
    # a KDA decode step: layers, rows, heads, head width (the state d x d)
    kda: Tuple[int, int, int, int]
    # softmax attention at a head width of TWO lane tiles (Qwen3-Next's full
    # layers at the benchmark cell's batch and length): layers, rows, KV
    # heads, query heads a KV head, slots (the decode read), head width,
    # whole-sequence forward length, a prefill chunk's queries and slots
    wide_heads: Tuple[int, int, int, int, int, int, int, int, int]
    # latent decode attention: layers, rows, query heads, slots, rank, rope
    mla: Tuple[int, int, int, int, int, int]
    # the same kernel where the heads' operations meet the slab's bytes:
    # layers, rows, query heads, slots, rank, rope (openPangu's at the
    # benchmark cell's batch and length)
    mla_wide: Tuple[int, int, int, int, int, int]
    # dense causal latent prefill, a chunk of one row's queries against its
    # slab: layers, rows, query heads, slots, rank, rope, nope and value
    # widths, queries a chunk (openPangu's; a quarter-chunk of queries, as
    # `mla_sparse`)
    mla_dense: Tuple[int, int, int, int, int, int, int, int, int]
    # learned sparse attention over the stacked dense cache: layers, rows, KV
    # heads, query heads a KV head, slots, head width, index heads, index
    # width AS STORED (a 64-value key in a whole lane row), positions chosen,
    # queries a prefill chunk (Keye-VL-2.0's at the benchmark cell's batch
    # and length)
    sparse: Tuple[int, int, int, int, int, int, int, int, int, int]
    # latent attention under a learned choice: layers, rows, query heads,
    # slots, rank, rope, nope and value widths, index heads, index width,
    # positions chosen, queries a prefill chunk (DeepSeek-V3.2's at the
    # benchmark cell's batch and length; a quarter-chunk of queries, whose
    # plain form's scores are 4.4 GB)
    mla_sparse: Tuple[int, int, int, int, int, int, int, int, int, int, int,
                      int]
    # the dense decode kernel over RINGS: layers, rows, KV heads, query heads
    # a KV head, slots (Trinity-Mini's twelve window layers at the benchmark
    # cell's batch)
    ring_stack: Tuple[int, int, int, int, int]
    # the BANDED flash forward: rows, positions, query heads, KV heads,
    # window (two of Trinity-Mini's prompts, as its prefill walks them)
    flash_band: Tuple[int, int, int, int, int]
    # a serving prefill's chunked delta rule, a decay a head: rows,
    # positions, key heads, value heads, head width, positions a block (a
    # chunk of Qwen3-Next's prompt, as its prefill walks it)
    delta_prefill: Tuple[int, int, int, int, int, int]
    # the same with a decay a CHANNEL (a group of Ling's prompts)
    delta_prefill_channel: Tuple[int, int, int, int, int, int]


FULL = Sizes(preset="qwen2-3b", train_layers=4, seq=2048, loss_chunk=1024,
             micro_batch=2, global_batch=8, prompt_lens=(96, 352),
             prompts_per_len=4, new_tokens=32, v2_slots=4, v2_max_seq=1024,
             v2_chunk=256, block=256, flash_long=32768,
             flash_narrow=(14, 2, 64), decode_batch=32,
             decode_ctx=1024,
             dense_stack=((36, 32, 8, 1280), (2, 64, 16, 1024)),
             paged_batch=64, paged_blocks=96,
             prefill_batch=8, parked=(48, 18, 16), gmm_rows=4096,
             gmm_experts=64, gmm_width=1024, qmm_group=256,
             ssm=(6, 64, 64, 64, 128, 8),
             gmm_decode=(("", 384, 64, 2688, 1856),
                         ("_ling", 1024, 128, 2560, 768),
                         ("_deepseek", 64, 16, 7168, 2048),
                         ("_openpangu", 64, 16, 7680, 2048)),
             held_rows=(2048, 8, 7168, 2048, 16, 256),
             held_rows_long=(16384, 8, 2048, 1024, 16, 128),
             diff_stack=((8, 64, 10, 512, 128), (1, 64, 10, 2816, 128)),
             ssm_m1=(9, 64, 16, 5120), kda=(5, 128, 32, 128),
             wide_heads=(3, 8, 2, 8, 33280, 256, 4096, 2048, 8320),
             mla=(1, 128, 32, 2048, 512, 64),
             mla_wide=(5, 8, 128, 25600, 512, 64),
             mla_dense=(2, 8, 128, 25600, 512, 64, 128, 128, 256),
             sparse=(2, 8, 4, 8, 33280, 128, 16, 128, 2048, 2048),
             mla_sparse=(2, 8, 128, 33280, 512, 64, 128, 128, 64, 128, 2048,
                         256),
             ring_stack=(12, 32, 4, 8, 2048),
             flash_band=(2, 8192, 32, 4, 2048),
             delta_prefill=(1, 2048, 16, 32, 128, 64),
             delta_prefill_channel=(8, 1024, 32, 32, 128, 32))
TINY = Sizes(preset="qwen2-tiny", train_layers=2, seq=64, loss_chunk=32,
             micro_batch=2, global_batch=8, prompt_lens=(8, 24),
             prompts_per_len=2, new_tokens=8, v2_slots=2, v2_max_seq=64,
             v2_chunk=16, block=16, flash_long=128, flash_narrow=(6, 2, 8),
             decode_batch=2,
             decode_ctx=64, dense_stack=((3, 2, 8, 64), (2, 4, 16, 32)),
             paged_batch=3, paged_blocks=9, prefill_batch=2,
             parked=(7, 4, 8), gmm_rows=64, gmm_experts=4, gmm_width=32,
             qmm_group=32, ssm=(2, 4, 4, 8, 16, 2),
             gmm_decode=(("", 32, 4, 32, 48), ("_ling", 32, 4, 48, 32)),
             held_rows=(288, 4, 64, 32, 1, 16),
             held_rows_long=(576, 4, 128, 32, 1, 16),
             diff_stack=((2, 3, 2, 16, 32), (1, 3, 2, 48, 32)),
             ssm_m1=(2, 4, 16, 256), kda=(2, 3, 4, 16),
             wide_heads=(2, 3, 2, 2, 64, 32, 64, 16, 48),
             mla=(2, 3, 4, 32, 32, 8),
             mla_wide=(2, 3, 128, 64, 32, 8),
             mla_dense=(2, 3, 4, 64, 32, 8, 16, 16, 16),
             sparse=(2, 3, 2, 2, 64, 16, 4, 8, 8, 16),
             mla_sparse=(2, 3, 4, 64, 32, 8, 16, 16, 4, 8, 8, 16),
             ring_stack=(2, 4, 2, 2, 16), flash_band=(1, 64, 4, 2, 24),
             delta_prefill=(1, 72, 1, 2, 128, 16),
             delta_prefill_channel=(2, 40, 2, 2, 128, 16))


def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj), flush=True)


def memory_stat(key: str, devices=None) -> List[int]:
    """`memory_stats()[key]` of each device (0 where the backend reports
    none). `peak_bytes_in_use` is a process-lifetime high-water mark: later
    phases only raise it. `bytes_in_use`, read while an engine is live,
    shows a mesh whose state all sits on device 0."""
    return [int((d.memory_stats() or {}).get(key, 0))
            for d in (jax.devices() if devices is None else devices)]


def check_every_device_holds(out: Dict[str, Any]) -> None:
    held = out["device_bytes_in_use"]
    if any(held) and not all(held):  # a backend without stats reports zeros
        raise AssertionError(f"a device of the mesh holds nothing: {held}")


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, NaN-propagating, in float32."""
    errs = []
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if g.shape != r.shape:
            return float("inf")
        errs.append(float(np.abs(g - r).max() / (np.abs(r).max() + 1e-9)))
    return max(errs)


# ------------------------------------------------------------- kernel cases


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One Pallas kernel at one shape: `fn` is the kernel as an engine
    calls it, `ref` the same math in `jax.numpy`, `make` a traceable
    `PRNGKey -> inputs` (so `jax.eval_shape(make, key)` gives the shapes
    tests/unit/ops/test_chip_compile.py compiles for the described chip)."""
    name: str
    fn: Callable
    ref: Callable
    make: Callable
    tol: float = FWD_TOL
    # (scope names, ms): on the chip the device time a call spends under
    # those scopes, by the trace joined to the program map, may not pass it
    scopes_ms: Optional[Tuple[Tuple[str, ...], float]] = None


def kernel_cases(sz: Sizes) -> List[KernelCase]:
    from deepspeed_tpu.inference.kv_cache import (PagedLayer,
                                                  _update_paged_layer,
                                                  dequantize_kv,
                                                  quantize_kv_tokens)
    from deepspeed_tpu.models.qwen2 import qwen2_config
    from deepspeed_tpu.moe.sharded_moe import (held_dispatch_gmm,
                                               held_row_bound, held_row_tile)
    from deepspeed_tpu.ops.attention import (blockwise_attention,
                                             reference_attention)
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention, padded_layout_indices)
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas.delta_rule import delta_rule_prefill
    from deepspeed_tpu.ops.pallas.decode_attention import (decode_attention,
                                                           decode_plan,
                                                           kv_write_dense)
    from deepspeed_tpu.ops.pallas import flash_attention as flash
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.grouped_gemm import (grouped_gemm,
                                                       held_tiling)
    from deepspeed_tpu.ops.pallas.held_combine import held_combine
    from deepspeed_tpu.ops.pallas.kda import (kda_state_update,
                                              kda_state_update_reference)
    from deepspeed_tpu.ops.pallas.mla import (latent_write_dense,
                                              mla_latent_decode,
                                              mla_latent_decode_reference)
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_kv_write, paged_prefill_attention)
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul
    from deepspeed_tpu.ops.pallas import mla_sparse as mlas
    from deepspeed_tpu.ops.pallas import sparse_select as sps
    from deepspeed_tpu.ops.pallas.diff_attention import (
        diff_decode_attention, diff_decode_attention_reference)
    from deepspeed_tpu.ops.pallas.ssm import (ssm_state_update,
                                              ssm_state_update_m1,
                                              ssm_state_update_m1_reference,
                                              ssm_state_update_reference)
    from deepspeed_tpu.ops.quantization import (dequantize_int8_blockwise,
                                                quantize_int8_blockwise)
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig

    cfg = qwen2_config(sz.preset)
    h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    hidden, ffn = cfg.hidden_size, cfg.intermediate_size
    bf16 = jnp.bfloat16
    cases: List[KernelCase] = []

    def normal(key, shape, dtype=bf16):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    # ---- flash attention (training; GQA) ----
    def qkv(b, s, h=h, hkv=hkv, d=d):
        def make(key):
            kq, kk, kv = jax.random.split(key, 3)
            return (normal(kq, (b, s, h, d)), normal(kk, (b, s, hkv, d)),
                    normal(kv, (b, s, hkv, d)))
        return make

    def flash_loss(attn):
        # a fixed non-uniform cotangent, so dq/dk/dv are not degenerate
        def loss(q, k, v):
            out = attn(q, k, v, causal=True).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(
                jnp.arange(out.shape[-1], dtype=jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))

    def blockwise_rows(q, k, v, causal):
        # 256 query rows a block: what its backward keeps is a block's
        # scores against the whole row, 0.5 GB a tensor at 32k
        return blockwise_attention(q, k, v, causal=causal, block_q=256)

    cases += [
        KernelCase(f"flash_fwd_s{sz.seq}",
                   lambda q, k, v: flash_attention(q, k, v, causal=True),
                   lambda q, k, v: reference_attention(q, k, v, causal=True),
                   qkv(sz.micro_batch, sz.seq)),
        KernelCase(f"flash_fwd_bwd_s{sz.seq}", flash_loss(flash_attention),
                   flash_loss(reference_attention),
                   qkv(sz.micro_batch, sz.seq), tol=BWD_TOL),
        # the (S, S) logits of the plain reference do not fit at 32k: the
        # blockwise online-softmax form is the jax.numpy reference there
        KernelCase(f"flash_fwd_s{sz.flash_long}",
                   lambda q, k, v: flash_attention(q, k, v, causal=True),
                   lambda q, k, v: blockwise_attention(q, k, v, causal=True),
                   qkv(1, sz.flash_long)),
        # the backward ONE kernel at head width 64 (train-2k's shape), and
        # past the length whose dq stays in VMEM: the two-pass form
        KernelCase(f"flash_fwd_bwd_s{sz.seq}_d{sz.flash_narrow[2]}",
                   flash_loss(flash_attention),
                   flash_loss(reference_attention),
                   qkv(sz.micro_batch, sz.seq, *sz.flash_narrow),
                   tol=BWD_TOL),
        KernelCase(f"flash_fwd_bwd_s{sz.flash_long}",
                   flash_loss(flash_attention), flash_loss(blockwise_rows),
                   qkv(1, sz.flash_long), tol=BWD_TOL),
    ]

    # ---- the banded flash forward (a prefill's window layers): against
    # the masked reference, a query block of rows at a time ----
    fb_rows, fb_s, fb_h, fb_hkv, fb_w = sz.flash_band

    def band_ref(q, k, v):
        """The masked reference for `size` queries at a time, each block
        against the keys its band can reach."""
        size = min(fb_s, 1024)
        back = -(-fb_w // size) * size          # whole blocks a window spans
        span = min(size + back, fb_s)

        def block(first):
            lo = jnp.clip(first - back, 0, fb_s - span)
            cut = lambda t, at, n: jax.lax.dynamic_slice_in_dim(t, at, n, 1)  # noqa: E731
            qi = first + jnp.arange(size)[:, None]
            kj = lo + jnp.arange(span)[None, :]
            keep = (kj <= qi) & (kj > qi - fb_w)
            return reference_attention(
                cut(q, first, size), cut(k, lo, span), cut(v, lo, span),
                causal=False, segment_mask=jnp.broadcast_to(
                    keep, (q.shape[0],) + keep.shape))
        out = jax.lax.map(block, jnp.arange(0, fb_s, size))
        return jnp.moveaxis(out, 0, 1).reshape(q.shape)

    cases.append(KernelCase(
        f"flash_band_s{fb_s}_w{fb_w}",
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=fb_w),
        band_ref, qkv(fb_rows, fb_s, fb_h, fb_hkv, d)))

    # ---- the forward in the projections' own order, (B, S, H, D) read as
    # (B, S, H x D), which an undifferentiated call at this head width
    # takes: EQUAL to the head-major kernel (the same bodies under other
    # block specs), band and triangular, at that prefill's shapes ----
    def head_major(window):
        blocks = (flash.BAND_BLOCK,) * 2 if window else (
            flash.DEFAULT_BLOCK_Q, flash.DEFAULT_BLOCK_K)

        def ref(q, k, v):
            return flash._swap(flash._flash_bhsd_fwd(
                q, k, v, d ** -0.5, True, *blocks, window)[0])
        return ref

    # LAST in the list: a case's inputs are drawn from seed + its index
    order_cases = [
        KernelCase(f"flash_fwd_tok_s{fb_s}",
                   lambda q, k, v: flash_attention(q, k, v, causal=True),
                   head_major(None), qkv(fb_rows, fb_s, fb_h, fb_hkv, d),
                   tol=0.0),
        KernelCase(f"flash_band_tok_s{fb_s}_w{fb_w}",
                   lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   window=fb_w),
                   head_major(fb_w), qkv(fb_rows, fb_s, fb_h, fb_hkv, d),
                   tol=0.0),
    ]

    # ---- dense decode (v1): one query per row over a padded cache ----
    db, dm = sz.decode_batch, sz.decode_ctx

    def make_decode(key):
        kq, kk, kv, kl = jax.random.split(key, 4)
        return (normal(kq, (db, 1, h, d)), normal(kk, (db, dm, hkv, d)),
                normal(kv, (db, dm, hkv, d)),
                jax.random.randint(kl, (db,), 1, dm + 1, jnp.int32))

    def decode_ref(q, k, v, lengths):
        mask = jnp.arange(k.shape[1])[None, None, :] < lengths[:, None, None]
        return reference_attention(q, k, v, causal=False, segment_mask=mask)

    def int8_kv(kernel, ref):
        """(kernel, reference) over int8-at-rest K/V: the kernel gets the
        quantized values and their per-token scales, the reference their
        dequantized form. Works for the dense caches and the paged pools
        alike: both quantize per (token, kv-head) over the head dim."""
        def fn(q, k, v, *rest):
            (kq, ks), (vq, vs) = quantize_kv_tokens(k), quantize_kv_tokens(v)
            return kernel(q, kq, vq, *rest, k_scales=ks, v_scales=vs)

        def fn_ref(q, k, v, *rest):
            deq = lambda x: dequantize_kv(*quantize_kv_tokens(x), q.dtype)
            return ref(q, deq(k), deq(v), *rest)
        return fn, fn_ref

    cases += [
        KernelCase("decode_bf16", decode_attention, decode_ref, make_decode),
        KernelCase("decode_int8kv", *int8_kv(decode_attention, decode_ref),
                   make_decode),
    ]

    # ---- the stacked dense cache (v1 generate): the kernel reads layer
    # `layer` of (L, B, Hkv, M, D) where it lies, with the step's new token
    # staged or already written; the writer lands a step's tokens ----
    def make_stack(layers, rows, n_rep, m, mixed=False, hkv=hkv, d=d):
        def make(key):
            kq, kk, kv, kl, kn = jax.random.split(key, 5)
            # the cursors; the last row parked (it has no slot: the kernel
            # and the writer drop its token)
            index = jax.random.randint(kl, (rows,), 0, m,
                                       jnp.int32).at[-1].set(m)
            if mixed:
                # the FIRST row group of a grid step (`decode_plan`): a
                # block's last slot, the next block's first, a parked row
                # and a single token side by side; then the cache's last slot
                blk_k = decode_plan(rows, hkv, m, d, 2)[1]
                index = index.at[:5].set(jnp.asarray(
                    [blk_k - 1, blk_k, m, 0, m - 1], jnp.int32)[:rows])
            return (normal(kq, (rows, 1, hkv * n_rep, d)),
                    normal(kk, (layers, rows, hkv, m, d)),
                    normal(kv, (layers, rows, hkv, m, d)), index,
                    normal(kn, (2, layers, rows, hkv, d)),
                    jnp.int32(layers - 1))
        return make

    def stack_ref(q, k, v, index, new, layer, staged=False):
        k, v = (jnp.swapaxes(x[layer], 1, 2) for x in (k, v))  # (B, M, Hkv, D)
        if staged:
            rows = jnp.arange(q.shape[0])
            k = k.at[rows, index].set(new[0, layer], mode="drop")
            v = v.at[rows, index].set(new[1, layer], mode="drop")
        return decode_ref(q, k, v, index + 1)

    def stack_write_ref(q, k, v, index, new, layer):
        rows = jnp.arange(q.shape[0])
        return tuple(x.at[:, rows, :, index].set(
            jnp.moveaxis(n, 1, 0), mode="drop") for x, n in ((k, new[0]),
                                                             (v, new[1])))

    def stack_staged(q, k, v, index, new, layer):
        return decode_attention(q, k, v, index + 1, layer=layer,
                                k_new=new[0, layer], v_new=new[1, layer])

    # ---- the same kernel over a RING (a window layer's cache): a count of
    # live slots and the staged token's slot; rows not yet full, exactly
    # full and wrapped side by side ----
    rl, rrows, rkv, rrep, rm = sz.ring_stack

    def make_ring(key):
        kq, kk, kv, kl, kn = jax.random.split(key, 5)
        # positions so far: under the ring's length, at it, and far past it
        index = jax.random.randint(kl, (rrows,), 0, 5 * rm, jnp.int32)
        index = index.at[:4].set(jnp.asarray([0, rm - 1, rm, 3 * rm + 5],
                                             jnp.int32))
        return (normal(kq, (rrows, 1, rkv * rrep, d)),
                normal(kk, (rl, rrows, rkv, rm, d)),
                normal(kv, (rl, rrows, rkv, rm, d)), index,
                normal(kn, (2, rl, rrows, rkv, d)), jnp.int32(rl - 1))

    def ring_staged(q, k, v, index, new, layer):
        return decode_attention(q, k, v, jnp.minimum(index + 1, rm),
                                layer=layer, k_new=new[0, layer],
                                v_new=new[1, layer], slots=index % rm)

    def ring_ref(q, k, v, index, new, layer):
        k, v = (jnp.swapaxes(x[layer], 1, 2) for x in (k, v))  # (B, M, Hkv, D)
        rows = jnp.arange(q.shape[0])
        k = k.at[rows, index % rm].set(new[0, layer])
        v = v.at[rows, index % rm].set(new[1, layer])
        return decode_ref(q, k, v, jnp.minimum(index + 1, rm))

    cases.append(KernelCase(f"decode_ring_l{rl}_b{rrows}_r{rrep}_m{rm}",
                            ring_staged, ring_ref, make_ring))

    for layers, rows, n_rep, m in sz.dense_stack:
        shape = f"l{layers}_b{rows}_r{n_rep}_m{m}"
        cases += [
            KernelCase(f"decode_stacked_{shape}",
                       lambda q, k, v, index, new, layer: decode_attention(
                           q, k, v, index + 1, layer=layer),
                       stack_ref, make_stack(layers, rows, n_rep, m)),
            KernelCase(f"decode_stacked_staged_{shape}", stack_staged,
                       lambda *a: stack_ref(*a, staged=True),
                       make_stack(layers, rows, n_rep, m)),
            KernelCase(f"decode_stacked_staged_mixed_{shape}", stack_staged,
                       lambda *a: stack_ref(*a, staged=True),
                       make_stack(layers, rows, n_rep, m, mixed=True)),
            KernelCase(f"kv_write_dense_{shape}",
                       lambda q, k, v, index, new, layer: kv_write_dense(
                           k, v, new[0], new[1], index),
                       stack_write_ref, make_stack(layers, rows, n_rep, m)),
        ]

    # ---- paged decode / prefill (v2): block tables over a shared pool ----
    bs, nb, t = sz.block, sz.paged_blocks, sz.v2_max_seq // sz.block

    def make_paged(batch, s, layers=0, t=t, live_every=1, ragged=False):
        """`layers` > 0: the pools are a stack of that many, as the v2
        programs hold them, and the last input is the layer to read (the
        last one, so that a kernel that read layer 0 would be wrong).
        `live_every` > 1: only every so-manieth row holds a request; the
        others are parked as the v2 engine parks them, past capacity with a
        table of -1 (docs/kv_cache.md). `ragged`: the live rows' lengths
        run from 1 to `t * bs - 1`, evenly, instead of being drawn."""
        pool = ((layers,) if layers else ()) + (hkv, nb, bs, d)

        def make(key):
            kq, kk, kv, kt, kl, kn = jax.random.split(key, 6)
            # rows may share physical blocks: the kernels only read them
            tables = jax.random.randint(kt, (batch, t), 0, nb, jnp.int32)
            # decode: valid tokens per row; prefill: where the s new start
            cursor = jax.random.randint(kl, (batch,), 1, t * bs - s + 1,
                                        jnp.int32)
            if ragged:
                n_live = -(-batch // live_every)
                cursor = 1 + (jnp.arange(batch) // live_every) * (
                    t * bs - 2) // max(n_live - 1, 1)
            live = jnp.arange(batch) % live_every == 0
            tables = jnp.where(live[:, None], tables, -1)
            cursor = jnp.where(live, cursor, t * bs + 1)
            new = normal(kn, (2, batch, hkv, d))
            out = (normal(kq, (batch, s, h, d)), normal(kk, pool),
                   normal(kv, pool), tables, cursor, new)
            return out + ((jnp.int32(layers - 1),) if layers else ())
        return make

    def dense_view(pool, tables):
        # (Hkv, NB, BS, D) through (B, T) tables -> (B, T*BS, Hkv, D)
        rows = jnp.take(pool, tables, axis=1)        # (Hkv, B, T, BS, D)
        rows = jnp.moveaxis(rows, 0, 3)              # (B, T, BS, Hkv, D)
        return rows.reshape(tables.shape[0], -1, pool.shape[0], pool.shape[3])

    def paged_ref(q, kp, vp, tables, lengths, new, staged=False):
        k, v = dense_view(kp, tables), dense_view(vp, tables)
        if staged:  # the row's last valid token is the staged one
            rows = jnp.arange(q.shape[0])
            k = k.at[rows, lengths - 1].set(new[0])
            v = v.at[rows, lengths - 1].set(new[1])
        return decode_ref(q, k, v, lengths)

    def prefill_ref(q, kp, vp, tables, starts, new):
        k, v = dense_view(kp, tables), dense_view(vp, tables)
        pos = starts[:, None] + jnp.arange(q.shape[1])[None, :]     # (B, S)
        mask = jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None]
        return reference_attention(q, k, v, causal=False, segment_mask=mask)

    def paged_decode(q, kp, vp, tables, lengths, new, layer=None, **scales):
        return paged_decode_attention(q, kp, vp, tables, lengths,
                                      layer=layer, **scales)

    def paged_prefill(q, kp, vp, tables, starts, new, layer=None, **scales):
        return paged_prefill_attention(q, kp, vp, tables, starts,
                                       layer=layer, **scales)

    def of_layer(ref, **kw):
        """`ref` on the layer the stacked case reads, cut out by hand."""
        return lambda q, kp, vp, tb, cur, new, layer: ref(
            q, kp[layer], vp[layer], tb, cur, new, **kw)

    def parked_rows(ref, staged=False):
        """`ref` where a row holds a request; a parked row comes back as
        zeros, or as its staged value for every head of the group."""
        def fn(q, kp, vp, tb, cur, new, layer):
            out = ref(q, kp, vp, tb, cur, new, layer)
            alone = jnp.repeat(new[1], h // hkv, axis=1)[:, None] \
                if staged else 0.0
            parked = (cur > tb.shape[1] * bs)[:, None, None, None]
            return jnp.where(parked, jnp.asarray(alone, out.dtype), out)
        return fn

    def staged_stacked(q, kp, vp, tb, ln, new, layer):
        """What a v2 decode round hands over: the whole stacked pool, the
        layer to read, the step's new token staged."""
        return paged_decode_attention(q, kp, vp, tb, ln, k_new=new[0],
                                      v_new=new[1], layer=layer)

    pb, fb = sz.paged_batch, sz.prefill_batch
    cases += [
        KernelCase("paged_decode_bf16", paged_decode, paged_ref,
                   make_paged(pb, 1)),
        KernelCase("paged_decode_staged",
                   lambda q, kp, vp, tb, ln, new: paged_decode_attention(
                       q, kp, vp, tb, ln, k_new=new[0], v_new=new[1]),
                   lambda *a: paged_ref(*a, staged=True), make_paged(pb, 1)),
        KernelCase("paged_decode_int8kv", *int8_kv(paged_decode, paged_ref),
                   make_paged(pb, 1)),
        KernelCase("paged_decode_stacked_staged", staged_stacked,
                   of_layer(paged_ref, staged=True), make_paged(pb, 1, 3)),
        KernelCase("paged_decode_stacked_int8kv",
                   *int8_kv(paged_decode, of_layer(paged_ref)),
                   make_paged(pb, 1, 3)),
        # the serving cell's decode half, one row in six holding a request:
        # the others cost the kernel a step that copies nothing (PRs 31, 46)
        KernelCase("paged_decode_parked", staged_stacked,
                   parked_rows(of_layer(paged_ref, staged=True), staged=True),
                   make_paged(sz.parked[0], 1, 3, t=sz.parked[1],
                              live_every=6)),
        # the same batch with every length a row can have between its live
        # rows, one token to a token short of capacity, parked rows between
        # them: each row walks its own blocks and no other (PR 46)
        KernelCase("paged_decode_ragged", staged_stacked,
                   parked_rows(of_layer(paged_ref, staged=True), staged=True),
                   make_paged(sz.parked[0], 1, 3, t=sz.parked[1],
                              live_every=2, ragged=True)),
        KernelCase("paged_decode_ragged_int8kv",
                   *int8_kv(paged_decode, parked_rows(of_layer(paged_ref))),
                   make_paged(sz.parked[0], 1, 3, t=sz.parked[1],
                              live_every=2, ragged=True)),
    ]
    # the slowest to compile (8 s each for the described chip): last, so
    # a test window that closes early has seen the other twenty-seven
    slow_cases = [
        KernelCase("paged_prefill_bf16", paged_prefill, prefill_ref,
                   make_paged(fb, sz.v2_chunk)),
        KernelCase("paged_prefill_int8kv",
                   *int8_kv(paged_prefill, prefill_ref),
                   make_paged(fb, sz.v2_chunk)),
        KernelCase("paged_prefill_stacked_bf16", paged_prefill,
                   of_layer(prefill_ref), make_paged(fb, sz.v2_chunk, 3)),
        KernelCase("paged_prefill_stacked_int8kv",
                   *int8_kv(paged_prefill, of_layer(prefill_ref)),
                   make_paged(fb, sz.v2_chunk, 3)),
        # its wide chunk half (`fused_batch:16:48`), as empty
        KernelCase("paged_prefill_parked", paged_prefill,
                   parked_rows(of_layer(prefill_ref)),
                   make_paged(sz.parked[0], sz.parked[2], 3, t=sz.parked[1],
                              live_every=6)),
    ]

    # ---- the paged pools' writer: new tokens into the stack, in place ----
    def make_write(layers, s):
        """Stacked pools, `layers` layers' worth of `s` new tokens a row
        (a chunk into layer 2; a staged token into every layer), rows that
        own their blocks (a block two rows wrote would have two orders),
        one row parked."""
        def make(key):
            kk, kv, kn, kl = jax.random.split(key, 4)
            tables = jnp.arange(fb * t, dtype=jnp.int32).reshape(fb, t)
            starts = jax.random.randint(kl, (fb,), 0, t * bs - s + 1,
                                        jnp.int32).at[0].set(t * bs)
            return (normal(kk, (3, hkv, nb, bs, d)),
                    normal(kv, (3, hkv, nb, bs, d)),
                    normal(kn, (2, layers, fb, s, hkv, d)), tables, starts)
        return make

    def kv_write(first):
        def fn(kp, vp, new, tables, starts):
            return paged_kv_write(kp, vp, new[0], new[1], tables, starts,
                                  first)[:2]

        def ref(kp, vp, new, tables, starts):
            for i in range(new.shape[1]):  # the XLA scatter, layer by layer
                kp, vp = (_update_paged_layer(
                    PagedLayer(pool=p, tables=tables,
                               layer=jnp.int32(first + i)), x[i], starts).pool
                    for p, x in ((kp, new[0]), (vp, new[1])))
            return kp, vp
        return fn, ref

    cases += [KernelCase("kv_write_chunk", *kv_write(2),
                         make_write(1, sz.v2_chunk)),
              KernelCase("kv_write_stage", *kv_write(0), make_write(3, 1))]

    # ---- fused int8 dequant-GEMM: every projection shape, three M ----
    def make_qmm(m, k, n):
        def make(key):
            kx, kw = jax.random.split(key)
            w = jax.random.normal(kw, (k, n), jnp.float32)
            return (normal(kx, (m, k)),) + quantize_int8_blockwise(
                w, sz.qmm_group)
        return make

    def qmm_ref(x, q, s):
        w = dequantize_int8_blockwise(q, s)
        return (x.astype(jnp.float32) @ w).astype(x.dtype)

    for k, n in ((hidden, h * d), (hidden, hkv * d), (hidden, ffn),
                 (ffn, hidden)):
        for m in (1, 32, 256):
            cases.append(KernelCase(f"qmm_{k}x{n}_m{m}", quantized_matmul,
                                    qmm_ref, make_qmm(m, k, n)))

    # ---- megablox grouped GEMM (MoE experts) ----
    rows, ne, width = sz.gmm_rows, sz.gmm_experts, sz.gmm_width

    def make_gmm(key):
        kl, kr = jax.random.split(key)
        return (normal(kl, (rows, hidden)),
                normal(kr, (ne, hidden, width)) * 0.05,
                jnp.full((ne,), rows // ne, jnp.int32))

    def gmm_ref(lhs, rhs, sizes):
        # equal groups: rows of group g are a contiguous slab
        out = jnp.einsum("gmk,gkn->gmn", lhs.reshape(ne, rows // ne, hidden),
                         rhs, preferred_element_type=jnp.float32)
        return out.reshape(rows, width).astype(lhs.dtype)

    cases.append(KernelCase("grouped_gemm", grouped_gemm, gmm_ref, make_gmm))

    # the same kernel as a held expert layer calls it at decode: a 16-row
    # tile and the whole K (`held_tiling`), groups that straddle the row
    # tiles, and rows after the last group that are no expert's and must
    # cost nothing (moe/sharded_moe.held_dispatch_gmm)
    def gmm_decode_case(suffix, drows, dne, dk, dn):
        sizes = 1 + (np.arange(dne) * 5) % 7
        held = int(sizes.sum())
        assert held <= drows

        def make(key):
            kl, kr = jax.random.split(key)
            return (normal(kl, (drows, dk)), normal(kr, (dne, dk, dn)) * 0.05,
                    jnp.asarray(sizes, jnp.int32))

        def held_rows(out):
            return jnp.where(jnp.arange(drows)[:, None] < held, out, 0)

        def fn(lhs, rhs, sizes):
            return held_rows(grouped_gemm(lhs, rhs, sizes,
                                          tiling=held_tiling(16, dk, dn)))

        def ref(lhs, rhs, sizes):
            w = jnp.repeat(rhs, sizes, axis=0, total_repeat_length=held)
            out = jnp.einsum("mk,mkn->mn", lhs[:held], w,
                             preferred_element_type=jnp.float32)
            return held_rows(jnp.pad(out, ((0, drows - held), (0, 0)))
                             ).astype(lhs.dtype)

        return KernelCase(f"grouped_gemm_decode{suffix}", fn, ref, make)

    cases.extend(gmm_decode_case(*shape) for shape in sz.gmm_decode)

    # a held expert layer at a prefill chunk's shape and at a long prefill's:
    # sorted rows sized by the chip's share (`held_row_bound`), against the
    # full-width body on the same routing; `dispatch` + `combine` timed by
    # the trace (read on the chip, PERF.md PR 61: 0.57 and 2.15 ms; the
    # choice is a program PARAMETER here, (T, k) int32 sixteen times padded,
    # and `dispatch` reads 0.19 and 1.25 ms of those where a model's own
    # `top_k` feeds it and it reads 0.04 and 0.33)
    def held_case(name, shape, limit_ms):
        ht, hk, hd, hf, hheld, hexperts = shape
        htile = held_row_tile(ht * hk, hexperts)
        bound = held_row_bound(ht * hk, hheld, hexperts, htile)
        assert bound < ht * hk

        def make_held(key):
            kx, kr, *kw = jax.random.split(key, 5)
            gate, idx = jax.lax.top_k(jax.nn.sigmoid(
                jax.random.normal(kr, (ht, hexperts), jnp.float32)), hk)
            return (normal(kx, (ht, hd)), gate / gate.sum(-1, keepdims=True),
                    idx.astype(jnp.int32),
                    normal(kw[0], (hheld, hd, hf)) * 0.02,
                    normal(kw[1], (hheld, hd, hf)) * 0.02,
                    normal(kw[2], (hheld, hf, hd)) * 0.02)

        def held_layer(bounded):
            def fn(x, gate, idx, w_gate, w_up, w_down):
                def grouped(rows, sizes):
                    def gg(lhs, rhs):
                        return grouped_gemm(lhs, rhs, sizes, tiling=(
                            htile, min(lhs.shape[1], 1024),
                            min(rhs.shape[2], 1024)))
                    return gg(jax.nn.silu(gg(rows, w_gate)) * gg(rows, w_up),
                              w_down)
                return held_dispatch_gmm(x, gate, idx, 0, hheld, grouped,
                                         bound=bound if bounded else None)[0]
            return fn

        return KernelCase(name, held_layer(True), held_layer(False), make_held,
                          scopes_ms=(("dispatch", "combine"), limit_ms))

    cases.append(held_case("held_rows", sz.held_rows, 1.5))
    cases.append(held_case("held_rows_long", sz.held_rows_long, 2.8))

    # the way back ALONE at the long shape, against each token's float32
    # sum of its rows found through the sort; what lies past the held rows
    # is large, so a row read from there shows
    ct, ck, cd, _, cheld, cexperts = sz.held_rows_long
    cbound = held_row_bound(ct * ck, cheld, cexperts,
                            held_row_tile(ct * ck, cexperts))

    def make_combine(key):
        kr, ko = jax.random.split(key)
        gate, idx = jax.lax.top_k(jax.nn.sigmoid(
            jax.random.normal(kr, (ct, cexperts), jnp.float32)), ck)
        local = jnp.where(idx < cheld, idx, cheld).astype(jnp.int32)
        rows = jnp.arange(cbound)[:, None] < jnp.sum(local < cheld)
        return (jnp.where(rows, normal(ko, (cbound, cd)), 1e4
                          ).astype(jnp.bfloat16), local, gate)

    def combine_ref(out_s, local, gate):
        place = jnp.argsort(jnp.argsort(local.reshape(-1))).reshape(ct, ck)
        rows = jnp.take(out_s, jnp.minimum(place, cbound - 1), axis=0)
        return jnp.sum(jnp.where(local < cheld, gate, 0.0)[:, :, None]
                       * rows.astype(jnp.float32), axis=1)   # no MXU rounding

    cases.append(KernelCase(
        "held_combine", lambda *a: held_combine(*a, cheld), combine_ref,
        make_combine, tol=1e-5))

    # ---- the recurrent-state update of a Mamba-2 decode step ----
    sl, sb, sh, sp, sn, sg = sz.ssm

    def make_ssm(key):
        ks = jax.random.split(key, 7)
        f32 = jnp.float32
        return (normal(ks[0], (sl, sb, sh, sp, sn), f32),
                normal(ks[1], (sb, sh, sp), f32),
                jax.nn.softplus(normal(ks[2], (sb, sh), f32) - 3.0),
                -jnp.exp(normal(ks[3], (sh,), f32)),
                normal(ks[4], (sb, sg, sn), f32),
                normal(ks[5], (sb, sg, sn), f32), normal(ks[6], (sh,), f32))

    cases.append(KernelCase(
        "ssm_state_update",
        lambda state, *rest: ssm_state_update(state, sl - 1, *rest),
        lambda state, *rest: ssm_state_update_reference(state, sl - 1, *rest),
        make_ssm))

    # ---- a Mamba-1 decode step: the state lies (L, B, N, C) ----
    ml, mb, mn, mc = sz.ssm_m1

    def make_ssm_m1(key):
        ks = jax.random.split(key, 7)
        f32 = jnp.float32
        return (normal(ks[0], (ml, mb, mn, mc), f32),
                normal(ks[1], (mb, mc), f32),
                jax.nn.softplus(normal(ks[2], (mb, mc), f32) - 3.0),
                -jnp.exp(normal(ks[3], (mn, mc), f32)),
                normal(ks[4], (mb, mn), f32), normal(ks[5], (mb, mn), f32),
                normal(ks[6], (mc,), f32))

    cases.append(KernelCase(
        "ssm_state_update_m1",
        lambda state, *rest: ssm_state_update_m1(state, ml - 1, *rest),
        lambda state, *rest: ssm_state_update_m1_reference(state, ml - 1,
                                                           *rest),
        make_ssm_m1))

    # ---- differential decode attention: a ring, then the shared slab ----
    for stack, ring in zip(sz.diff_stack, (True, False)):
        def make_diff(key, shape=stack, ring=ring):
            l, b, g, m, w = shape
            kq, kk, kv, kn, kl = jax.random.split(key, 5)
            pos = jax.random.randint(kl, (b,), 0, 2 * m if ring else m,
                                     jnp.int32)
            # two pairs a group: [q1 | 0] twice, then [0 | q2] twice
            q = normal(kq, (b, g, 4, w))
            half = (jnp.arange(w) < w // 2)[None, None, None, :]
            first = (jnp.arange(4) < 2)[None, None, :, None]
            return (jnp.where(half == first, q, 0).astype(bf16),
                    normal(kk, (l, b, g, m, w)), normal(kv, (l, b, g, m, w)),
                    jnp.minimum(pos + 1, m), pos % m,
                    normal(kn, (2, b, g, w)))

        def run_diff(fn, q, k, v, lengths, slots, new, ring=ring,
                     layer=stack[0] - 1):
            return fn(q, k, v, layer, lengths, jnp.float32(0.7), 0.125,
                      k_new=new[0], v_new=new[1], slots=slots, ring=ring)

        cases.append(KernelCase(
            "diff_attn_window_decode" if ring else "diff_attn_shared_decode",
            functools.partial(run_diff, diff_decode_attention),
            functools.partial(run_diff, diff_decode_attention_reference),
            make_diff))

    # ---- a KDA decode step: a head's state a d x d matrix, (L, B, H, d, d) ----
    kl, kb, kh, kd = sz.kda

    def make_kda(key):
        ks = jax.random.split(key, 6)
        f32 = jnp.float32
        unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
        return (normal(ks[0], (kl, kb, kh, kd, kd), f32),
                unit(normal(ks[1], (kb, kh, kd), f32)) * kd ** -0.5,
                unit(normal(ks[2], (kb, kh, kd), f32)),
                normal(ks[3], (kb, kh, kd), f32),
                -5.0 * jax.nn.sigmoid(normal(ks[4], (kb, kh, kd), f32) - 2.0),
                jax.nn.sigmoid(normal(ks[5], (kb, kh), f32)))

    cases.append(KernelCase(
        "kda_state_update",
        lambda state, *rest: kda_state_update(state, kl - 1, *rest),
        lambda state, *rest: kda_state_update_reference(state, kl - 1, *rest),
        make_kda))

    # ---- latent (MLA) decode, absorbed, and the latent cache's writer ----
    ll, lb, lh, lm, lrank, lrope = sz.mla

    def make_mla(key, shape=sz.mla):
        ll, lb, lh, lm, lrank, lrope = shape
        kq, kr, kc, kn, kp = jax.random.split(key, 5)
        pos = jax.random.randint(kp, (lb,), 0, lm, jnp.int32)
        return (normal(kq, (lb, lh, lrank)), normal(kr, (lb, lh, lrope)),
                normal(kc, (ll, lb, 1, lm, lrank + lrope)), pos,
                normal(kn, (lb, lrank + lrope)))

    def run_mla(fn, q_lat, q_rope, stack, pos, new):
        return fn(q_lat, q_rope, stack, stack.shape[0] - 1, pos + 1,
                  stack.shape[-1] ** -0.5, new=new, slots=pos)

    cases.append(KernelCase(
        "mla_latent_decode", functools.partial(run_mla, mla_latent_decode),
        functools.partial(run_mla, mla_latent_decode_reference), make_mla))
    # 128 heads over rows of 25,600 slots: the plan `mla.decode_block` takes
    # where the heads' operations meet the slab's bytes
    cases.append(KernelCase(
        "mla_latent_decode_h128",
        functools.partial(run_mla, mla_latent_decode),
        functools.partial(run_mla, mla_latent_decode_reference),
        functools.partial(make_mla, shape=sz.mla_wide)))
    cases.append(KernelCase(
        "latent_write_dense",
        lambda q_lat, q_rope, stack, pos, new: latent_write_dense(
            stack, jnp.broadcast_to(new[None], (ll,) + new.shape), pos),
        lambda q_lat, q_rope, stack, pos, new: stack.at[
            :, jnp.arange(lb), 0, pos].set(
                jnp.broadcast_to(new[None], (ll,) + new.shape)),
        make_mla))

    # ---- learned sparse attention: a decode step's choice, attention under
    # it, and a prefill chunk against a row's slabs. The index operands are
    # small integers: their scores are exact in any order of summation, tie
    # in plenty, and kernel and plain form make the SAME choice ----
    (sl, sb, shkv, srep, sm, sd, shi, sdi, stopk, schunk) = sz.sparse
    sh = shkv * srep

    def small(key, shape):
        return jax.random.randint(key, shape, -3, 4).astype(bf16)

    def make_sparse_decode(key):
        ks = jax.random.split(key, 10)
        lengths = jax.random.randint(ks[9], (sb,), sm // 2, sm + 1, jnp.int32)
        q_i, w = small(ks[0], (sb, shi, sdi)), small(ks[1], (sb, shi))
        keys, new_i = small(ks[2], (sl, sb, 1, sm, sdi)), small(ks[3], (sb, sdi))
        bias, _ = sps.sparse_index_select_reference(
            q_i, w, keys, sl - 1, lengths, stopk, new_i)
        return (q_i, w, keys, lengths, new_i, bias, normal(ks[4], (sb, sh, sd)),
                normal(ks[5], (sl, sb, shkv, sm, sd)),
                normal(ks[6], (sl, sb, shkv, sm, sd)),
                normal(ks[7], (sb, shkv, sd)), normal(ks[8], (sb, shkv, sd)))

    def run_select(fn, q_i, w, keys, lengths, new_i, *rest):
        # 1 at a kept slot (the bias itself is 0 or -1e30), and the row's
        # count of them as the kernel made it, of `topk`
        bias, kept = fn(q_i, w, keys, sl - 1, lengths, stopk, new_i)
        return jnp.concatenate([(bias == 0.0).astype(jnp.float32),
                                kept[:, None] / stopk], axis=1)

    def run_sparse_decode(fn, q_i, w, keys, lengths, new_i, bias, q, k, v, kn,
                          vn):
        return fn(q, k, v, sl - 1, lengths, bias, sd ** -0.5, kn, vn)

    cases.append(KernelCase(
        "sparse_index_select",
        functools.partial(run_select, sps.sparse_index_select),
        functools.partial(run_select, sps.sparse_index_select_reference),
        make_sparse_decode))
    cases.append(KernelCase(
        "sparse_attn_decode",
        functools.partial(run_sparse_decode, sps.sparse_attn_decode),
        functools.partial(run_sparse_decode, sps.sparse_attn_decode_reference),
        make_sparse_decode))

    def make_sparse_prefill(key):
        ks = jax.random.split(key, 6)
        return (normal(ks[0], (schunk, sh, sd)), small(ks[1], (schunk, shi, sdi)),
                small(ks[2], (schunk, shi)),
                normal(ks[3], (sl, sb, shkv, sm, sd)),
                normal(ks[4], (sl, sb, shkv, sm, sd)),
                small(ks[5], (sl, sb, 1, sm, sdi)))

    def run_sparse_prefill(fn, *ops):
        # the row's last chunk but one: a causal edge inside the slab
        out, kept = fn(*ops, sl - 1, sb - 1, sm - 2 * schunk, stopk,
                       sd ** -0.5)
        return jnp.concatenate([out.reshape(schunk, -1).astype(jnp.float32),
                                kept[:, None] / stopk], axis=1)

    cases.append(KernelCase(
        "sparse_attn_prefill",
        functools.partial(run_sparse_prefill, sps.sparse_attn_prefill),
        functools.partial(run_sparse_prefill,
                          sps.sparse_attn_prefill_reference),
        make_sparse_prefill))

    # ---- latent attention under the learned choice: a decode step over the
    # chosen rows in both forms of its read, and a prefill chunk (choice at
    # this family's index sizes, then the expanded flash pass) ----
    (ml, mb, mh, mm, mrank, mrope, mdn, mdv, mhi, mdi, mtopk,
     mchunk) = sz.mla_sparse

    def make_mla_sparse_decode(key):
        ks = jax.random.split(key, 8)
        lengths = jax.random.randint(ks[7], (mb,), mm // 2, mm + 1, jnp.int32)
        q_i, w = small(ks[0], (mb, mhi, mdi)), small(ks[1], (mb, mhi))
        keys, new_i = small(ks[2], (ml, mb, 1, mm, mdi)), small(ks[3], (mb, mdi))
        bias, kept = sps.sparse_index_select_reference(
            q_i, w, keys, ml - 1, lengths, mtopk, new_i)
        return (normal(ks[4], (mb, mh, mrank)), normal(ks[5], (mb, mh, mrope)),
                normal(ks[6], (ml, mb, 1, mm, mrank + mrope)), lengths, bias,
                kept, normal(ks[0], (mb, mrank + mrope)))

    mscale = (mdn + mrope) ** -0.5
    cases.append(KernelCase(
        "mla_sparse_decode",
        lambda ql, qr, lat, n, bias, kept, new: mlas.mla_sparse_decode(
            ql, qr, lat, ml - 1, n, bias, mscale, new),
        lambda ql, qr, lat, n, bias, kept, new:
            mlas.mla_sparse_decode_reference(ql, qr, lat, ml - 1, n, bias,
                                             mscale, new),
        make_mla_sparse_decode))
    cases.append(KernelCase(
        "mla_sparse_decode_gathered",
        lambda ql, qr, lat, n, bias, kept, new:
            mlas.mla_sparse_decode_gathered(ql, qr, lat, ml - 1, n, bias, kept,
                                            mtopk, mscale, new),
        lambda ql, qr, lat, n, bias, kept, new:
            mlas.mla_sparse_decode_reference(ql, qr, lat, ml - 1, n, bias,
                                             mscale, new),
        make_mla_sparse_decode))

    def make_mla_sparse_prefill(key):
        ks = jax.random.split(key, 7)
        return (normal(ks[0], (mchunk, mh, mdn)),
                normal(ks[1], (mchunk, mh, mrope)),
                normal(ks[2], (mrank, mh, mdn + mdv)) * mrank ** -0.5,
                small(ks[3], (mchunk, mhi, mdi)), small(ks[4], (mchunk, mhi)),
                normal(ks[5], (ml, mb, 1, mm, mrank + mrope)),
                small(ks[6], (ml, mb, 1, mm, mdi)))

    def run_mla_sparse_prefill(attend, choose, qn, qr, w_kvb, q_i, w, lat,
                               keys):
        # the row's last chunk but one: a causal edge inside the slab
        row, start = mb - 1, mm - 2 * mchunk
        bias, kept = choose(q_i, w, keys, row, start)
        out = attend(qn, qr, w_kvb, bias, lat, ml - 1, row, start, mscale)
        return jnp.concatenate([out.reshape(mchunk, -1).astype(jnp.float32),
                                kept[:, None] / mtopk], axis=1)

    def choose_plain(q_i, w, keys, row, start):
        return sps.choice_plain(q_i, w, keys[ml - 1, row, 0],
                                start + jnp.arange(mchunk), mtopk)

    cases.append(KernelCase(
        "mla_sparse_prefill",
        functools.partial(
            run_mla_sparse_prefill, mlas.mla_sparse_prefill,
            lambda q_i, w, keys, row, start: sps.sparse_prefill_choice(
                q_i, w, keys, ml - 1, row, start, mtopk)),
        functools.partial(run_mla_sparse_prefill,
                          mlas.mla_sparse_prefill_reference, choose_plain),
        make_mla_sparse_prefill))

    # ---- the same prefill kernel with NO bias: every cached row up to the
    # query's own (openPangu's dense latent attention), a chunk whose causal
    # edge lies inside the slab ----
    (dl, db, dh, dm, drank, drope, ddn, ddv, dchunk) = sz.mla_dense

    def make_mla_dense_prefill(key):
        ks = jax.random.split(key, 4)
        return (normal(ks[0], (dchunk, dh, ddn)),
                normal(ks[1], (dchunk, dh, drope)),
                normal(ks[2], (drank, dh, ddn + ddv)) * drank ** -0.5,
                normal(ks[3], (dl, db, 1, dm, drank + drope)))

    def run_mla_dense_prefill(fn, qn, qr, w_kvb, lat):
        return fn(qn, qr, w_kvb, lat, dl - 1, db - 1, dm - 2 * dchunk,
                  (ddn + drope) ** -0.5).astype(jnp.float32)

    cases.append(KernelCase(
        "mla_dense_prefill",
        functools.partial(run_mla_dense_prefill, mlas.mla_dense_prefill),
        functools.partial(run_mla_dense_prefill,
                          mlas.mla_dense_prefill_reference),
        make_mla_dense_prefill))

    # ---- block-sparse attention (MHA; layout is static host data) ----
    sblk = min(64, sz.seq // 4)
    layout = BigBirdSparsityConfig(num_heads=h, block=sblk).make_layout(sz.seq)
    idx, nlive = padded_layout_indices(np.asarray(layout))

    def make_sparse(key):
        kq, kk, kv = jax.random.split(key, 3)
        shape = (1, sz.seq, h, d)
        return normal(kq, shape), normal(kk, shape), normal(kv, shape)

    def sparse_ref(q, k, v):
        # the block layout expanded to an elementwise (H, S, S) mask
        mask = np.kron(np.asarray(layout, bool), np.ones((sblk, sblk), bool))
        mask &= np.tril(np.ones((sz.seq, sz.seq), bool))[None]
        return reference_attention(q, k, v, causal=False,
                                   segment_mask=jnp.asarray(mask)[None])

    cases.append(KernelCase(
        "block_sparse",
        lambda q, k, v: block_sparse_attention(q, k, v, idx, nlive, sblk,
                                               causal=True),
        sparse_ref, make_sparse))
    # ---- a decay a HEAD (Gated DeltaNet): the same body, a second name ----
    def make_gdn(key):
        state, q, k, v, g, beta = make_kda(key)
        return state, q, k, v, g[..., 0], beta

    def gdn_ref(state, q, k, v, g, beta):
        return kda_state_update_reference(
            state, kl - 1, q, k, v, jnp.broadcast_to(g[..., None], q.shape),
            beta)

    # ---- softmax attention at head width 256, 2 KV heads, n_rep 8: the
    # flash forward over a whole sequence and over a CHUNK of a cached row,
    # the dense decode kernel over the stack with the token staged ----
    wl, wb, wkv, wrep, wm, wd, ws, wc, wcm = sz.wide_heads

    make_wide_stack = make_stack(wl, wb, wrep, wm, hkv=wkv, d=wd)

    def make_chunk(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (normal(kq, (wc, wkv * wrep, wd)),
                normal(kk, (wl, wb, wkv, wcm, wd)),
                normal(kv, (wl, wb, wkv, wcm, wd)))

    # the chunk ends a block and a half into the row: live, diagonal and
    # dead key blocks all met
    chunk_at = dict(row=wb - 2, start=wcm // 2 - wc // 2)

    def chunk_ref(q, k, v):
        from deepspeed_tpu.inference.kv_cache import DenseLayer
        from deepspeed_tpu.ops.attention import chunk_prefill_reference
        return chunk_prefill_reference(
            q, DenseLayer(k, jnp.int32(wl - 1)),
            DenseLayer(v, jnp.int32(wl - 1)), **chunk_at)

    wide_cases = [
        KernelCase("gdn_state_update",
                   lambda state, *rest: kda_state_update(state, kl - 1, *rest),
                   gdn_ref, make_gdn),
        KernelCase(f"flash_fwd_d{wd}",
                   lambda q, k, v: flash_attention(q, k, v, causal=True),
                   lambda q, k, v: reference_attention(q, k, v, causal=True),
                   qkv(1, ws, wkv * wrep, wkv, wd)),
        KernelCase(f"flash_fwd_chunk_d{wd}",
                   lambda q, k, v: flash.flash_prefill_chunk(
                       q, k, v, wl - 1, chunk_at["row"], chunk_at["start"]),
                   chunk_ref, make_chunk),
        KernelCase(f"decode_stack_d{wd}",
                   lambda q, k, v, index, new, layer: decode_attention(
                       q, k, v, index + 1, layer=layer, k_new=new[0, layer],
                       v_new=new[1, layer]),
                   functools.partial(stack_ref, staged=True),
                   make_wide_stack),
        KernelCase(f"kv_write_d{wd}",
                   lambda q, k, v, index, new, layer: kv_write_dense(
                       k, v, new[0], new[1], index),
                   stack_write_ref, make_wide_stack),
    ]
    # ---- a serving prefill's chunked delta rule, both decay forms, the keys
    # as the convolution leaves them, against the plain chunked form between
    # the same norms: float32 products at `highest` on both sides ----
    def delta_case(name, shape, channel):
        pb, ps, pk, pv, pd, pc = shape

        def make_delta(key):
            ks = jax.random.split(key, 7)
            f32 = jnp.float32
            gate = normal(ks[3], (pb, ps, pv) + ((pd,) if channel else ()),
                          f32)
            return (normal(ks[0], (pb, ps, pk, pd), f32),
                    normal(ks[1], (pb, ps, pk, pd), f32),
                    normal(ks[2], (pb, ps, pv, pd), f32),
                    # the families' gates: a softplus a head, a bounded one
                    # a channel
                    -5.0 * jax.nn.sigmoid(gate) if channel
                    else -4.0 * jax.nn.softplus(gate),
                    jax.nn.sigmoid(normal(ks[4], (pb, ps, pv), f32)),
                    0.1 * normal(ks[5], (pb, pv, pd, pd), f32),
                    1.0 + 0.1 * normal(ks[6], (pd,), f32))

        return KernelCase(
            name, lambda *operands: delta_rule_prefill(
                *operands[:-1], pc, operands[-1], l2_eps=hybrid.L2_EPS,
                norm_eps=1e-6),
            lambda *operands: hybrid.delta_prefill_reference(
                *operands[:-1], pc, operands[-1], 1e-6),
            make_delta, tol=DELTA_TOL)

    wide_cases += [
        delta_case("delta_prefill_head", sz.delta_prefill, False),
        delta_case("delta_prefill_channel", sz.delta_prefill_channel, True)]
    # after every other: a case's inputs are drawn from seed + its index
    return cases + slow_cases + order_cases + wide_cases


def scopes_ms(case: KernelCase, inputs, reps: int = 10) -> Optional[float]:
    """ms a call of `case.fn` the device spends under `case.scopes_ms`'s
    scopes: `reps` calls traced and joined to the program map. None where
    the profile has no device line (off the chip)."""
    import tempfile
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.program_map import join_logdir, seconds_where

    def named(*a):
        return case.fn(*a)
    named.__name__ = f"ds_smoke_{case.name}"
    jitted = jax.jit(named)
    telemetry.forget_programs()
    telemetry.keep_program(f"smoke:{case.name}", jitted.trace(*inputs))
    jax.block_until_ready(jitted(*inputs))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_case_") as logdir:
        with telemetry.trace_capture(logdir):
            for _ in range(reps):
                out = jitted(*inputs)
            jax.block_until_ready(out)
        joined = join_logdir(logdir)[1]
    telemetry.forget_programs()
    if not joined["busy_s"]:
        return None
    return 1e3 * seconds_where(joined, any_scope=case.scopes_ms[0]) / reps


def phase_kernels(sz: Sizes, seed: int) -> Dict[str, Any]:
    errs, timed, bad = {}, {}, []
    for i, case in enumerate(kernel_cases(sz)):
        inputs = jax.jit(case.make)(jax.random.PRNGKey(seed + i))
        got = jax.jit(case.fn)(*inputs)
        ref = jax.jit(case.ref)(*inputs)
        err = rel_err(got, ref)
        errs[case.name] = round(err, 5)
        if not err <= case.tol:  # NaN fails
            bad.append(f"{case.name}: rel err {err:.3g} > {case.tol}")
        if case.scopes_ms:
            ms = scopes_ms(case, inputs)
            timed[case.name] = None if ms is None else round(ms, 4)
            if ms is None and jax.devices()[0].platform == "tpu":
                bad.append(f"{case.name}: the trace has no device line")
            elif ms is not None and not ms <= case.scopes_ms[1]:
                bad.append(f"{case.name}: {ms:.3f} ms under "
                           f"{'+'.join(case.scopes_ms[0])} > "
                           f"{case.scopes_ms[1]} ms")
    if bad:
        raise AssertionError("; ".join(bad))
    return {"cases": len(errs), "rel_err": errs, "scopes_ms": timed}


# ---------------------------------------------------------------- dispatch


@contextlib.contextmanager
def dispatch_calls():
    """Count, at trace time, which attention implementation the dispatchers
    in `ops/attention.py` handed each call to, and every `kernel_fallback`
    announcement — the answer to "did the engine select the kernel or
    quietly take the XLA path". The callers look these names up at call
    time, so wrapping the module attributes observes every selection
    without touching the package. Beside them, from the telemetry hub's
    own counters, the order each traced flash FORWARD took its operands in
    (`flash_fwd/token_major`, `flash_fwd/head_major`)."""
    import deepspeed_tpu.ops.attention as disp
    import deepspeed_tpu.ops.pallas.decode_attention as dense
    import deepspeed_tpu.ops.pallas.flash_attention as flash
    import deepspeed_tpu.ops.pallas.paged_attention as paged
    import deepspeed_tpu.ops.pallas.sharded as sharded
    sites = [(flash, "flash_attention"), (dense, "decode_attention"),
             (paged, "paged_decode_attention"),
             (paged, "paged_prefill_attention"),
             (sharded, "sharded_decode_attention"),
             (sharded, "sharded_paged_decode_attention"),
             (sharded, "sharded_paged_prefill_attention"),
             (sharded, "kernel_fallback"),
             (disp, "reference_attention"), (disp, "blockwise_attention")]
    counts: Dict[str, int] = {"kernel_fallback": 0}  # always reported
    saved = [(mod, name, getattr(mod, name)) for mod, name in sites]

    def counting(name, fn):
        def wrapper(*a, **kw):
            counts[name] = counts.get(name, 0) + 1
            return fn(*a, **kw)
        return wrapper

    from deepspeed_tpu.telemetry import get_hub
    before = dict(get_hub().counters)
    for mod, name, fn in saved:
        setattr(mod, name, counting(name, fn))
    try:
        yield counts
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)
        for name, n in get_hub().counters.items():
            if name.startswith("flash_fwd/") and n > before.get(name, 0):
                counts[name] = int(n - before.get(name, 0))


# ------------------------------------------------------------------- train


def train_losses(sz: Sizes, seed: int, devices, dp: int = 1, tp: int = 1,
                 steps: int = 4, trace_to: Optional[str] = None
                 ) -> Dict[str, Any]:
    """`steps` fused train steps on a repeated batch over `devices`
    (dp x tp mesh): ZeRO-3, bf16, FusedAdam, flash attention, remat and
    chunked cross-entropy — the recipe of the benchmark's train cells at
    this model's widths. The global batch is the same whatever the mesh.
    `trace_to`: a directory that takes a profile of the steps after the
    first (`telemetry.trace_capture`, which leaves the program map there)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.qwen2 import (init_params_and_specs,
                                            llama_loss_fn, materialize_params,
                                            qwen2_config)
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.groups import MeshTopology

    cfg = qwen2_config(sz.preset, num_hidden_layers=sz.train_layers,
                       max_position_embeddings=sz.seq, remat=True,
                       remat_policy="checkpoint_dots",
                       loss_chunk_size=sz.loss_chunk, dtype=jnp.bfloat16)
    groups.reset_topology()
    topology = MeshTopology(dp=dp, tp=tp, devices=list(devices))
    gas = sz.global_batch // (sz.micro_batch * dp)
    # bf16 from the start: the engine casts to its bf16 model dtype before it
    # builds the fp32 master anyway, and the fp32 tree would only crowd HBM
    model, params = materialize_params(cfg, rng=jax.random.PRNGKey(seed),
                                       param_dtype=jnp.bfloat16)
    _, specs = init_params_and_specs(cfg)
    with dispatch_calls() as calls:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, topology=topology,
            config={"train_micro_batch_size_per_gpu": sz.micro_batch,
                    "gradient_accumulation_steps": gas,
                    "steps_per_print": 0,
                    "optimizer": {"type": "FusedAdam",
                                  "params": {"lr": 2e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 3},
                    "tensor_parallel": {"tp_size": tp}},
            loss_fn=llama_loss_fn(model), base_param_specs=specs)
        del params  # the engine's state is the only copy from here on
        batch = {"input_ids": np.random.default_rng(seed).integers(
            0, cfg.vocab_size, size=(sz.global_batch, sz.seq)).astype(np.int32)}
        losses, walls = [], []
        with contextlib.ExitStack() as traced:
            for i in range(steps):
                if i == 1 and trace_to:
                    from deepspeed_tpu.telemetry import trace_capture
                    traced.enter_context(trace_capture(trace_to))
                t0 = time.perf_counter()
                losses.append(float(engine.train_batch(batch=batch)))
                walls.append(time.perf_counter() - t0)
    n_params = int(engine.total_params)
    held = memory_stat("bytes_in_use", devices)
    engine.state = None
    engine._jit_cache.clear()
    del engine
    groups.reset_topology()
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    run_s = float(np.median(walls[1:]))
    return {"layers": sz.train_layers, "params_m": round(n_params / 1e6, 1),
            "mesh": {"dp": dp, "tp": tp}, "seq": sz.seq,
            "global_batch": sz.global_batch, "micro_batch": sz.micro_batch,
            "gas": gas, "losses": [round(l, 5) for l in losses],
            "loss_first": round(losses[0], 5),
            "loss_first_hex": float(losses[0]).hex(),
            "loss_last": round(losses[-1], 5),
            "compile_s": round(walls[0] - run_s, 2), "run_s": round(run_s, 3),
            "dispatch": dict(calls), "device_bytes_in_use": held}


def phase_train(sz: Sizes, seed: int) -> Dict[str, Any]:
    out = train_losses(sz, seed, jax.devices()[:1])
    if not out["loss_last"] < out["loss_first"]:
        raise AssertionError(
            f"loss did not fall on a repeated batch: {out['losses']}")
    return out


# ----------------------------------------------------------------- serving


def serving_model(sz: Sizes, seed: int):
    """The full-depth model and its bf16 weights from `seed`. Each engine
    gets its own tree (an engine must own the only reference for its
    leaf-wise relayout to free the old copy), identical by construction."""
    from deepspeed_tpu.models.qwen2 import materialize_params, qwen2_config
    cfg = qwen2_config(sz.preset, max_position_embeddings=sz.v2_max_seq,
                       remat=False, dtype=jnp.bfloat16)
    return materialize_params(cfg, rng=jax.random.PRNGKey(seed),
                              param_dtype=jnp.bfloat16)


def make_prompts(sz: Sizes, seed: int, vocab: int) -> List[np.ndarray]:
    """`prompts_per_len` prompts of each length, shorter length first."""
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(1, vocab, size=(n,)).astype(np.int32)
            for n in sz.prompt_lens for _ in range(sz.prompts_per_len)]


# Two correct bf16 programs for one model order their sums differently, and
# 36 layers of bf16 rounding reach the logits: measured on the chip, cached
# decode and the uncached forward disagree by up to ~8 bf16 steps (0.125 at
# a top logit of 3.8). With seeded weights the top few of 151936 logits sit
# that close, so "same argmax" is not the oracle; "argmax up to this
# tolerance, and mostly the argmax itself" is. A wrong token (a cache or
# position bug) lands a whole logit spread away, far outside it.
TIE_TOL = 2.0 ** -4       # relative to the larger of the two logits
MIN_ARGMAX_SHARE = 0.75   # of generated tokens that are the exact argmax


def tie_gap(row: np.ndarray, got: int):
    """None if `got` is the argmax of this row of reference logits, else
    how far below it, relative to the larger of the two."""
    want = int(np.argmax(row))
    if got == want:
        return None
    return abs(float(row[got]) - float(row[want])) / max(
        abs(float(row[got])), abs(float(row[want])), 1e-6)


def judge(label, gaps, where, total,
          min_share=MIN_ARGMAX_SHARE) -> Dict[str, Any]:
    """Counts for a phase line; raises where a token is no tie, or where too
    few are the argmax itself."""
    out = {"tokens_argmax": total - len(gaps), "tokens_tied": len(gaps),
           "tie_gaps": sorted(round(g, 4) for g in gaps),
           "tied_at": where,  # [request, generated-token offset]
           "tie_tolerance": TIE_TOL}
    if gaps and max(gaps) > TIE_TOL:
        i, t = where[int(np.argmax(gaps))]
        raise AssertionError(f"{label}: request {i}, generated token {t}, "
                             f"is {max(gaps):.3f} below the reference "
                             f"argmax; {out}")
    if total - len(gaps) < min_share * total:
        raise AssertionError(f"{label}: only {total - len(gaps)} of {total} "
                             f"tokens are the reference argmax; {out}")
    return out


def check_against_forward(ref_logits, prompts, sequences, label):
    """Teacher-forced check of greedy decoding: `ref_logits[i][t - 1]` are
    the uncached forward's logits for position t of sequence i GIVEN that
    sequence's own prefix, so every generated token must be their argmax or
    tie with it within `TIE_TOL`."""
    gaps, where, total = [], [], 0
    for i, (p, seq) in enumerate(zip(prompts, sequences)):
        for t in range(len(p), len(seq)):
            total += 1
            gap = tie_gap(ref_logits[i][t - 1], int(seq[t]))
            if gap is not None:
                gaps.append(gap)
                where.append([i, t - len(p)])
    return judge(label, gaps, where, total)


def check_first_tokens(anchor, prompts, sequences, label):
    """The one check an engine cannot pass by agreeing with itself: each
    request's FIRST generated token against `anchor`, the logits the RAW
    weight tree gives for its prompt (`prompt_logits`, taken before any
    engine placed, cast or re-laid the tree)."""
    gaps, where = [], []
    for i, (p, seq) in enumerate(zip(prompts, sequences)):
        gap = tie_gap(anchor[i], int(seq[len(p)]))
        if gap is not None:
            gaps.append(gap)
            where.append([i, 0])
    # eight tokens are too few for a share: three ties in eight is chance
    return judge(label + " first tokens vs raw weights", gaps, where,
                 len(prompts), min_share=0.0)


def prompt_logits(model, params, prompts) -> np.ndarray:
    """(requests, vocab) float32: the uncached forward of the raw `params`
    over each prompt, at its last position."""
    width = max(len(p) for p in prompts)
    padded = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    last = np.asarray([len(p) - 1 for p in prompts], np.int32)

    @jax.jit
    def rows(params, ids, last):
        logits = model.apply({"params": params}, ids)
        return jnp.take_along_axis(logits, last[:, None, None],
                                   axis=1)[:, 0].astype(jnp.float32)
    return np.asarray(rows(params, padded, last))


def forward_logits(apply, sequences) -> np.ndarray:
    """Uncached forward over right-padded `sequences` (causal attention
    never looks at the padding), as float32 on the host."""
    width = max(len(s) for s in sequences)
    padded = np.zeros((len(sequences), width), np.int32)
    for i, s in enumerate(sequences):
        padded[i, :len(s)] = s
    logits = np.asarray(apply(padded), np.float32)
    if not np.isfinite(logits).all():
        raise AssertionError("non-finite logits from the uncached forward")
    return logits


def phase_v1(sz: Sizes, seed: int, keep: Dict[str, Any], tp: int = 1
             ) -> Dict[str, Any]:
    """`init_inference(...).generate` over one batch per prompt length, then
    the engine's own uncached `forward` over what it generated: cached
    decode must agree with the full forward token by token. Leaves the
    prompts and sequences in `keep` for the v2 phase."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    groups.reset_topology()
    model, params = serving_model(sz, seed)
    prompts = make_prompts(sz, seed, model.cfg.vocab_size)
    anchor = prompt_logits(model, params, prompts)
    with dispatch_calls() as calls:
        engine = deepspeed_tpu.init_inference(
            model, params=params, dtype="bf16",
            tensor_parallel={"tp_size": tp})
        del params
        sequences, compile_s, run_s = [], 0.0, 0.0
        for n in sz.prompt_lens:
            ids = np.stack([p for p in prompts if len(p) == n])
            t0 = time.perf_counter()
            engine.generate(ids, max_new_tokens=sz.new_tokens)
            t1 = time.perf_counter()
            out = engine.generate(ids, max_new_tokens=sz.new_tokens)
            t2 = time.perf_counter()
            compile_s += (t1 - t0) - (t2 - t1)
            run_s += t2 - t1
            if out.shape != (len(ids), n + sz.new_tokens) or \
                    out.min() < 0 or out.max() >= model.cfg.vocab_size:
                raise AssertionError(f"generate output {out.shape} out of "
                                     "shape or vocabulary")
            sequences += [row for row in out]
        ref_logits = forward_logits(engine.forward, sequences)
    label = f"v1 tp={tp}"
    first = check_first_tokens(anchor, prompts, sequences, label)
    checked = check_against_forward(ref_logits, prompts, sequences, label)
    keep.update(prompts=prompts, sequences=sequences, anchor=anchor)
    out = {"layers": model.cfg.num_hidden_layers, "tp": tp,
           "device_bytes_in_use": memory_stat("bytes_in_use",
                                              jax.devices()[:tp]),
           "serve_mode": engine.serve_mode, "requests": len(prompts),
           "prompt_lens": list(sz.prompt_lens), "new_tokens": sz.new_tokens,
           "tokens_generated": len(prompts) * sz.new_tokens,
           "first_vs_raw": first, "vs_forward": checked,
           "compile_s": round(compile_s, 2), "run_s": round(run_s, 3),
           "dispatch": dict(calls)}
    engine.params = None
    engine._generate_jit.clear()
    del engine
    groups.reset_topology()
    return out


def agreement(keep, sequences) -> Dict[str, int]:
    """How far `sequences` follow the v1 phase's, request by request. Both
    sides passed the teacher-forced check against the same weights, so a
    place where they part is a tie in the reference logits, broken
    differently by two programs; after it each follows its own prefix."""
    part_at = []  # generated tokens in common before the first difference
    for p, want, got in zip(keep["prompts"], keep["sequences"], sequences):
        got = np.asarray(got)
        if got.shape != want.shape or (got[:len(p)] != p).any():
            raise AssertionError("a served sequence lost its prompt or length")
        diff = np.nonzero(got != want)[0]
        part_at.append((int(diff[0]) if len(diff) else len(got)) - len(p))
    return {"identical_sequences": sum(n == len(s) - len(p) for n, s, p in zip(
                part_at, sequences, keep["prompts"])),
            "common_prefix_tokens": part_at}


def phase_v2(sz: Sizes, seed: int, keep: Dict[str, Any]) -> Dict[str, Any]:
    """`InferenceEngineV2(kv_layout="paged").generate` over the v1 phase's
    prompts, long and short interleaved, on fewer slots than requests."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    if "sequences" not in keep:
        raise AssertionError("no v1 tokens to compare with (v1 phase failed)")
    groups.reset_topology()
    model, params = serving_model(sz, seed)
    prompts = keep["prompts"]
    half = len(prompts) // 2
    order = [i for pair in zip(range(half, len(prompts)), range(half))
             for i in pair]  # long, short, long, short, ...
    with dispatch_calls() as calls:
        engine = InferenceEngineV2(
            model, params=params, max_batch=sz.v2_slots,
            max_seq_len=sz.v2_max_seq, kv_layout="paged",
            cache_block_size=sz.block, split_fuse_chunk=sz.v2_chunk)
        del params
        requests = [prompts[i].tolist() for i in order]
        t0 = time.perf_counter()
        engine.generate(requests, max_new_tokens=sz.new_tokens)
        t1 = time.perf_counter()
        served = engine.generate(requests, max_new_tokens=sz.new_tokens)
        t2 = time.perf_counter()
        sequences = [None] * len(prompts)
        for i, seq in zip(order, served):
            sequences[i] = np.asarray(seq, np.int32)
        forward = jax.jit(lambda p, ids: model.apply({"params": p}, ids))
        ref_logits = forward_logits(lambda ids: forward(engine.params, ids),
                                    sequences)
    first = check_first_tokens(keep["anchor"], prompts, sequences, "v2")
    checked = check_against_forward(ref_logits, prompts, sequences, "v2")
    snap = engine.telemetry_snapshot()
    out = {"layers": model.cfg.num_hidden_layers,
           "serve_mode": engine.serve_mode, "kv_layout": engine.kv_layout,
           "slots": sz.v2_slots, "requests": len(prompts),
           "tokens_generated": len(prompts) * sz.new_tokens,
           "first_vs_raw": first, "vs_forward": checked,
           "vs_v1": agreement(keep, sequences),
           "flushed_sequences": snap["flushed_sequences"],
           "kv_util_peak": snap["kv_util_peak"],
           "pinned_recompiles": snap["pinned_recompiles"],
           "compile_s": round((t1 - t0) - (t2 - t1), 2),
           "run_s": round(t2 - t1, 3), "dispatch": dict(calls)}
    engine.params = engine.cache = None
    engine._jits.clear()
    del engine
    groups.reset_topology()
    return out


# ------------------------------------------------------------- four chips


def phase_train_sharded(sz: Sizes, seed: int) -> Dict[str, Any]:
    """dp2 x tp2 against one chip: same global batch, same seed, same steps.
    ZeRO-3 shards state over `data`, the projections over `model`; the
    losses may differ by reduction order in bf16 and no more."""
    tol = 5e-3  # relative, per step: bf16 sums reassociated across 4 chips
    #             (measured 2.8e-4 on the 2x2 v5e)
    one = train_losses(sz, seed, jax.devices()[:1])
    four = train_losses(sz, seed, jax.devices()[:4], dp=2, tp=2)
    check_every_device_holds(four)
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(one["losses"], four["losses"]))
    out = {"one_chip": one, "dp2_tp2": four, "loss_rel_diff": round(worst, 5),
           "tolerance": tol}
    if not worst <= tol:
        raise AssertionError(f"sharded and one-chip losses differ by "
                             f"{worst:.3g} > {tol}: {out}")
    if not four["loss_last"] < four["loss_first"]:
        raise AssertionError(f"sharded loss did not fall: {four['losses']}")
    return out


def phase_v1_tp(sz: Sizes, seed: int) -> Dict[str, Any]:
    """v1 `generate` at tp_size=2 against tp=1, token for token (each side
    checked against its own uncached forward; see `agreement`)."""
    keep: Dict[str, Any] = {}
    tp1 = phase_v1(sz, seed, keep, tp=1)
    sharded: Dict[str, Any] = {}
    tp2 = phase_v1(sz, seed, sharded, tp=2)
    check_every_device_holds(tp2)
    return {"tp1": tp1, "tp2": tp2,
            "tp2_vs_tp1": agreement(keep, sharded["sequences"])}


# -------------------------------------------------------------------- main


def named_share(logdir: str) -> Dict[str, Any]:
    """Of the busy time on the device line of the trace in `logdir`, the
    share the program map beside it names (`telemetry.by_scope`)."""
    from deepspeed_tpu.telemetry.program_map import join_logdir
    maps, joined = join_logdir(logdir)
    lost = sum(joined["unmatched"].values())
    return {"programs": sorted(d["program"] for d in maps.values()),
            "map_cache": sorted({d["cache"] for d in maps.values()}),
            "map_seconds": round(sum(d["compile_s"] + d["parse_s"]
                                     for d in maps.values()), 3),
            "rows_that_ran": len(joined["rows"]),
            "busy_s": round(joined["busy_s"], 6),
            "named_share": round(1.0 - lost / joined["busy_s"], 6)
            if joined["busy_s"] else None,
            "unmatched": sorted(joined["unmatched"].items(),
                                key=lambda kv: -kv[1])[:5]}


def phase_scopes(sz: Sizes, seed: int) -> Dict[str, Any]:
    """The one thing a CPU cannot check of the program map: that the
    profiler's instruction names ARE the compiled text's. A train step and
    a v1 generate are traced, and the map must name at least 99% of the
    device line's busy time. And that the scope names are metadata only: a
    train step with every `with jax.named_scope` made a no-op gives, bit
    for bit, the same first loss. Off the chip a profile has no device line:
    the share is not judged there."""
    import tempfile
    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.utils import groups
    devices = jax.devices()[:1]
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = train_losses(sz, seed, devices, steps=1)["loss_first_hex"]
    finally:
        jax.named_scope = real
    out: Dict[str, Any] = {}
    telemetry.forget_programs()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_scopes_") as logdir:
        named = train_losses(sz, seed, devices, steps=3,
                             trace_to=logdir)["loss_first_hex"]
        out["train"] = named_share(logdir)
    if named != bare:
        raise AssertionError(f"first loss {named} with the scopes, {bare} "
                             "without them")
    telemetry.forget_programs()
    groups.reset_topology()
    model, params = serving_model(sz, seed)
    engine = deepspeed_tpu.init_inference(model, params=params, dtype="bf16")
    del params
    n = sz.prompt_lens[0]
    ids = np.stack([p for p in make_prompts(sz, seed, model.cfg.vocab_size)
                    if len(p) == n])
    engine.generate(ids, max_new_tokens=sz.new_tokens)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_scopes_") as logdir:
        with telemetry.trace_capture(logdir):
            engine.generate(ids, max_new_tokens=sz.new_tokens)
        out["v1"] = named_share(logdir)
    engine.params = None
    engine._generate_jit.clear()
    del engine
    groups.reset_topology()
    telemetry.forget_programs()
    for name in ("train", "v1"):
        share = out[name]["named_share"]
        if share is None:
            if jax.devices()[0].platform == "tpu":
                raise AssertionError(f"{name}: the trace has no device line")
        elif share < 0.99:
            raise AssertionError(
                f"{name}: the program map names {share:.4f} of the device "
                f"line's busy time; unmatched {out[name]['unmatched']}")
    return {"loss_first_hex": named, "loss_first_hex_without_scopes": bare,
            **out}


def run_phase(name: str, fn: Callable[[], Dict[str, Any]]) -> bool:
    """Run one phase and print its line. A failure is recorded with its
    traceback (stderr) and the remaining phases still run: the script's
    exit code reports it."""
    t0 = time.perf_counter()
    try:
        body, ok = fn(), True
    except Exception as e:  # phase boundary: report, keep going, fail at exit
        traceback.print_exc()
        body, ok = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
    emit({"phase": name, "ok": ok, "wall_s": round(time.perf_counter() - t0, 2),
          **body, "peak_bytes": memory_stat("peak_bytes_in_use")})
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded train and tp generate paths, each "
                         "against one chip, and no other phase")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, same control flow")
    ap.add_argument("--rehearsal", action="store_true",
                    help="accept a backend that is not a TPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", action="append",
                    help="run this phase alone (may be given again)")
    args = ap.parse_args(argv)

    from benchmarks.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    from deepspeed_tpu.accelerator import on_tpu
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not on_tpu() and not args.rehearsal:
        print(f"chip_smoke: default backend is {dev.platform!r}, not a TPU "
              "(--rehearsal runs the control flow anyway)", file=sys.stderr)
        return 2
    switches = [v for v in ("DS_TPU_PALLAS_INTERPRET", "DS_TPU_DISABLE_PALLAS")
                if os.environ.get(v)]
    if on_tpu() and switches:
        print(f"chip_smoke: {switches} set — on the chip the kernels run "
              "compiled or the run fails; unset them", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 2

    sz = TINY if args.tiny else FULL
    emit({"phase": "start", "jax": jax.__version__, "device": device,
          "sizes": "tiny" if args.tiny else "full", "preset": sz.preset,
          "seed": args.seed, "chips": args.chips, "compile_cache": cache_dir})
    if args.chips == 4:
        phases = [("train_sharded", lambda: phase_train_sharded(sz, args.seed)),
                  ("v1_tp", lambda: phase_v1_tp(sz, args.seed))]
    else:
        keep: Dict[str, Any] = {}
        phases = [("kernels", lambda: phase_kernels(sz, args.seed)),
                  ("train", lambda: phase_train(sz, args.seed)),
                  ("v1", lambda: phase_v1(sz, args.seed, keep)),
                  ("v2", lambda: phase_v2(sz, args.seed, keep)),
                  ("scopes", lambda: phase_scopes(sz, args.seed))]
    if args.phase:
        phases = [(n, fn) for n, fn in phases if n in args.phase]
    results = [run_phase(name, fn) for name, fn in phases]  # run them all
    ok = all(results)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
