"""InferenceEngineV2 — continuous batching (reference
`inference/v2/engine_v2.py:30`: `put:107`, `query:158`, `flush`).

TPU scheduling model: a fixed pool of cache slots; prompt prefill runs as a
single-row program (bucketed by padded prompt length), token generation as
one batched decode step over every live slot. Static shapes throughout —
joining/leaving sequences never recompile; the per-row cache cursors
(`kv_cache.KVCache.index`) carry the raggedness the reference handles with
its ragged kernel set.
"""

from __future__ import annotations

import functools
import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import _cache_dims
from deepspeed_tpu.inference.kv_block_manager import KVBlockManager
from deepspeed_tpu.inference.kv_cache import KVCache, PagedKVCache
from deepspeed_tpu.inference.v2.ragged import DSStateManager
from deepspeed_tpu.resilience.faults import fault_point, is_oom_error
from deepspeed_tpu.telemetry import (RecompileDetector, RequestTracer,
                                     compile_span, compile_totals,
                                     device_busy, get_hub, init_phase,
                                     init_span, jit_name, keep_program)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import logger, warn_once

_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)
_OFF = nullcontext()   # in place of a span while the tracer is off


def _uid_fold(uid) -> int:
    """Stable 31-bit mix of a caller-chosen uid for PRNG key folding —
    external uids may be 64-bit (hash/snowflake ids); int32 assignment
    would overflow, and plain masking is fine for a fold value."""
    return int(uid) & 0x7FFFFFFF


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return -(-n // 4096) * 4096


def chunk_row_widths(max_batch: int) -> Tuple[int, ...]:
    """Row widths of the chunk half of `fused_batch` (paged layout),
    ascending, the last always `max_batch`. How many prompts are mid-prefill
    at once is set by the arrival rate and the rounds a prompt takes, not by
    `max_batch`, so the narrow rung is a fixed small width. Every row of a
    round rides its matmuls, parked or not (a parked row costs the paged
    attention kernels nothing since PR 31, and the layers' matmuls what a
    real one does), which is why `put` FILLS the width: the rows no prompt
    needs for its one chunk carry further chunks of the prompts that are
    prefilling (PR 36), so the width is also the most chunks a round
    takes in, and what a decode row can be held up by. A row costs little
    once the round is narrow (on a
    v5e, Qwen2.5-3B, 16-token chunks, measured while a parked row still
    ran the kernels: 115 ms at 48 rows, 78 at 16, 75 at 8), while every
    further width costs about 1.7 s of set-up even out of the persistent
    cache (it is traced and lowered before the cache can be asked): hence
    one narrow rung, and 16 rather than 8."""
    return (16, max_batch) if max_batch > 16 else (max_batch,)


def width_for(rows: int, max_batch: int) -> int:
    """The narrowest compiled chunk width that holds `rows` prompts, a row
    each. The PROMPTS choose it, not their pending tokens: a round goes
    `max_batch` wide only when more prompts prefill than the narrow rung
    has rows."""
    return next(w for w in chunk_row_widths(max_batch) if w >= rows)


class InferenceEngineV2:
    @init_span("v2")
    def __init__(self, model: Any, config: Optional[DeepSpeedInferenceConfig] = None,
                 params: Any = None, max_batch: int = 8,
                 max_seq_len: int = 2048, split_fuse_chunk: int = 256,
                 kv_layout: Optional[str] = None, cache_block_size: int = 256,
                 num_cache_blocks: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = None,
                 prefix_sharing: bool = True,
                 serve_mode: Optional[str] = None,
                 quant: Optional[dict] = None,
                 speculative: Optional[dict] = None):
        """`kv_layout='paged'` (the reference's FastGen layout,
        `inference/v2/ragged/blocked_allocator.py`): cache HBM is a pool of
        `num_cache_blocks × cache_block_size`-token blocks allocated to
        sequences on demand, so memory scales with tokens in flight and
        `num_cache_blocks` can be sized to the HBM budget independently of
        max_batch×max_seq_len (default: full capacity, i.e. slot parity).
        `kv_layout='slot'` keeps the dense row-per-sequence cache.
        Default (None): paged for every family — the paged kernels
        evaluate sliding-window bands and alibi biases in-tile (r4), so
        bloom/mistral page like everyone else.

        `kv_cache_dtype='int8'` (paged only) stores K/V int8-at-rest with
        per-(kv-head, slot) scales quantized in the batched `apply_stage`
        scatter and folded in-register by the decode/prefill kernels — the
        dense bf16 cache form never exists in HBM (docs/kv_cache.md).
        `prefix_sharing` (paged only, default on) admits prompts through a
        prefix-hash match against committed blocks: N requests sharing a
        system prompt hold ONE physical copy, refcounted with
        copy-on-write on fork (`kv_block_manager.KVBlockManager`).

        `serve_mode`/`quant` write through to the config (the same
        kwargs `init_inference` takes): v2 runs the SAME serve-mode
        resolver and placement as v1 (inference/serve_modes.py) —
        whole-tree `dequant`, int8 `layer_scan`, host-streamed
        `capacity` — with the streamed modes driving every bucketed
        program through the shared `make_block_fn` scan body
        (docs/fastgen_v2.md has the serve-mode × layout matrix)."""
        init_phase("plan")
        if config is None:
            config = DeepSpeedInferenceConfig()
        self._config = config
        if serve_mode is not None:
            config.serve_mode = serve_mode
        if quant is not None:
            config.quant = quant
        if speculative is not None:
            config.speculative = speculative
        if not getattr(config, "max_batch_size", None):
            # the auto resolver accounts KV + workspace at the serving
            # batch — feed it the real one, not the config default
            config.max_batch_size = max_batch
        if isinstance(model, tuple):
            model, params = model
        self.module = model
        self.model_cfg = model.cfg
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self._kv_layout_explicit = kv_layout is not None
        if kv_layout is None:
            # r4: paged is the default — the paged kernels evaluate
            # sliding-window bands and alibi biases in-tile. ONE exception
            # remains: alibi models at shapes outside the kernel's
            # validated regime (head_dim or block_size < 128 — Mosaic
            # rejects some tiny-tile alibi layouts, see ops/attention.py)
            # would silently gather the dense view every step, which is
            # strictly worse than a resident dense cache → keep 'slot'.
            small_alibi = getattr(model.cfg, "uses_alibi", False) and (
                getattr(model.cfg, "head_dim",
                        model.cfg.hidden_size
                        // model.cfg.num_attention_heads) < 128
                or cache_block_size < 128)
            kv_layout = "slot" if small_alibi else "paged"
        if kv_layout not in ("paged", "slot"):
            raise ValueError(f"kv_layout must be 'paged' or 'slot', got {kv_layout!r}")
        self._requested_kv_layout = kv_layout
        # Dynamic split-fuse (reference blogs/deepspeed-fastgen, ragged
        # scheduling): prompts longer than this prefill in fixed-size chunks,
        # and each chunk rides the SAME compiled step as the live decode rows
        # — long prompts never stall ongoing generation for more than one
        # chunk's worth of work.
        self.split_fuse_chunk = split_fuse_chunk

        try:
            self.topology = groups.get_topology(create_default=False)
        except RuntimeError:
            tp = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
            self.topology = groups.initialize(
                tp=tp, dp=1, devices=jax.devices()[:tp])
        self.mesh = self.topology.mesh

        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None or 'int8', got {kv_cache_dtype!r}")
        self.kv_cache_dtype = kv_cache_dtype

        # v2-OWNED serve-mode placement (inference/serve_modes.py — the
        # shared resolver/ladder v1 runs; until r11 this borrowed v1's
        # `_shard_params` UNBOUND with the resolver getattr-guarded out,
        # pinning v2 to dequant placement semantics). `_forced_mode` pins
        # an OOM-degraded rung across re-placement; `_capacity` holds the
        # capacity runner for the streamed mode.
        self._forced_mode: Optional[str] = None
        self._capacity = None
        self._quantized = False
        self._layouts_pinned = False
        self._chunk_family_warm = False
        self._weight_bytes_cache = None
        self._jits: Dict[Any, Any] = {}
        # Serving telemetry: every serving program is PINNED — its input
        # signature is supposed to stay constant once compiled, so any
        # signature miss is a silent ~3.5 s recompile and warns loudly.
        self.recompiles = RecompileDetector("serving_v2", pinned_default=True)
        # Request-level span records (telemetry/spans.py): the serving
        # loops open host-timed spans around their EXISTING materialization
        # points — free when the hub is disabled, zero new device fetches
        # when enabled. Survives `_degrade_to` (the engine rebuild drops
        # programs and caches, never in-flight request traces).
        self.tracer = RequestTracer(engine="v2")
        part = init_phase("place_params")
        self.params = self._place_with_recovery(params)
        part["async"] = device_busy(self.params)
        if self.kv_cache_dtype == "int8" and self.serve_mode != "dequant":
            raise ValueError(
                "kv_cache_dtype='int8' rides the paged dequant path; the "
                f"layer-streamed serve mode {self.serve_mode!r} keeps dense "
                "slot rows with no per-row view of a quantized cache — use "
                "serve_mode='dequant' or drop the int8 cache")

        part = init_phase("alloc_cache")
        self.kv_layout = self._resolve_kv_layout(kv_layout)
        if kv_cache_dtype == "int8" and self.kv_layout != "paged":
            raise ValueError(
                "kv_cache_dtype='int8' needs the paged layout (the dense "
                "slot rows have no per-row view of a quantized cache); "
                "drop kv_layout='slot' or the int8 cache")
        self._cache_block_size = cache_block_size
        self._num_cache_blocks = num_cache_blocks
        self._prefix_sharing = prefix_sharing
        self._setup_cache()
        part["async"] = device_busy(self.cache)
        init_phase("build_programs")
        self._apply = self._make_apply()
        self._sample_cfg = None   # (temperature, top_k, top_p) or None
        self.last_timing: Dict[int, Dict[str, float]] = {}  # per-uid SLA
        self.serving_counters: Dict[str, int] = {
            "flushed_sequences": 0, "generated_tokens": 0,
            "decode_waves": 0, "mixed_rounds": 0,
            "spec_rounds": 0, "spec_draft_tokens": 0,
            "spec_accepted_tokens": 0,
            # counted where the work happens, tracing on or off: `put`
            # rounds, block-table pushes to the device, and the token
            # slots (rows x positions) the dispatched programs computed
            # against the tokens they were fed (speculative rounds apart)
            "rounds": 0, "table_syncs": 0,
            "token_slots_computed": 0, "tokens_fed": 0,
            # rows of those programs whose cursor stood at capacity (they
            # hold nothing, and the paged attention kernels skip them), and
            # chunk rows that went to a prompt beyond its first of the round
            # (they would have been parked)
            "rows_parked": 0, "rows_refilled": 0,
            # (row, block) pairs of the decode rows that held tokens, which
            # is what the paged decode kernel walks, against the entries of
            # their block tables (rows x T), which is what it used to
            "kv_blocks_live": 0, "kv_blocks_table": 0}
        self._kv_util_peak = 0.0
        self._rng = jax.random.PRNGKey(0)
        self._setup_spec()
        logger.info(f"InferenceEngineV2: {self._cache_desc}, "
                    f"serve_mode={self.serve_mode}, "
                    f"{self.topology.describe()}")

    # ---------------------------------------------------- serve-mode placement
    def _place_with_recovery(self, params):
        """Place params with OOM-driven serve-mode degradation — v1's loop
        verbatim over the shared helpers (docs/resilience.md): on a real
        or injected RESOURCE_EXHAUSTED, walk dequant → layer_scan →
        capacity and re-place from the RAW tree. The retry happens AFTER
        the except block so the failed attempt's tree frees before the
        next placement allocates (the r5 residency lesson)."""
        while True:
            try:
                return self._place_params(params)
            except Exception as e:
                mode = getattr(self, "serve_mode", "dequant")
                if not self._degrade_enabled() or not is_oom_error(e):
                    raise
                nxt = self._degraded_mode(mode, params)
                if nxt is None:
                    raise
                from deepspeed_tpu.inference.serve_modes import note_degraded
                note_degraded("v2", mode, nxt, stage="placement", reason=e)
                self._capacity = None
                self._forced_mode = nxt
            # `e` and its traceback are gone here; the loop re-places

    def _place_params(self, params):
        from deepspeed_tpu.inference.serve_modes import place_params
        return place_params(self, params)

    def _degrade_enabled(self) -> bool:
        from deepspeed_tpu.inference.serve_modes import degrade_enabled
        return degrade_enabled(self._config)

    def _degraded_mode(self, mode: str, params) -> Optional[str]:
        """Next viable ladder rung (inference/serve_modes.py), with ONE v2
        constraint on top: the int8 KV cache exists only in the paged
        pools the dequant mode serves — the streamed modes force dense
        slot rows, so an int8-KV engine has no rung to fall to."""
        if self.kv_cache_dtype == "int8":
            warn_once(("v2_degrade_kv_int8",),
                      "v2: kv_cache_dtype='int8' pins the paged dequant "
                      "path — no serve-mode degradation rung exists "
                      "(the streamed modes keep dense slot rows); "
                      "the OOM re-raises")
            return None
        from deepspeed_tpu.inference.serve_modes import degraded_mode
        return degraded_mode(self, mode, params)

    def _degrade_to(self, nxt: str) -> None:
        """Re-place the CURRENT tree for a lower serve mode after a
        compile/dispatch-time OOM. Engine-held references (params handle,
        program caches, capacity runner, spec draft, the KV cache itself)
        drop FIRST so the only live copy during re-placement is the local
        source tree. The cache and scheduler state are rebuilt fresh —
        sequences admitted through direct put() calls are lost (generate()
        re-prefills its own in-flight work when it retries)."""
        src, self.params = self.params, None
        self._jits = {}
        self._weight_bytes_cache = None
        self._capacity = None
        self._apply = None
        self._spec_enabled = False
        self._spec_draft = None
        self._spec_state = {}
        self._layouts_pinned = False
        self._chunk_family_warm = False
        self._forced_mode = nxt
        self.params = self._place_params(src)
        del src
        self._apply = self._make_apply()
        self.kv_layout = self._resolve_kv_layout(self._requested_kv_layout)
        self._setup_cache()
        self._setup_spec()

    def _resolve_kv_layout(self, requested: Optional[str]) -> str:
        """The streamed serve modes run the engine-level scan body over
        DENSE cache rows (`make_scan_apply` takes (L, B, M, H, D) arrays)
        — the paged pool's table indirection lives in the model's own
        cache path, which those modes bypass. So layer_scan/capacity
        force the 'slot' layout: an EXPLICIT paged request errors up
        front; a paged default (or a degraded engine, where changing
        layout beats dying) warns once and falls back. Prefix sharing
        and COW are paged-only and go inactive with the fallback."""
        if requested is None:
            requested = self._requested_kv_layout
        if self.serve_mode == "dequant":
            return requested
        if requested == "paged":
            if self._kv_layout_explicit and self._forced_mode is None:
                raise ValueError(
                    f"kv_layout='paged' is incompatible with serve_mode="
                    f"{self.serve_mode!r}: the layer-streamed scan body "
                    "runs over dense slot rows (the paged table "
                    "indirection lives in the model cache path those "
                    "modes bypass) — drop kv_layout or serve dequant")
            warn_once(("v2_kv_layout", self.serve_mode),
                      f"v2: serve_mode={self.serve_mode!r} forces the "
                      "dense 'slot' KV layout (prefix sharing/COW are "
                      "paged-only and go inactive)")
        return "slot"

    def _setup_cache(self) -> None:
        """Build the KV cache + scheduler state for the CURRENT kv_layout
        (factored out of __init__ so `_degrade_to` can rebuild both when a
        degraded serve mode changes the layout)."""
        max_batch, max_seq_len = self.max_batch, self.max_seq_len
        cache_block_size = self._cache_block_size
        num_cache_blocks = self._num_cache_blocks
        config = self._config
        self.block_manager: Optional[KVBlockManager] = None
        layers, kv_heads, head_dim = _cache_dims(self.model_cfg)
        if self.kv_layout == "paged":
            t = -(-max_seq_len // cache_block_size)
            if num_cache_blocks is None:
                num_cache_blocks = max_batch * t  # slot-parity capacity
            self.cache = PagedKVCache.create(
                layers, max_batch, max_seq_len, kv_heads, head_dim,
                num_blocks=num_cache_blocks, block_size=cache_block_size,
                dtype=config.dtype, staged=True,
                quantized=self.kv_cache_dtype == "int8")
            self.state_manager = DSStateManager(
                max_batch, num_blocks=num_cache_blocks,
                block_size=cache_block_size)
            if self._prefix_sharing:
                # API-compatible superset of BlockedAllocator: refcounts,
                # prefix registry, COW queue — DSStateManager plumbing
                # (ensure_blocks / flush_sequence) is unchanged
                self.block_manager = KVBlockManager(num_cache_blocks,
                                                    cache_block_size)
                self.state_manager.block_allocator = self.block_manager
            self._tables_np = np.full((max_batch, t), -1, np.int32)
            self._tables_dirty = True  # install the -1 sentinels
            self._cache_desc = (
                f"{num_cache_blocks} blocks × {cache_block_size} tokens "
                f"(paged{', int8' if self.kv_cache_dtype else ''}), "
                f"{max_batch} seq rows")
        else:
            self.cache = KVCache.create(layers, max_batch, max_seq_len,
                                        kv_heads, head_dim, dtype=config.dtype)
            self.state_manager = DSStateManager(max_batch)
            self._cache_desc = f"{max_batch} slots × {max_seq_len} tokens"
        # park every slot: cursor at max_len → writes drop, reads mask out,
        # and the paged attention kernels run no step for the row
        # (docs/kv_cache.md, "A row that holds nothing")
        self.cache = self.cache.replace(
            index=jnp.full((max_batch,), self.cache.max_len, jnp.int32))
        # the host's mirror of which device cursors stand below capacity: a
        # slot leaves the parking when a program first writes its cursor
        # (prefill, a chunk) or `fork` sets it, and returns on flush
        self._unparked = np.zeros((max_batch,), bool)
        # Pin every cache leaf to ONE explicit sharding. jax.jit keys its
        # compile cache on input shardings: a freshly-created cache arrives
        # as uncommitted arrays, while the same program's donated output
        # comes back committed — without the pin, the serving programs
        # (chunk_batch etc.) silently recompile (~3.5 s each on the 470m
        # model) on the first round of every admission wave.
        from jax.sharding import NamedSharding, PartitionSpec
        from deepspeed_tpu.inference.kv_cache import tp_cache_shardings
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        # On a pure-TP mesh the pins shard the KV-head dim over 'model'
        # (tp_cache_shardings) so the sharded decode kernels find their
        # operands already distributed; everywhere else this is the
        # replicated pin it always was.
        self._cache_pin = tp_cache_shardings(self.cache, self.mesh)
        self.cache = jax.device_put(self.cache, self._cache_pin)
        # uid resident in each cache slot — folded into sampling keys so a
        # sequence's draws depend on (seed, uid, step), not on which slot
        # the scheduler reused (slot churn would otherwise permute rows'
        # noise between calls)
        self._slot_uids = np.zeros((max_batch,), np.int32)
        self._register_cache_residency()

    def _register_cache_residency(self) -> None:
        """MemoryPlane kv_cache row for the preallocated cache (real
        leaf nbytes — the v2 cache is a host-visible pytree, unlike v1's
        in-program cache). The block manager additionally keeps a LOGICAL
        occupancy row (excluded from tier totals — the physical bytes are
        this preallocation)."""
        from deepspeed_tpu.telemetry.memory import (get_plane, owner_for,
                                                    tree_bytes)
        owner = owner_for(self, type(self).__name__)
        get_plane().register(f"{owner}:kv_cache", component="kv_cache",
                             tier="hbm", nbytes=tree_bytes(self.cache),
                             owner=owner)
        if self.block_manager is not None:
            layers, kv_heads, head_dim = _cache_dims(self.model_cfg)
            elt = 1 + 4 / head_dim if self.kv_cache_dtype == "int8" \
                else jnp.dtype(self._config.dtype).itemsize
            self.block_manager.plane_wire(
                owner=owner,
                block_bytes=int(2 * layers * kv_heads *
                                self._cache_block_size * head_dim * elt))

    def _use_fused_int8(self) -> bool:
        fused = getattr(self._config, "fused_int8", None)
        if fused is not None:
            return bool(fused)
        return on_tpu()

    def _maybe_dequant(self, params):
        if not getattr(self, "_quantized", False):
            return params
        from deepspeed_tpu.inference.quantization import dequantize_param_tree
        return dequantize_param_tree(params, dtype=self._config.dtype)

    def _auto_layouts(self) -> bool:
        al = getattr(self._config, "auto_layouts", None)
        if al is not None:
            return bool(al)
        return on_tpu()

    def _make_apply(self):
        """The forward every bucketed program traces: `apply(params, ids,
        cache) → (logits, cache)`. dequant = the zoo model's own cached
        path (int8 trees dequantize in-program); layer_scan = the shared
        `make_block_fn` scan body over the per-layer int8 stacks
        (`make_scan_apply` — op-identical to v1's layer scan, the parity
        contract); capacity = an EAGER host-driven layer loop streaming
        the host tiers through the capacity runner's jitted block
        programs (capacity is for fit, not speed — per-op dispatch is the
        accepted cost, docs/capacity_serving.md)."""
        mode = self.serve_mode
        if mode == "layer_scan":
            from deepspeed_tpu.inference.quantized_layer_scan import (
                make_scan_apply)
            from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
            mesh = self.mesh if nontrivial_axes(self.mesh) else None
            return make_scan_apply(self.model_cfg,
                                   fused=self._use_fused_int8(), mesh=mesh)
        if mode == "capacity":
            runner = self._capacity
            logits_jit = runner.logits_program()

            def apply(params, ids, cache):
                max_len = int(cache.k.shape[2])
                embed_jit = runner._programs(max_len)
                h, aux = embed_jit(jnp.asarray(ids, jnp.int32),
                                   cache.index, max_len)
                cache_k = [cache.k[l] for l in range(runner.num_layers)]
                cache_v = [cache.v[l] for l in range(runner.num_layers)]
                h = runner._pass(h, aux, cache_k, cache_v)
                return logits_jit(h), KVCache(
                    k=jnp.stack(cache_k), v=jnp.stack(cache_v),
                    index=cache.index)
            return apply
        model = self.module
        if self._quantized:
            return lambda params, ids, cache: model.apply(
                {"params": self._maybe_dequant(params)}, ids, cache=cache)
        return lambda params, ids, cache: model.apply(
            {"params": params}, ids, cache=cache)

    # ------------------------------------------------------- paged plumbing
    def _reserve(self, seq, total_tokens: int) -> None:
        """Grow a sequence's physical block ownership to `total_tokens`
        (no-op in slot mode) and stage the block-table rows for device sync.
        With prefix sharing, this is also the fork-on-first-write gate: a
        write landing in a refcount>1 block (a forked partial tail) COWs it
        here, BEFORE the compiled step that writes — block copy queued for
        the batched sync, table entry rewritten."""
        if self.kv_layout != "paged":
            return
        # clamp to the row's logical capacity — writes past max_len DROP
        # (same degrade-gracefully semantics as the dense slot layout), so
        # reserving table entries past T would only overflow the table
        total_tokens = min(total_tokens, self.cache.max_len)
        if self.block_manager is not None and seq.blocks:
            cur = seq.seen_tokens          # next write position
            bs = self.state_manager.block_size
            bi = cur // bs
            # only a PARTIAL cursor block can be shared-and-written: prefix
            # matches share whole blocks (cursor lands on a boundary), so
            # this fires only after fork()
            if cur < total_tokens and cur % bs and bi < len(seq.blocks) \
                    and self.block_manager.refcount(seq.blocks[bi]) > 1:
                fresh_blk = self.block_manager.cow(seq.blocks[bi])
                seq.blocks[bi] = fresh_blk
                self._tables_np[seq.slot, bi] = fresh_blk
                self._tables_dirty = True
                self.tracer.bump(seq.uid, "cow_copies")
        fresh = self.state_manager.ensure_blocks(seq, total_tokens)
        if fresh:
            start = len(seq.blocks) - len(fresh)
            self._tables_np[seq.slot, start:start + len(fresh)] = fresh
            self._tables_dirty = True
            self._kv_util_peak = max(self._kv_util_peak,
                                     self.kv_utilization())

    def _copy_blocks_fn(self, width: int):
        """Batched COW block copy: gather `src` pool blocks, scatter at
        `dst` (padded entries carry an out-of-range dst → drop). ONE
        compiled program per pad width, pinned like every serving program."""
        key = ("cow_copy", width)
        if key in self._jits:
            return self._jits[key]

        def copy(cache, src, dst):
            def cp(pool):  # pool (L,Hkv,NB,BS[,D]) — NB is axis 2
                return pool.at[:, :, dst].set(
                    jnp.take(pool, src, axis=2), mode="drop")
            k = cache.k.replace(pool=cp(cache.k.pool))
            v = cache.v.replace(pool=cp(cache.v.pool))
            if cache.k.scales is not None:
                k = k.replace(scales=cp(cache.k.scales))
                v = v.replace(scales=cp(cache.v.scales))
            return PagedKVCache(k=k, v=v, index=cache.index)

        return self._register(key, copy, donate=(0,))

    def _maybe_sync_tables(self):
        """Push host-side block-table edits to the device cache. Called
        before every compiled step; a no-op unless allocation changed (the
        common decode round re-uses the resident tables). Tables are
        device_put with the pinned sharding — an uncommitted array here
        would change the jit cache key and recompile the serving programs.
        Queued COW copies drain here FIRST (they read pre-step source
        content; steps only run after this sync), batched into one padded
        gather/scatter — never a per-copy dispatch. Returns (the tables
        were dirty, COW copies drained)."""
        if self.kv_layout != "paged":
            return False, 0
        copies = (self.block_manager.drain_copies()
                  if self.block_manager is not None else [])
        if copies:
            width = 1 << max(len(copies) - 1, 0).bit_length()
            nb = self.cache.k.pool.shape[2]
            src = np.zeros((width,), np.int32)
            dst = np.full((width,), nb, np.int32)  # OOB sentinel: drop
            for i, (s, d) in enumerate(copies):
                src[i], dst[i] = s, d
            self.cache = self._copy_blocks_fn(width)(
                self.cache, jnp.asarray(src), jnp.asarray(dst))
            self._tables_dirty = True  # every cow rewrote a table entry
        dirty = self._tables_dirty
        if dirty:
            self.cache = jax.device_put(
                self.cache.with_tables(jnp.asarray(self._tables_np)),
                self._cache_pin)
            self._tables_dirty = False
            self.serving_counters["table_syncs"] += 1
        return dirty, len(copies)

    def _match_prefix(self, seq, tokens) -> int:
        """Admission-time prefix match: share the longest committed block
        chain of `tokens` (capped at len−1 so the last prompt token always
        runs and yields logits), install the shared blocks in the table,
        and advance the cursor. Returns matched tokens (multiple of the
        block size; 0 = no sharing)."""
        if self.block_manager is None or len(tokens) < 2:
            return 0
        n, blocks = self.block_manager.match_prefix(
            list(map(int, tokens)), max_tokens=len(tokens) - 1)
        if not n:
            return 0
        seq.blocks = list(blocks)
        self._tables_np[seq.slot, :len(blocks)] = blocks
        self._tables_dirty = True
        seq.seen_tokens = n
        return n

    def _commit_prefix(self, seq) -> None:
        """Register a freshly-prefilled sequence's FULL blocks in the
        prefix registry (idempotent; partial tail stays private)."""
        if self.block_manager is not None and seq.blocks:
            self.block_manager.commit_prefix(
                seq.tokens[:seq.seen_tokens], seq.blocks)

    def fork(self, parent_uid: int, child_uid: int) -> None:
        """Clone a live sequence's full context under a new uid: the child
        shares EVERY parent block — including the partial tail — with
        refcounts; whichever of the two writes that tail first triggers the
        copy-on-write in `_reserve`. Bit-exact vs re-prefilling the same
        tokens by construction (same physical KV until a write forks it)."""
        if self.kv_layout != "paged" or self.block_manager is None:
            raise ValueError("fork() needs the paged layout with "
                             "prefix_sharing enabled")
        if self.state_manager.known_sequence(child_uid):
            raise ValueError(f"fork target uid {child_uid} already tracked")
        parent = self.state_manager.get_sequence(parent_uid)
        if parent.pending:
            raise ValueError(f"cannot fork uid {parent_uid} mid-prefill")
        child = self.state_manager.get_or_create_sequence(child_uid)
        self._slot_uids[child.slot] = _uid_fold(child_uid)
        # the child's trace starts here: its "prompt" is the shared context
        self.tracer.begin_request(child_uid, prompt_tokens=parent.seen_tokens,
                                  slot=child.slot, forked_from=parent_uid)
        self.block_manager.share(parent.blocks)
        child.blocks = list(parent.blocks)
        child.tokens = list(parent.tokens)
        child.seen_tokens = parent.seen_tokens
        self._tables_np[child.slot, :len(child.blocks)] = child.blocks
        self._tables_dirty = True
        # un-park the child's device cursor (decode programs read it)
        self.cache = self.cache.replace(
            index=self.cache.index.at[child.slot].set(child.seen_tokens))
        self._unparked[child.slot] = True

    # ----------------------------------------------------------- telemetry
    def _stall_total(self) -> float:
        """Lifetime capacity-staging stall (ms) — the runner's monotone
        accumulator; 0.0 outside capacity mode. Span bodies delta-read it
        so a wave's `prefetch_stall_ms` rides the span fields instead of a
        second timing source."""
        c = self._capacity
        return getattr(c, "prefetch_stall_ms_total", 0.0) \
            if c is not None else 0.0

    @property
    def _eager_serving(self) -> bool:
        """Capacity mode's host-driven layer loop can't trace into one
        jit — its program bodies run EAGERLY (composed of the runner's
        jitted block/embed/head programs)."""
        return self.serve_mode == "capacity"

    def _register(self, key, body, donate=(1,)):
        """Build-register a serving program: jit (donating the cache
        argument) + `_track` wrapping, or the eager body in capacity mode.
        The jitted body is renamed so that the device trace's `XLA Modules`
        line reads `jit_ds_v2_<program>` (`_program_name`), not a
        closure's name."""
        if key in self._jits:
            return self._jits[key]
        fault_point("program_compile", label=self.serve_mode)
        if self._eager_serving:
            fn = self._track(key, body, raw=False)
        else:
            body.__name__ = body.__qualname__ = jit_name(
                "v2:" + self._program_name(key))
            fn = self._track(key, jax.jit(body, donate_argnums=donate),
                             body=body)
        self._jits[key] = fn
        return fn

    def _program_name(self, key) -> str:
        """The name the detector pins and the `compile` span carries; with
        `v2:` before it and through `telemetry.jit_name`, the jitted
        function's and so the module's in a device trace
        (`jit_ds_v2_fused_batch_128_4`): one name a program. Single-device
        dequant names are bare (the stability contract); a non-default
        serve mode, a quantized cache and a mesh each make a DIFFERENT
        program, so each adds its suffix."""
        name = key if isinstance(key, str) else ":".join(map(str, key))
        if self.serve_mode != "dequant":
            name = f"{name}@{self.serve_mode}"
        if getattr(self, "kv_cache_dtype", None):
            name = f"{name}@kv_{self.kv_cache_dtype}"
        from deepspeed_tpu.ops.pallas.sharded import mesh_fingerprint
        fp = mesh_fingerprint(self.mesh)
        return f"{name}@{fp}" if fp else name

    def _track(self, key, fn, body=None, raw=True):
        """Wrap a compiled serving program with dispatch-time signature
        tracking: a recompile of a pinned program (the Round-4 unpinned-
        cache-leaf bug class) becomes a loud warning + telemetry event
        instead of a silent multi-second stall.

        On layout-auto platforms (TPU), the FIRST jitted dispatch also
        pins the param tree's AUTO input layouts (`_pin_param_layouts`)
        BEFORE the program compiles — pin-once for the whole bucketed
        family: every later program compiles against the committed
        layouts, so no bucket pays the v1 relayout-in-program +3 GB or a
        ~3.5 s signature-miss recompile."""
        name = self._program_name(key)
        det = self.recompiles
        first = True

        def wrapped(*args):
            nonlocal first
            if (body is not None and not self._layouts_pinned
                    and self._auto_layouts() and args
                    and args[0] is self.params):
                rest = args[1:]
                with compile_span(name, "v2", phase="pin_layouts",
                                  under=self.tracer.current()):
                    self._pin_param_layouts(body, rest)
                args = (self.params,) + rest
            det.observe(name, args)
            if first:
                # the program's compile (or its load from the persistent
                # cache) is this call: one `compile` span with its name
                first = False
                with compile_span(name, "v2", under=self.tracer.current()):
                    if raw:   # the tracing the call uses, for the map
                        keep_program(name, fn.trace(*args), mesh=self.mesh)
                    return fn(*args)
            return fn(*args)
        # the raw jit and the detector name, for tools/tpuverify (the
        # wrapper hides .lower(); the verifier lowers the raw program and
        # cross-checks detector coverage by name). Eager capacity
        # bodies carry no raw jit — the verifier skips them.
        wrapped._ds_raw = fn if raw else None
        wrapped._ds_program = name
        return wrapped

    def _pin_param_layouts(self, body, rest) -> None:
        """Resolve AUTO input layouts for ONE representative serving
        program and re-place `self.params` in them, leaf-wise (v1's
        `_compile_auto_layout` recipe): lower on ABSTRACT avals (concrete
        placed leaves carry committed formats AUTO refuses), read the
        compiled program's preferred param formats, rebind each leaf so
        the old copy frees before the next relayouts. Later programs
        compile against the committed layouts — resolve once, serve every
        (bucket, serve_mode) program. The AOT executable is discarded
        (the caller's ordinary jit recompiles against the pinned tree).
        Failures warn once and serve default layouts — never fatal."""
        self._layouts_pinned = True
        try:
            from deepspeed_tpu.telemetry.recompile import abstract_args
            from deepspeed_tpu.utils.layouts import (auto_input_format,
                                                     compiled_input_formats,
                                                     relayout_leaves)
            jfn = jax.jit(body, in_shardings=auto_input_format())
            # avals keep the committed SHARDINGS (params, cache): without
            # them the program is compiled for replicated inputs
            compiled = jfn.lower(*abstract_args((self.params,) + rest)
                                 ).compile()
            fmts = compiled_input_formats(compiled)[0]
            leaves, treedef = jax.tree_util.tree_flatten(self.params)
            fmt_leaves = jax.tree_util.tree_leaves(fmts[0])
            self.params = None  # engine ref drops; leaves list keeps each
            try:
                relayout_leaves(leaves, fmt_leaves)
            finally:
                # a mid-loop OOM must leave a usable (mixed-layout) tree
                self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        except Exception as e:  # serving goes on under default layouts
            warn_once(("v2_auto_layout",),
                      f"v2: auto-layout pin failed ({type(e).__name__}: "
                      f"{str(e)[:160]}); serving with default layouts")

    def kv_utilization(self) -> float:
        """Fraction of the KV pool in use: physical blocks (paged) or
        sequence slots (dense)."""
        if self.kv_layout == "paged":
            alloc = self.state_manager.block_allocator
        else:
            alloc = self.state_manager.allocator
        total = max(alloc.num_blocks, 1)
        return (total - alloc.free_blocks) / total

    def _weight_bytes_per_step(self):
        """(at-rest, dense-equivalent) weight bytes one decode step reads —
        the telemetry pair that makes 'is this serve mode weight-read-bound
        where it should be' a one-line check. Cached (invalidated on
        degradation); llama-layout trees use the layer-scan accounting
        (embed gather excluded), other trees fall back to whole-tree byte
        counts."""
        if self._weight_bytes_cache is None:
            from deepspeed_tpu.inference import quantized_layer_scan as qls
            from deepspeed_tpu.inference.quantization import is_quantized_leaf
            if self.serve_mode == "capacity":
                self._weight_bytes_cache = \
                    self._capacity.weight_bytes_step_pair()
            elif isinstance(self.params, dict) and "layers" in self.params:
                self._weight_bytes_cache = (
                    qls.weight_bytes_per_step(self.params),
                    qls.dense_bytes_per_step(self.params, self._config.dtype))
            else:
                itemsize = jnp.dtype(self._config.dtype).itemsize
                at_rest = dense = 0
                for leaf in jax.tree_util.tree_leaves(
                        self.params, is_leaf=is_quantized_leaf):
                    if is_quantized_leaf(leaf):
                        at_rest += (leaf["__q8__"].nbytes
                                    + leaf["scales"].nbytes)
                        dense += leaf["__q8__"].size * itemsize
                    elif hasattr(leaf, "nbytes"):
                        at_rest += leaf.nbytes
                        dense += leaf.size * itemsize
                self._weight_bytes_cache = (int(at_rest), int(dense))
        return self._weight_bytes_cache

    def telemetry_snapshot(self) -> Dict[str, Any]:
        """Serving counters for the telemetry hub: TTFT percentiles,
        decode throughput, KV-page utilization, flush/recompile counts.
        Derived from last_timing (the SLA stamps), so it reflects the most
        recent generate() call plus engine-lifetime counters."""
        ftls = sorted(rec["first"] for rec in self.last_timing.values()
                      if "first" in rec)
        done = [rec for rec in self.last_timing.values()
                if "done" in rec and "first" in rec]
        gen = sum(int(r.get("new_tokens", 0)) for r in done)
        span = max((r["done"] for r in done), default=0.0)
        pct = lambda a, q: (round(a[min(len(a) - 1, int(q * len(a)))], 4)
                            if a else None)
        # kv_bytes is pure shape arithmetic over the cache leaves (array
        # metadata) — never a device fetch (the hot-loop contract)
        kv_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            self.cache) if hasattr(leaf, "nbytes"))
        mgr = self.block_manager
        wb, wb_dense = self._weight_bytes_per_step()
        drafted = self.serving_counters["spec_draft_tokens"]
        return {"queries": len(self.last_timing),
                "serve_mode": self.serve_mode,
                "weight_bytes_step": wb,
                "weight_bytes_step_dense": wb_dense,
                "speculative": self._spec_enabled,
                "spec_k": self._spec_k if self._spec_enabled else None,
                "acceptance_rate":
                    (round(self.serving_counters["spec_accepted_tokens"]
                           / drafted, 4) if drafted else None),
                "unstamped_queries": len(self.last_timing) - len(ftls),
                "ttft_p50_s": pct(ftls, 0.5), "ttft_p95_s": pct(ftls, 0.95),
                "decode_tok_s": round(gen / span, 1) if span > 0 else None,
                "kv_layout": self.kv_layout,
                "kv_dtype": (self.kv_cache_dtype
                             or jnp.dtype(self._config.dtype).name),
                "kv_bytes": int(kv_bytes),
                "kv_shared_blocks": mgr.shared_blocks if mgr else 0,
                "kv_cow_copies": mgr.cow_copies if mgr else 0,
                "kv_prefix_hits": mgr.prefix_hits if mgr else 0,
                "kv_prefix_tokens_reused":
                    mgr.prefix_tokens_reused if mgr else 0,
                "kv_util": round(self.kv_utilization(), 4),
                "kv_util_peak": round(self._kv_util_peak, 4),
                "recompiles": self.recompiles.misses,
                "pinned_recompiles": self.recompiles.pinned_misses,
                **self.serving_counters}

    # ------------------------------------------------------------ compiled
    @jax.named_scope("row_view")
    def _row_view(self, cache, slot, start):
        """A batch-of-1 view of `slot`'s cache row. Dense: slice the row
        arrays. Paged: slice only the (L, B, T) block tables — the pools are
        shared, and the row's writes land in its own blocks, so prefill
        never copies cache rows at all (the paged layout's second win)."""
        if self.kv_layout == "paged":
            # stage stripped: prefill/chunk programs never call apply_stage,
            # so a staged write here (e.g. a 1-token chunk) would be LOST —
            # without stage, update_layer scatters straight to the pool
            return PagedKVCache(
                k=cache.k.replace(tables=jax.lax.dynamic_slice_in_dim(
                    cache.k.tables, slot, 1, axis=1), stage=None),
                v=cache.v.replace(tables=jax.lax.dynamic_slice_in_dim(
                    cache.v.tables, slot, 1, axis=1), stage=None),
                index=start[None])
        return KVCache(
            k=jax.lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1),
            v=jax.lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1),
            index=start[None])

    @jax.named_scope("merge_row")
    def _merge_row(self, cache, row, slot, new_index):
        """Fold a row view's updates back into the full cache."""
        if self.kv_layout == "paged":
            return PagedKVCache(
                k=cache.k.replace(pool=row.k.pool, scales=row.k.scales),
                v=cache.v.replace(pool=row.v.pool, scales=row.v.scales),
                index=cache.index.at[slot].set(new_index))
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, row.k, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, row.v, slot, axis=1)
        return KVCache(k=k, v=v, index=cache.index.at[slot].set(new_index))

    def _prefill_fn(self, sp: int):
        key = ("prefill", sp)
        apply = self._apply

        def prefill(params, cache, ids, slot, true_len):
            row = self._row_view(cache, slot, jnp.zeros((), jnp.int32))
            logits, row = apply(params, ids, row)
            with jax.named_scope("head"):
                last = jnp.take_along_axis(
                    logits,
                    (true_len - 1)[None, None, None].astype(jnp.int32),
                    axis=1)[0, 0]
            return self._merge_row(cache, row, slot, true_len), last

        return self._register(key, prefill)

    def _chunk_parts(self):
        """Shared chunk-prefill body: insert a (1, C) chunk of a prompt at
        row `slot` starting at cursor `start`; `valid` of the C ids are real
        (the tail of a prompt pads to the fixed chunk length so ONE compiled
        program serves every chunk). The model's cache path already places
        queries at per-row cursor offsets, so a chunk is just a cached call
        on the row view."""
        apply = self._apply

        def chunk_into(params, cache, ids, slot, start, valid):
            row = self._row_view(cache, slot, start)
            logits, row = apply(params, ids, row)
            with jax.named_scope("head"):
                last = jnp.take_along_axis(
                    logits, (valid - 1)[None, None, None].astype(jnp.int32),
                    axis=1)[0, 0]
            return self._merge_row(cache, row, slot, start + valid), last
        return chunk_into

    def _chunk_fn(self):
        """Chunk-only step (no decode rows to fuse with)."""
        return self._register(("chunk", self.split_fuse_chunk),
                              self._chunk_parts())

    def _chunk_batch_parts(self):
        """Batched chunk prefill (paged layout): R rows' prompt chunks run
        as ONE compiled call — the reference packs mixed prefill rows into
        one ragged batch (`inference/v2/ragged/ragged_wrapper.py`); here the
        rows share the (R, C) program, each writing through its slot's
        block-table row at its own cursor. Several rows may be ONE
        sequence's, consecutive chunks at consecutive cursors: a layer
        writes every row's K/V before its attention reads the pool, and
        the attention masks by absolute position, so they are one longer
        chunk, bit for bit (`tests/unit/inference/
        test_filled_chunk_rounds.py`). `slots[i]` is the row's slot, or
        `~slot` when another row of the same sequence follows it in this
        call: only the last row of a sequence sets its cursor. Unused rows
        park (start = max_len → writes drop, outputs ignored)."""
        apply = self._apply

        def chunk_batch(params, cache, ids, slots, starts, valids):
            # parked rows carry slot == max_batch (out of range): the table
            # gather clips (their writes drop on the parked cursor anyway)
            # and the index scatter DROPS them — a parked row must never
            # collide with a live row's slot in the scatter (duplicate-index
            # scatter is last-wins). Nor may two rows of ONE sequence: a
            # row that another row of its sequence follows carries ~slot,
            # which reads the slot's table and leaves the cursor to the
            # sequence's last row.
            followed = slots < 0
            tables_of = jnp.where(followed, ~slots, slots)
            cursor_of = jnp.where(followed, self.max_batch, slots)
            with jax.named_scope("table_gather"):
                rows = PagedKVCache(
                    k=cache.k.replace(tables=jnp.take(cache.k.tables,
                                                      tables_of, axis=1,
                                                      mode="clip"),
                                      stage=None),  # chunks write the pool
                    v=cache.v.replace(tables=jnp.take(cache.v.tables,
                                                      tables_of, axis=1,
                                                      mode="clip"),
                                      stage=None),
                    index=starts)
            logits, rows = apply(params, ids, rows)
            with jax.named_scope("merge_row"):
                index = cache.index.at[cursor_of].set(starts + valids,
                                                      mode="drop")
                new_cache = PagedKVCache(
                    k=cache.k.replace(pool=rows.k.pool,
                                      scales=rows.k.scales),
                    v=cache.v.replace(pool=rows.v.pool,
                                      scales=rows.v.scales), index=index)
            with jax.named_scope("head"):
                last = jnp.take_along_axis(
                    logits, jnp.maximum(valids - 1, 0)[:, None, None],
                    axis=1)[:, 0]      # (R, V) — one next-token row each
            return new_cache, last
        return chunk_batch

    def _chunk_batch_fn(self):
        """Chunks alone, `max_batch` rows wide: the round that has nothing
        to decode and more prompts than the widest narrow rung holds."""
        return self._register(("chunk_batch", self.split_fuse_chunk),
                              self._chunk_batch_parts())

    def _fused_batch_fn(self, width: int):
        """Split-fuse, batched: ONE program decodes every live row (always
        `max_batch` wide: cache rows are slots) AND runs every pending
        prompt chunk, `width` rows of them (`chunk_row_widths`). Below
        `max_batch` it also serves the round with no row to decode: the
        idle decode half costs less than `max_batch` chunk rows, and a
        chunk-only program for each width would be one more to compile."""
        key = ("fused_batch", self.split_fuse_chunk, width)
        apply = self._apply
        chunk_batch = self._chunk_batch_parts()

        def fused(params, cache, tokens, active, ids, slots, starts, valids):
            old_index = cache.index
            logits_d, cache = apply(params, tokens, cache)
            cache = cache.apply_stage()
            cache = cache.replace(
                index=jnp.where(active, old_index + 1, old_index))
            cache, last = chunk_batch(params, cache, ids, slots, starts,
                                      valids)
            return cache, logits_d[:, -1, :], last

        return self._register(key, fused)

    def _parked_rows(self, width: int):
        """(ids, slots, starts, valids) of `width` chunk rows, every one
        parked: slot `max_batch` (the index scatter drops it), cursor at
        `max_len` (its writes drop), nothing valid."""
        return (np.zeros((width, self.split_fuse_chunk), np.int32),
                np.full((width,), self.max_batch, np.int32),
                np.full((width,), self.cache.max_len, np.int32),
                np.zeros((width,), np.int32))

    def _warm_chunk_family(self, reduce) -> None:
        """Compile every batched chunk program (`chunk_batch`, and
        `fused_batch` at each width), once, before the first of them serves
        a round: each is dispatched with every row parked and no decode row
        active, so its writes drop and the cache comes back as it went in,
        and `reduce` (the round's own on-device reduce) is run on each
        (width, V) output, which compiles per shape too. A caller warms
        with a few prompts and then meets every width under load, where a
        first compile would cost seconds inside a round. Not rounds:
        nothing is counted. Widest first, so the layout pin (`_track`) is
        resolved on the program that holds the most rows."""
        decode = (np.zeros((self.max_batch, 1), np.int32),
                  np.zeros((self.max_batch,), bool))
        rng = self._rng   # a sampling reduce splits it; warming must not
        self.cache, last = self._chunk_batch_fn()(
            self.params, self.cache,
            *map(jnp.asarray, self._parked_rows(self.max_batch)))
        reduce(last)
        for width in reversed(chunk_row_widths(self.max_batch)):
            self.cache, _, last = self._fused_batch_fn(width)(
                self.params, self.cache,
                *map(jnp.asarray, decode + self._parked_rows(width)))
            reduce(last)
        self._rng = rng
        self._chunk_family_warm = True

    def _fused_fn(self):
        """The split-fuse step: ONE compiled program decodes every live row
        AND pushes one prefill chunk. The decode write at the chunk row's
        cursor is garbage but the chunk immediately overwrites that slot;
        rows are otherwise disjoint."""
        key = ("fused", self.split_fuse_chunk)
        apply = self._apply
        chunk_into = self._chunk_parts()

        def fused(params, cache, tokens, active, ids, slot, start, valid):
            old_index = cache.index
            logits_d, cache = apply(params, tokens, cache)
            cache = cache.apply_stage()
            index = jnp.where(active, old_index + 1, old_index)
            cache = cache.replace(index=index)
            cache, last = chunk_into(params, cache, ids, slot, start, valid)
            return cache, logits_d[:, -1, :], last

        return self._register(key, fused)

    def _decode_scan_fn(self, k: int):
        """K decode steps in ONE compiled program (the v1 engine's
        scan-decode, over the continuous-batching cache): the serving loop
        dispatches once per K tokens instead of once per token — K times
        fewer host round-trips per token. Greedy, or on-device
        temperature/top-k/top-p sampling when the serving loop set a
        sampling config (one split key per scan step)."""
        cfg = self._sample_cfg
        key = ("decode_scan", k, cfg)
        apply = self._apply
        from deepspeed_tpu.ops.sampling import sample_logits
        sampled = cfg is not None and cfg[0] != 0.0

        def step(params, cache, toks, active, rng_i, fold):
            old = cache.index
            logits, cache = apply(params, toks, cache)
            cache = cache.apply_stage()
            cache = cache.replace(index=jnp.where(active, old + 1, old))
            with jax.named_scope("head"):
                last = logits[:, -1, :]
                if sampled:
                    nxt = sample_logits(last, rng_i, *cfg, row_fold=fold)
                else:
                    nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return cache, nxt

        if self._eager_serving:
            # capacity: the host-driven layer loop can't live inside a
            # lax.scan — run the K steps as a python loop of the SAME ops
            # in the SAME order (incl. the key draw), so eager capacity
            # decode is op-for-op the jitted scan body
            def fn(params, cache, tokens, active, rng, fold):
                keys = (jax.random.split(rng, k) if sampled
                        else jnp.zeros((k, 2), jnp.uint32))
                toks, out = tokens, []
                for i in range(k):
                    cache, nxt = step(params, cache, toks, active, keys[i],
                                      fold)
                    out.append(nxt)
                    toks = nxt[:, None]
                return cache, jnp.stack(out)  # (K, B) token ids
        else:
            def fn(params, cache, tokens, active, rng, fold):
                keys = (jax.random.split(rng, k) if sampled
                        else jnp.zeros((k, 2), jnp.uint32))

                def body(carry, rng_i):
                    cache, toks = carry
                    cache, nxt = step(params, cache, toks, active, rng_i,
                                      fold)
                    return (cache, nxt[:, None]), nxt
                (cache, _), toks = jax.lax.scan(body, (cache, tokens), keys)
                return cache, toks  # (K, B) token ids

        return self._register(key, fn)

    def _decode_fn(self):
        key = "decode"
        apply = self._apply

        def decode(params, cache, tokens, active):
            # tokens (R, 1); active (R,) bool — inactive rows are parked at
            # max_len so their writes drop and their cursors stay put
            old_index = cache.index
            logits, cache = apply(params, tokens, cache)
            cache = cache.apply_stage()
            index = jnp.where(active, old_index + 1, old_index)
            return cache.replace(index=index), logits[:, -1, :]

        return self._register(key, decode)

    # ----------------------------------------------------------- speculative
    def _setup_spec(self) -> None:
        """Speculative decoding over the continuous batcher: the k+1
        verify window rides the target cache's write-past-cursor
        semantics (truncate = cursor rollback), but ONLY for
        single-sequence-per-step buckets — rows of a ragged decode batch
        accept DIFFERENT draft counts per round, which breaks the
        fixed-shape wave contract, so multi-row steps fall back loudly
        to vanilla waves (`_generate`). v2 spec is self-draft only (a
        layer-sliced sub-stack sharing embed/norm/head), single-device,
        and not on capacity mode (the draft needs resident layers);
        structurally-unsupported configs warn and serve vanilla,
        user-config errors raise (the r8 contract)."""
        self._spec_state: Dict[int, Dict[str, Any]] = {}
        self._spec_enabled = False
        self._spec_draft = None
        self._spec_k = 0
        spec = getattr(self._config, "speculative", None) or {}
        if not spec.get("enabled"):
            return
        if str(spec.get("draft", "self")) != "self":
            raise ValueError(
                "v2 speculative decoding supports draft='self' only (the "
                "separate-model flavor lives in the v1 engine)")
        k = int(spec.get("k", 4))
        if k < 1:
            raise ValueError("speculative: k must be >= 1")
        from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
        if nontrivial_axes(self.mesh):
            warn_once(("v2_spec", "mesh"),
                      "v2 speculative decoding is single-device; "
                      "serving vanilla decode")
            return
        if self.serve_mode == "capacity":
            warn_once(("v2_spec", "capacity"),
                      "v2 speculative decoding does not ride capacity "
                      "mode (the draft needs resident layers); serving "
                      "vanilla decode")
            return
        from deepspeed_tpu.inference import quantized_layer_scan as qls
        # detect on the DENSE tree shape — quantized at-rest trees carry
        # flat scales the shape probe would trip on (r8 lesson)
        try:
            dense_abs = jax.eval_shape(self._maybe_dequant, self.params)
        except Exception:
            dense_abs = self.params
        if not (isinstance(self.params, dict)
                and qls.layer_scan_supported(dense_abs)):
            warn_once(("v2_spec", "layout"),
                      "v2 speculative decoding needs a llama-layout param "
                      "tree (stacked 'layers'); serving vanilla decode")
            return
        from deepspeed_tpu.inference.quantized_layer_scan import (
            make_scan_apply)
        from deepspeed_tpu.models.draft import (num_layers_of,
                                                resolve_draft_layers)
        idx = resolve_draft_layers(num_layers_of(self.model_cfg),
                                   spec.get("draft_layers", 0.5))
        self._spec_layers = len(idx)
        self._spec_draft = self._materialize_draft(list(idx))
        # the draft always runs the engine-level scan body — op-identical
        # for any leading L', so the SAME apply serves the sub-stack
        self._spec_apply = make_scan_apply(self.model_cfg,
                                           fused=self._use_fused_int8())
        self._spec_k = k
        self._spec_enabled = True
        logger.info(f"v2 speculative decoding: k={k}, draft=self "
                    f"layers={list(idx)}, serve_mode={self.serve_mode}")

    def _materialize_draft(self, idx: List[int]):
        """Gather the draft sub-stack ONCE. Non-layer leaves (embed, norm,
        head) are shared with the target tree; the layer gather copies
        len(idx)/L of the stacks (`spec_draft_bytes` accounts it in the
        auto resolver). Whole-tree-quantized dequant trees dequantize
        INSIDE the same jit — the draft runs many small steps, so its
        slice is held dense (and its embed/head too: the whole-tree
        quantizer covers them, and the scan body wants them dense)."""
        idx_arr = jnp.asarray(idx, jnp.int32)
        dequant_first = self.serve_mode == "dequant" and self._quantized

        def build(p):
            if dequant_first:
                p = self._maybe_dequant(p)
            out = {kk: vv for kk, vv in p.items() if kk != "layers"}
            out["layers"] = jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx_arr, axis=0), p["layers"])
            return out
        return jax.jit(build)(self.params)

    def _spec_prefill_fn(self, sp: int):
        """Draft prefill: run the (bucketed) prompt through the draft
        sub-stack into a FRESH dense draft cache created in-program. The
        garbage KV at padded positions is overwritten before any query at
        or past it attends — the same write-before-attend contract as the
        bucketed target prefill."""
        key = ("spec_prefill", sp)
        spec_apply = self._spec_apply
        _, kv_heads, head_dim = _cache_dims(self.model_cfg)
        dl, dmax = self._spec_layers, self.cache.max_len
        dtype = self._config.dtype

        def body(draft, ids):
            shape = (dl, 1, dmax, kv_heads, head_dim)
            cache = KVCache(k=jnp.zeros(shape, dtype),
                            v=jnp.zeros(shape, dtype),
                            index=jnp.zeros((1,), jnp.int32))
            _, cache = spec_apply(draft, ids, cache)
            return cache.k, cache.v

        return self._register(key, body, donate=())

    def _spec_propose_fn(self, cfg):
        """k-token draft proposal (`speculative.draft_propose` — the
        pinned width-2 catch-up feed + k−1 single-token steps). Returns
        (drafts (1, k), filtered draft probs or None when greedy, and the
        advanced draft cache arrays); the post-round draft cursor is the
        verify program's business (dci), so the propose-side index is
        dropped."""
        key = ("spec_propose", self._spec_k, cfg)
        from deepspeed_tpu.inference.speculative import draft_propose
        spec_apply = self._spec_apply
        k = self._spec_k
        temperature, top_k, top_p = cfg if cfg else (0.0, 0, 1.0)

        def body(draft, dk, dv, dix, pend, pl, c, keys):
            def d_fwd(st, toks):
                ck, cv, ix = st
                logits, cache = spec_apply(
                    draft, toks, KVCache(k=ck, v=cv, index=ix))
                return logits, (cache.k, cache.v, ix + toks.shape[1])

            def d_set(st, ix):
                return (st[0], st[1],
                        jnp.broadcast_to(ix, st[2].shape).astype(jnp.int32))

            drafts, dprobs, (dk, dv, _) = draft_propose(
                d_fwd, d_set, (dk, dv, dix), pend, pl, c, keys, k=k,
                temperature=temperature, top_k=top_k, top_p=top_p)
            return drafts, dprobs, dk, dv

        return self._register(key, body, donate=(1, 2))

    def _spec_verify_fn(self, cfg, eos):
        """Target-side verify: feed the k+1 candidate window
        `[t0, d_1..d_k]` through the serve mode's apply at the row's
        cursor (the staged-KV append region past the committed cursor IS
        the verify window), then `accept_commit` — acceptance rolls the
        row cursor to committed+accepted+1, so rejected tokens' KV is
        never attendable (dense-cursor truncate semantics)."""
        key = ("spec_verify", self._spec_k, cfg, eos)
        from deepspeed_tpu.inference.speculative import accept_commit
        apply = self._apply
        temperature, top_k, top_p = cfg if cfg else (0.0, 0, 1.0)

        def body(params, cache, slot, c, t0, drafts, dprobs, acc_key):
            row = self._row_view(cache, slot, c[0])
            cand = jnp.concatenate([t0[:, None], drafts], axis=1)  # (1,k+1)
            vlogits, row = apply(params, cand, row)
            emit, count, acc, pend, pl, c_new, dci, _ = accept_commit(
                vlogits, drafts, dprobs, acc_key, c,
                jnp.zeros((1,), jnp.bool_), temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos, pad_token_id=0)
            cache = self._merge_row(cache, row, slot, c_new[0])
            return cache, emit, count, acc, pend, pl, dci

        return self._register(key, body)

    def _spec_round(self, uid, seq, results, budget, eos_token_id) -> bool:
        """One draft-and-verify round for the lone live sequence; returns
        True when it retired (budget/eos). The draft cache and round
        cursors persist host-side per uid across rounds under the pinned
        invariant dci + pl == c + 1; ANY trim of the emitted run (eos or
        budget) retires the row, so the in-program cursor never needs a
        host-side fixup."""
        cfg = self._sample_cfg
        k = self._spec_k
        c = seq.seen_tokens
        t0 = int(results[uid][-1])
        # Round cursors always enter propose as committed
        # SingleDeviceSharding arrays: verify's jit outputs come back with
        # compiler-chosen NamedShardings, and a sharding-repr flip re-keys
        # the pinned propose program. The re-put of three scalar-sized
        # arrays per round is noise next to the propose/verify dispatches.
        put = lambda x: jax.device_put(x, jax.devices()[0])
        st = self._spec_state.get(uid)
        if st is None or st["c"] != c:
            sp = _bucket(max(c, 1))
            ids = np.zeros((1, sp), np.int32)
            ids[0, :c] = results[uid][:c]
            dk, dv = self._spec_prefill_fn(sp)(self._spec_draft,
                                               jnp.asarray(ids))
            st = {"dk": dk, "dv": dv,
                  "dix": put(jnp.full((1,), c, jnp.int32)),
                  "pend": put(jnp.asarray([[t0, 0]], jnp.int32)),
                  "pl": put(jnp.ones((1,), jnp.int32)), "c": c}
            self._spec_state[uid] = st
        self._reserve(seq, min(c + k + 1, self.cache.max_len))
        self._maybe_sync_tables()
        ks = jax.random.split(self._rng, k + 2)
        self._rng, acc_key, prop_keys = ks[0], ks[1], ks[2:]
        cv = jnp.full((1,), c, jnp.int32)
        drafts, dprobs, dk, dv = self._spec_propose_fn(cfg)(
            self._spec_draft, st["dk"], st["dv"], st["dix"], st["pend"],
            st["pl"], cv, prop_keys)
        self.cache, emit, count, acc, pend, pl, dci = \
            self._spec_verify_fn(cfg, eos_token_id)(
                self.params, self.cache, jnp.asarray(seq.slot, jnp.int32),
                cv, jnp.full((1,), t0, jnp.int32), drafts, dprobs, acc_key)
        # ONE fetch for the round's verdict (the r8 telemetry contract)
        emit_np, count_np, acc_np = jax.device_get((emit, count, acc))
        count_i, acc_i = int(count_np[0]), int(acc_np[0])
        new = [int(t) for t in emit_np[0][:count_i]]
        if eos_token_id is not None and eos_token_id in new:
            new = new[:new.index(eos_token_id) + 1]
        new = new[:budget[uid]]
        seq.tokens.extend(new)
        results[uid].extend(new)
        budget[uid] -= len(new)
        self.serving_counters["generated_tokens"] += len(new)
        self.serving_counters["spec_rounds"] += 1
        self.serving_counters["spec_draft_tokens"] += k
        self.serving_counters["spec_accepted_tokens"] += acc_i
        if (len(new) < count_i or budget[uid] <= 0
                or (eos_token_id is not None and new
                    and new[-1] == eos_token_id)):
            self._spec_state.pop(uid, None)
            return True
        seq.seen_tokens = c + len(new)
        self._spec_state[uid] = {"dk": dk, "dv": dv, "dix": put(dci),
                                 "pend": put(pend), "pl": put(pl),
                                 "c": c + len(new)}
        return False

    # ------------------------------------------------------------ scheduling
    def can_schedule(self, uids: Sequence[int], lengths: Sequence[int]) -> bool:
        """Reference `can_schedule:184`: slot AND (paged) physical-block
        availability."""
        new_uids = [u for u in uids if not self.state_manager.known_sequence(u)]
        if len(new_uids) > self.state_manager.allocator.free_blocks or \
                any(l > self.max_seq_len for l in lengths):
            return False
        if self.kv_layout == "paged":
            need = sum(self.state_manager.blocks_for(l)
                       for u, l in zip(uids, lengths)
                       if not self.state_manager.known_sequence(u))
            return need <= self.state_manager.block_allocator.free_blocks
        return True

    def _count_slots(self, slots: int, fed: int, fields=None) -> None:
        """Token slots (rows x positions) the dispatched program computes
        against the tokens it was fed: into the lifetime counters always,
        onto the round's span when there is one."""
        self.serving_counters["token_slots_computed"] += slots
        self.serving_counters["tokens_fed"] += fed
        if fields is not None:
            fields["token_slots"] = slots
            fields["tokens_fed"] = fed

    def _count_rows(self, rows: int, live: int, fields=None) -> None:
        """Rows of the dispatched program (decode rows plus chunk rows)
        against those whose cursor stands below capacity; the others are
        parked, and the paged attention kernels skip them. Counted like
        `_count_slots`."""
        self.serving_counters["rows_parked"] += rows - live
        if fields is not None:
            fields["rows_live"] = live
            fields["rows_parked"] = rows - live

    def _count_blocks(self, fields=None, steps: int = 1) -> None:
        """Pool blocks that hold tokens under the decode rows of the
        dispatched program, one count a (row, block) pair and decode step,
        against the entries of the block table (rows x T): the paged decode
        kernel walks the former. From the host's mirror of the cursors (a
        wave's rows advance a token a step); counted like `_count_slots`."""
        if self.kv_layout != "paged":
            return
        blocks_for = self.state_manager.blocks_for
        live = sum(blocks_for(seq.seen_tokens + i)
                   for seq in self.state_manager.tracked_sequences.values()
                   if self._unparked[seq.slot] for i in range(steps))
        table = steps * self._tables_np.size
        self.serving_counters["kv_blocks_live"] += live
        self.serving_counters["kv_blocks_table"] += table
        if fields is not None:
            fields["kv_blocks_live"] = live
            fields["kv_blocks_table"] = table

    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[np.ndarray],
            argmax_only: bool = False) -> Dict[int, np.ndarray]:
        """Schedule tokens for each uid (reference `put:107`): prompts for
        unknown uids (prefill), single continuation tokens for known ones
        (batched decode), multi-token feeds for known ones (prefill
        continuation). One scheduling ROUND per call, the chunks riding the
        same compiled step as this call's decode rows (dynamic split-fuse).
        Paged, a round computes `width x split_fuse_chunk` token slots for
        its chunks, `width` the narrowest of `chunk_row_widths(max_batch)`
        that holds the sequences mid-prefill (fed this call or earlier) a
        row each, plus `max_batch` decode rows when any row decodes or the
        width is a narrow one; the first such round compiles every width.
        The width is a token budget, and it is FILLED (the reference's
        Dynamic SplitFuse): every sequence mid-prefill advances by one
        chunk of `split_fuse_chunk` tokens, in admission order, so none
        starves; the rows left over go to the same sequences in the same
        order, each taking the further chunks it still has pending. What a
        decode row can be held up by is one round of the width's fixed
        size, however long the prompts beside it. (The slot layout has no
        batched program: ONE chunk a sequence a round, a program each.)
        Returns
        next-token logits only for uids that produced one this round (a
        decode, or a prompt whose LAST chunk ran); keep calling put (with or
        without new tokens) to drain the rest.

        Traced (`tracer.active`, read once here): depth-0 spans `schedule`
        (validation, admission, prefix match, the decode batch) and
        `prefill` / `chunk` / `decode`, whose children `feeds`, `sync`,
        `dispatch`, `fetch`, `commit` follow each other; every record carries
        this round's number. Untraced, a round makes no record, allocates
        no field dict and reads no clock."""
        # BEFORE any mutation (like the validation loop below): a fault
        # retried by the caller must see un-admitted uids, not half-state
        fault_point("generate_dispatch", label="v2_put")
        tr = self.tracer
        self.serving_counters["rounds"] += 1
        if not tr.active:
            return self._put(batch_uids, batch_tokens, argmax_only, tr, False)
        tr.round = self.serving_counters["rounds"]
        try:
            return self._put(batch_uids, batch_tokens, argmax_only, tr, True)
        finally:
            tr.round = None

    def _put(self, batch_uids, batch_tokens, argmax_only, tr, on):
        out: Dict[int, np.ndarray] = {}
        decode_uids: List[int] = []
        # argmax_only (the serving loop): reduce every result ON DEVICE and
        # fetch token ids, not (., V) logits — V floats per row per round
        # is the largest transfer of the serving loop otherwise. With a
        # sampling config set, the reduce is an
        # on-device categorical draw instead of argmax.
        if argmax_only and self._sample_cfg and self._sample_cfg[0] != 0.0:
            skey = ("sample", self._sample_cfg)
            if skey not in self._jits:
                from deepspeed_tpu.ops.sampling import sample_logits
                cfg = self._sample_cfg
                self._jits[skey] = jax.jit(
                    lambda x, r, f: sample_logits(x, r, *cfg, row_fold=f))
            sampler = self._jits[skey]

            def _mat(x, fold=None):
                self._rng, sub = jax.random.split(self._rng)
                if fold is None:
                    from deepspeed_tpu.ops.sampling import sample_logits \
                        as _sl
                    return np.asarray(_sl(x, sub, *self._sample_cfg))
                fold = np.asarray(fold, np.int32)
                if fold.shape[0] != x.shape[0]:
                    # programs pad rows to a bucket; rows past the real
                    # count are discarded by the caller — fold zeros there
                    padded = np.zeros((x.shape[0],), np.int32)
                    padded[:fold.shape[0]] = fold[:x.shape[0]]
                    fold = padded
                return np.asarray(sampler(x, sub, jnp.asarray(fold)))
        else:
            _g = ((lambda x: np.asarray(jnp.argmax(x, axis=-1)))
                  if argmax_only else (lambda x: np.asarray(x)))

            def _mat(x, fold=None):
                return _g(x)

        # the children of a round's span; no-ops while the tracer is off
        phase = tr.phase if on else (lambda name: None)

        def dispatch(fn, *args, family=False):
            """The `dispatch` child: feeds to the device and the call of the
            compiled program (which returns before the device has run it).
            `family`: a batched chunk program, the first dispatch of which
            compiles them all (`_warm_chunk_family`)."""
            f = phase("dispatch")
            n0 = compile_totals()[0] if on else 0
            if family and not self._chunk_family_warm:
                self._warm_chunk_family(
                    lambda x: _mat(x, np.zeros((x.shape[0],), np.int32)))
            res = fn(self.params, self.cache, *[jnp.asarray(a) for a in args])
            if on:
                f["program"] = fn._ds_program
                f["compiled"] = compile_totals()[0] != n0
            return res

        def sync():
            """The `sync` child around `_maybe_sync_tables`."""
            f = phase("sync")
            dirty, cow = self._maybe_sync_tables()
            if on:
                f["dirty"], f["cow_copies"] = dirty, cow

        with tr.span("schedule") if on else _OFF:
            # Validate the WHOLE batch before any mutation: raising mid-loop
            # would leave earlier uids half-admitted (slot consumed, no
            # compute ran) and a retry would misread them as continuation
            # feeds.
            cap = min(self.max_seq_len, self.cache.max_len)
            for uid, toks in zip(batch_uids, batch_tokens):
                n = np.asarray(toks, np.int32).reshape(-1).shape[0]
                if self.state_manager.known_sequence(uid):
                    seq = self.state_manager.get_sequence(uid)
                    # pending holds admitted-but-unprocessed prompt chunks —
                    # they WILL occupy cache rows, so a continuation fed
                    # while a chunked prefill drains must count them or it
                    # can still run past capacity into the silent
                    # drop-write region
                    seen = seq.seen_tokens + len(seq.pending)
                else:
                    seen = 0
                if seen + n > cap:
                    # cache writes past the row capacity DROP
                    # (bucketed-padding protection) — feeding past it would
                    # silently corrupt the sequence's KV, so refuse loudly
                    # at the serving boundary (paged rounds cache.max_len UP
                    # to block granularity, so the user-facing max_seq_len
                    # is the binding limit)
                    raise ValueError(
                        f"sequence {uid} would reach {seen + n} tokens "
                        f"but max_seq_len={cap} — raise max_seq_len or "
                        "shorten the prompt/generation budget")
            new_short: List[Any] = []
            for uid, toks in zip(batch_uids, batch_tokens):
                toks = np.asarray(toks, np.int32).reshape(-1)
                if not self.state_manager.known_sequence(uid):
                    seq = self.state_manager.get_or_create_sequence(uid)
                    self._slot_uids[seq.slot] = _uid_fold(uid)
                    if on:
                        tr.begin_request(uid, prompt_tokens=len(toks),
                                         slot=seq.slot)
                    seq.tokens = list(map(int, toks))
                    matched = self._match_prefix(seq, toks)
                    if matched:
                        if on:
                            tr.note(uid, prefix_matched=matched)
                        # shared blocks cover the prefix; only the remainder
                        # runs — through the CHUNK path (its programs take a
                        # start cursor; the single-shot prefill assumes 0)
                        seq.pending = list(map(int, toks[matched:]))
                    elif len(toks) <= self.split_fuse_chunk:
                        new_short.append((uid, seq, toks))
                    else:
                        seq.pending = list(map(int, toks))
                else:
                    seq = self.state_manager.get_sequence(uid)
                    if len(toks) == 0:
                        raise ValueError(
                            f"put got an empty token list for known uid "
                            f"{uid} — a decode feed is exactly one token, a "
                            "prefill continuation at least one")
                    seq.tokens.extend(map(int, toks))
                    if len(toks) == 1 and not seq.pending:
                        decode_uids.append(uid)
                    else:  # prefill continuation feed (FastGen ragged
                        seq.pending.extend(map(int, toks))   # semantics)
        # Short prompts: a LONE one takes the single-shot bucketed prefill
        # (cheapest); SEVERAL arriving together go through the batched
        # chunk program instead — N joins cost one dispatch, not N
        # (reference ragged batching).
        def single_prefill(uid, seq, toks):
            sp = _bucket(len(toks))
            with (tr.span("prefill", uids=(uid,), bucket=sp,
                          tokens=len(toks)) if on else _OFF) as pf:
                phase("feeds")
                ids = np.zeros((1, sp), np.int32)
                ids[0, :len(toks)] = toks
                fn = self._prefill_fn(sp)
                self._reserve(seq, len(toks))
                self._count_slots(sp, len(toks), pf)
                sync()
                self.cache, last = dispatch(
                    fn, ids, np.asarray(seq.slot, np.int32),
                    np.asarray(len(toks), np.int32))
                phase("fetch")
                got = _mat(last, np.asarray([_uid_fold(uid)], np.int32)
                           if getattr(last, "ndim", 1) == 2 else None)
                phase("commit")
                seq.seen_tokens = len(toks)
                self._unparked[seq.slot] = True
                self._commit_prefix(seq)
                out[uid] = got

        lone_short = len(new_short) == 1 and (
            self.kv_layout != "paged" or not any(
                s.pending for s in
                self.state_manager.tracked_sequences.values()))
        if lone_short:
            single_prefill(*new_short[0])
        elif new_short:
            if self.kv_layout == "paged":
                for uid, seq, toks in new_short:
                    seq.pending = list(map(int, toks))
            else:  # slot layout has no batched chunk program
                for uid, seq, toks in new_short:
                    single_prefill(uid, seq, toks)
        with tr.span("schedule") if on else _OFF:
            # every mid-prefill sequence is in this round, whether its
            # tokens arrived in this call or an earlier one
            chunk_uids = [uid for uid, seq in
                          self.state_manager.tracked_sequences.items()
                          if seq.pending]

            # Build this put's decode batch once; it runs fused with the
            # FIRST chunk if any prompt is mid-prefill.
            tokens = np.zeros((self.max_batch, 1), np.int32)
            active = np.zeros((self.max_batch,), bool)
            for uid in decode_uids:
                seq = self.state_manager.get_sequence(uid)
                tokens[seq.slot, 0] = seq.tokens[-1]
                active[seq.slot] = True
                self._reserve(seq, seq.seen_tokens + 1)

        ran_decode = not decode_uids
        csz = self.split_fuse_chunk
        if chunk_uids and self.kv_layout == "paged":
            # Batched split-fuse: EVERY pending chunk rides one compiled
            # step (plus the decode rows, when any) — N joining prompts no
            # longer serialize (reference ragged_wrapper's mixed batch).
            # The chunk half is as wide as the prompts that are prefilling
            # (the narrowest compiled width that holds them), not as wide
            # as max_batch, and every row of it rides every matmul of the
            # round, so the rows are FILLED (the reference's token budget,
            # `RaggedBatchWrapper`): each prompt takes one, in admission
            # order, and the rows left over go to the same prompts in the
            # same order, each taking the further chunks it still has
            # pending. Only `fused_batch` has narrow widths, so a narrow
            # round rides it even with no row to decode.
            seqs = [self.state_manager.get_sequence(uid)
                    for uid in chunk_uids[:self.max_batch]]
            R = width_for(len(seqs), self.max_batch)
            spare = R - len(seqs)
            plan = []     # (sequence, its first row, its rows, its tokens)
            n_rows = 0
            for seq in seqs:
                take = 1 + min(spare, -(-len(seq.pending) // csz) - 1)
                plan.append((seq, n_rows, take,
                             min(take * csz, len(seq.pending))))
                spare -= take - 1
                n_rows += take
            fused = (not ran_decode and bool(decode_uids)
                     or R < self.max_batch)
            span_uids = tuple(s.uid for s in seqs) + (
                tuple(decode_uids) if fused else ())
            with (tr.span("chunk", uids=span_uids, fused=fused, rows=n_rows,
                          sequences=len(seqs), width=R)
                  if on else _OFF) as cf:
                phase("feeds")
                ids, slots, starts, valids = self._parked_rows(R)
                folds = np.zeros((n_rows,), np.int32)
                for seq, i, take, n in plan:
                    ids[i:i + take].reshape(-1)[:n] = seq.pending[:n]
                    # all but the sequence's last row leave the cursor alone
                    slots[i:i + take] = ~seq.slot
                    slots[i + take - 1] = seq.slot
                    starts[i:i + take] = seq.seen_tokens + csz * np.arange(take)
                    valids[i:i + take] = csz
                    valids[i + take - 1] = n - csz * (take - 1)
                    folds[i:i + take] = _uid_fold(seq.uid)
                    self._reserve(seq, seq.seen_tokens + n)
                self.serving_counters["rows_refilled"] += n_rows - len(seqs)
                fed = sum(n for _, _, _, n in plan)
                if fused:
                    self._count_slots(R * csz + self.max_batch,
                                      fed + len(decode_uids), cf)
                    # the decode half runs first: a row admitted this round
                    # is still parked in it
                    self._count_rows(R + self.max_batch,
                                     n_rows + int(self._unparked.sum()), cf)
                    self._count_blocks(cf)
                else:
                    self._count_slots(R * csz, fed, cf)
                    self._count_rows(R, n_rows, cf)
                sync()
                if fused:
                    self.cache, logits, last = dispatch(
                        self._fused_batch_fn(R), tokens, active, ids, slots,
                        starts, valids, family=True)
                    phase("fetch")
                    if decode_uids:
                        logits_np = _mat(logits, self._slot_uids)
                    last_np = _mat(last, folds)
                    phase("commit")
                    for duid in decode_uids:
                        dseq = self.state_manager.get_sequence(duid)
                        dseq.seen_tokens += 1
                        out[duid] = logits_np[dseq.slot]
                    ran_decode = True
                else:
                    self.cache, last = dispatch(
                        self._chunk_batch_fn(), ids, slots, starts, valids,
                        family=True)
                    phase("fetch")
                    last_np = _mat(last, folds)
                    phase("commit")
                for seq, i, take, n in plan:
                    seq.pending = seq.pending[n:]
                    seq.seen_tokens += n
                    self._unparked[seq.slot] = True
                    if not seq.pending:  # final chunk → next-token logits
                        self._commit_prefix(seq)
                        out[seq.uid] = last_np[i + take - 1]
            chunk_uids = chunk_uids[self.max_batch:]
        for uid in chunk_uids:  # slot layout: ONE chunk each this round
            fused = not ran_decode and bool(decode_uids)
            with (tr.span("chunk", uids=(uid,) + (tuple(decode_uids)
                                                  if fused else ()),
                          fused=fused, rows=1) if on else _OFF) as cf:
                phase("feeds")
                seq = self.state_manager.get_sequence(uid)
                piece = seq.pending[:csz]
                ids = np.zeros((1, csz), np.int32)
                ids[0, :len(piece)] = piece
                self._reserve(seq, seq.seen_tokens + len(piece))
                if fused:
                    self._count_slots(csz + self.max_batch,
                                      len(piece) + len(decode_uids), cf)
                    self._count_rows(1 + self.max_batch,
                                     1 + int(self._unparked.sum()), cf)
                    self._count_blocks(cf)
                else:
                    self._count_slots(csz, len(piece), cf)
                    self._count_rows(1, 1, cf)
                sync()
                row = (ids, np.asarray(seq.slot, np.int32),
                       np.asarray(seq.seen_tokens, np.int32),
                       np.asarray(len(piece), np.int32))
                if fused:
                    self.cache, logits, last = dispatch(
                        self._fused_fn(), tokens, active, *row)
                    phase("fetch")
                    logits_np = _mat(logits, self._slot_uids)
                else:
                    self.cache, last = dispatch(self._chunk_fn(), *row)
                    phase("fetch")
                last_np = None
                if len(seq.pending) <= len(piece):  # final chunk
                    last_np = _mat(last,
                                   np.asarray([_uid_fold(uid)], np.int32)
                                   if getattr(last, "ndim", 1) == 2
                                   else None)
                phase("commit")
                if fused:
                    for duid in decode_uids:
                        dseq = self.state_manager.get_sequence(duid)
                        dseq.seen_tokens += 1
                        out[duid] = logits_np[dseq.slot]
                    ran_decode = True
                seq.pending = seq.pending[len(piece):]
                seq.seen_tokens += len(piece)
                self._unparked[seq.slot] = True
                if not seq.pending:  # final chunk → next-token logits
                    self._commit_prefix(seq)
                    out[uid] = last_np

        if not ran_decode:
            st0 = self._stall_total()
            with (tr.span("decode", uids=tuple(decode_uids))
                  if on else _OFF) as df:
                phase("feeds")
                fn = self._decode_fn()
                self._count_slots(self.max_batch, len(decode_uids), df)
                self._count_rows(self.max_batch, int(self._unparked.sum()),
                                 df)
                self._count_blocks(df)
                sync()
                self.cache, logits = dispatch(fn, tokens, active)
                phase("fetch")
                logits_np = _mat(logits, self._slot_uids)
                phase("commit")
                for uid in decode_uids:
                    seq = self.state_manager.get_sequence(uid)
                    seq.seen_tokens += 1
                    out[uid] = logits_np[seq.slot]
                stall = self._stall_total() - st0
                if stall and on:
                    df["prefetch_stall_ms"] = round(stall, 3)
        return out

    def flush(self, uid: int) -> None:
        """Release a sequence's slot — and, paged, its physical blocks —
        (reference `flush:205`). Parks the cursor at max_len so the row is
        inert until reused."""
        self._flush_batch([uid])

    def _flush_batch(self, uids: Sequence[int]) -> None:
        """Park several finished rows with ONE device op. A per-uid eager
        `index.at[slot].set` costs a device dispatch each, serialized
        behind the previous one."""
        if not uids:
            return
        tr = self.tracer
        ended = []  # (uid, total_tokens); closed AFTER the flush span so
        #             the request's own flush time lands in its window
        with tr.span("flush", uids=tuple(uids)):
            # rows being retired still count — stamp the peak pre-release
            self._kv_util_peak = max(self._kv_util_peak,
                                     self.kv_utilization())
            self.serving_counters["flushed_sequences"] += len(uids)
            slots = []
            for uid in uids:
                seq = self.state_manager.get_sequence(uid)
                slots.append(seq.slot)
                self._unparked[seq.slot] = False
                ended.append((uid, len(seq.tokens)))
                if self.kv_layout == "paged":
                    self._tables_np[seq.slot] = -1
                    self._tables_dirty = True
                self.state_manager.flush_sequence(uid)
                self._spec_state.pop(uid, None)  # draft cache dies with row
            # fixed (max_batch,) shape with drop-mode sentinels: an eager
            # scatter compiles per distinct index-vector LENGTH (~1.5 s on
            # v5e)
            slots_np = np.full((self.max_batch,), self.max_batch, np.int32)
            slots_np[:len(slots)] = slots
            self.cache = self.cache.replace(
                index=self.cache.index.at[jnp.asarray(slots_np)].set(
                    self.cache.max_len, mode="drop"))
        for uid, total in ended:
            tr.end_request(uid, total_tokens=total,
                           serve_mode=self.serve_mode)

    # ------------------------------------------------------------ serving loop
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 64,
                 eos_token_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0) -> List[List[int]]:
        """Continuous-batching loop: admits prompts as slots free up,
        decodes every live sequence each step (the FastGen serving loop in
        miniature). Greedy by default; `temperature` > 0 switches every
        decode (scan steps AND mixed-phase reduces) to on-device
        temperature/top-k/top-p sampling seeded by `seed`.

        COMPILE/RUNTIME-stage OOM degradation (the placement stage lives in
        `_place_with_recovery`): a RESOURCE_EXHAUSTED raised while the
        serving programs compile or run steps the engine down the r9
        ladder (dequant → layer_scan → capacity) and RERUNS the whole call
        — `_degrade_to` rebuilt the cache/state manager, so the retry
        re-prefills from scratch (put()-level in-flight state does not
        survive a degrade; generate() owns its full input so it can)."""
        self._sample_cfg = ((float(temperature), int(top_k), float(top_p))
                            if temperature and temperature > 0.0 else None)
        self._rng = jax.random.PRNGKey(seed)
        try:
            return self._generate(prompts, max_new_tokens, eos_token_id)
        except Exception as e:
            if not (self._degrade_enabled() and is_oom_error(e)):
                raise
            nxt = self._degraded_mode(self.serve_mode, self.params)
            if nxt is None:
                raise
            from deepspeed_tpu.inference.serve_modes import note_degraded
            note_degraded("v2", self.serve_mode, nxt, stage="compile",
                          reason=e)
        finally:
            # don't leak the sampling config into later direct put() calls
            self._sample_cfg = None
        # kwargs evaluate BEFORE the rebuild, so from_mode is the OOMed rung;
        # open request traces ride through (begin_request is idempotent on
        # the retry — their admit stamps survive the engine rebuild)
        with self.tracer.span("degrade",
                              uids=tuple(self.tracer.open_uids()),
                              from_mode=self.serve_mode, to_mode=nxt,
                              stage="compile"):
            self._degrade_to(nxt)
        return self.generate(prompts, max_new_tokens=max_new_tokens,
                             eos_token_id=eos_token_id,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, seed=seed)

    def _generate(self, prompts, max_new_tokens, eos_token_id):
        cap = min(self.max_seq_len, self.cache.max_len)
        for p in prompts:
            if len(p) + 1 > cap:
                raise ValueError(
                    f"prompt of {len(p)} tokens leaves no room to generate "
                    f"within max_seq_len={cap} — KV writes past the row "
                    "capacity would silently drop recent context")
        if any(len(p) + max_new_tokens > cap for p in prompts):
            # HF-generate semantics: generation stops at the row capacity
            # (running past it would drop the NEW tokens' KV — the model
            # would stop seeing its own recent output, silently degrading)
            logger.warning(
                "max_new_tokens=%d clamped to max_seq_len=%d for %d "
                "prompt(s)", max_new_tokens, cap,
                sum(len(p) + max_new_tokens > cap for p in prompts))
        pending = list(enumerate(prompts))
        results: Dict[int, List[int]] = {}
        budget: Dict[int, int] = {}
        live: List[int] = []
        prefilling: set = set()
        # Per-query service timestamps (the FastGen effective-throughput
        # accounting, blogs/deepspeed-fastgen/README.md:163 — SLA checks
        # need first-token latency + generation rate per query). Tokens
        # are stamped when they MATERIALIZE on the host (wave end for
        # scan-decoded tokens) — honest availability, not emission.
        t_start = time.perf_counter()
        timing: Dict[int, Dict[str, float]] = {}
        plen: Dict[int, int] = {}

        def _stamp(retired_uids=()):
            now = time.perf_counter() - t_start
            for u, rec in timing.items():
                if "first" not in rec and len(results[u]) > plen[u]:
                    rec["first"] = now
                    # the tracer's clock, not `now` — same materialization
                    # instant, independent epoch (retired-in-first-wave uids
                    # already closed; end_request's first=done covers them)
                    self.tracer.first_token(u)
            for u in retired_uids:
                timing[u]["done"] = now
                timing[u]["new_tokens"] = len(results[u]) - plen[u]
        self.last_timing = timing

        while pending or live:
            step_uids = [u for u in live if u not in prefilling]
            step_tokens: List[List[int]] = [[results[u][-1]] for u in step_uids]
            # Admit new prompts INTO this step — a long prompt prefills a
            # round's chunk rows per step, fused with the live rows' decode
            # (split-fuse), so ongoing generation never stalls for more than
            # one round of the width's fixed size.
            admitted: List[int] = []  # filled DURING the span body — the
            # tracer snapshots uids at span exit, so late appends count
            adm_cm = (self.tracer.span("admit", uids=admitted)
                      if pending
                      and self.state_manager.allocator.free_blocks > 0
                      else nullcontext())
            with adm_cm:
                while pending and \
                        self.state_manager.allocator.free_blocks > 0:
                    if self.kv_layout == "paged":
                        worst = self.state_manager.blocks_for(min(
                            len(pending[0][1]) + max_new_tokens,
                            self.cache.max_len))
                        pool = self.state_manager.block_allocator
                        if worst > pool.num_blocks:
                            raise ValueError(
                                f"prompt needs {worst} KV blocks worst-case"
                                f" but the pool only has {pool.num_blocks}"
                                " — raise num_cache_blocks or shorten the "
                                "prompt/generation budget")
                        if worst > pool.free_blocks:
                            break  # not enough physical blocks; retry later
                    uid, prompt = pending.pop(0)
                    # reserve the slot AND prepay the sequence's worst-case
                    # block footprint (prompt + generation budget) now —
                    # later admissions see the true free count and an
                    # admitted sequence never hits pool exhaustion mid-
                    # decode
                    seq_new = self.state_manager.get_or_create_sequence(uid)
                    self._slot_uids[seq_new.slot] = _uid_fold(uid)
                    self.tracer.begin_request(uid,
                                              prompt_tokens=len(prompt),
                                              slot=seq_new.slot)
                    admitted.append(uid)
                    matched = self._match_prefix(seq_new,
                                                 list(map(int, prompt)))
                    self._reserve(seq_new, len(prompt) + max_new_tokens)
                    if matched:
                        # shared blocks cover the prefix; only the
                        # remainder prefills — put() drains seq.pending
                        # chunk by chunk from the matched cursor
                        self.tracer.note(uid, prefix_matched=matched)
                        seq_new.tokens = list(map(int, prompt))
                        seq_new.pending = seq_new.tokens[matched:]
                    else:
                        step_uids.append(uid)
                        step_tokens.append(list(map(int, prompt)))
                    results[uid] = list(map(int, prompt))
                    timing[uid] = {"admit": time.perf_counter() - t_start}
                    plen[uid] = len(prompt)
                    budget[uid] = min(max_new_tokens,
                                      self.max_seq_len - len(prompt),
                                      self.cache.max_len - len(prompt))
                    live.append(uid)
                    prefilling.add(uid)
            # Speculative rounds serve the SINGLE-sequence pure-decode
            # bucket (draft-and-verify, k+1 tokens per target dispatch);
            # ragged batches conflict with spec's per-row acceptance
            # raggedness and fall back loudly to vanilla waves.
            if self._spec_enabled and live and not prefilling:
                if len(live) > 1:
                    warn_once(("v2_spec", "ragged"),
                              "v2 speculative decoding serves single-"
                              "sequence buckets only — rows of a ragged "
                              "decode batch accept different draft counts "
                              "per round; serving vanilla decode waves")
                else:
                    uid = live[0]
                    seq = self.state_manager.get_sequence(uid)
                    if seq.seen_tokens + self._spec_k + 1 \
                            <= self.cache.max_len:
                        acc0 = self.serving_counters["spec_accepted_tokens"]
                        with self.tracer.span("spec_round",
                                              uids=(uid,)) as sf:
                            spec_done = self._spec_round(
                                uid, seq, results, budget, eos_token_id)
                            sf["drafted"] = self._spec_k
                            sf["accepted"] = (
                                self.serving_counters["spec_accepted_tokens"]
                                - acc0)
                        if spec_done:
                            live.remove(uid)
                            self._flush_batch([uid])
                            _stamp([uid])
                        else:
                            _stamp()
                        continue
                    # no room for the k+1 verify window: the vanilla wave
                    # below drains the tail of the row's capacity
            # Pure-decode phase: run K greedy steps in one compiled dispatch
            # (dispatch latency amortization; exact greedy semantics —
            # overshoot past eos is trimmed, the row is flushed right
            # after). Queued prompts don't block this: the admission loop
            # above already admitted everything admissible, so remaining
            # `pending` is waiting for a slot/blocks that only a completing
            # row can free.
            if live and not prefilling:
                k = min(64, min(budget[u] for u in live))
                if k < 64 and any(budget[u] != k for u in live):
                    # ragged budgets: pow2 floor bounds compiled variants
                    k = 1 << (k.bit_length() - 1)
                # else: uniform budget (the common serving config) — ONE
                # exact-K scan per wave instead of a log2 ladder of
                # dispatches
            else:
                k = 1
            if k > 1:
                st0 = self._stall_total()
                with self.tracer.span(
                        "decode_wave", uids=tuple(live), k=k,
                        wave=self.serving_counters["decode_waves"],
                        occupancy=len(live)) as wf:
                    tokens = np.zeros((self.max_batch, 1), np.int32)
                    active = np.zeros((self.max_batch,), bool)
                    for uid in live:
                        seq = self.state_manager.get_sequence(uid)
                        tokens[seq.slot, 0] = results[uid][-1]
                        active[seq.slot] = True
                        self._reserve(seq, seq.seen_tokens + k)
                    self._maybe_sync_tables()
                    self._rng, sub = jax.random.split(self._rng)
                    self.cache, toks = self._decode_scan_fn(k)(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(active), sub,
                        jnp.asarray(self._slot_uids, jnp.int32))
                    toks_np = np.asarray(toks)  # (K, B)
                    self._count_slots(k * self.max_batch, k * len(live), wf)
                    self._count_rows(k * self.max_batch,
                                     k * int(self._unparked.sum()), wf)
                    self._count_blocks(wf, steps=k)
                    self.serving_counters["decode_waves"] += 1
                    retired = []
                    for uid in list(live):
                        seq = self.state_manager.get_sequence(uid)
                        new = [int(t) for t in toks_np[:, seq.slot]]
                        if eos_token_id is not None and eos_token_id in new:
                            new = new[:new.index(eos_token_id) + 1]
                        seq.seen_tokens += k
                        seq.tokens.extend(new)
                        results[uid].extend(new)
                        self.serving_counters["generated_tokens"] += len(new)
                        budget[uid] -= len(new)
                        if budget[uid] <= 0 or (
                                eos_token_id is not None and new
                                and new[-1] == eos_token_id):
                            retired.append(uid)
                            live.remove(uid)
                    stall = self._stall_total() - st0
                    if stall:
                        wf["prefetch_stall_ms"] = round(stall, 3)
                self._flush_batch(retired)
                _stamp(retired)
                continue
            # mixed phase: per-token put (split-fuse prefill + decode);
            # token ids reduced on device (argmax_only) — the full (B, V)
            # logits never cross to the host per round
            st0 = self._stall_total()
            # uids=live, not step_uids: prefix-matched prompts drain their
            # pending chunks inside this put() without appearing in
            # step_uids — their time is THIS round, not "_other"
            with self.tracer.span("mixed_round", uids=tuple(live),
                                  round=self.serving_counters[
                                      "mixed_rounds"]) as mf:
                outs = self.put(step_uids, step_tokens, argmax_only=True)
                self.serving_counters["mixed_rounds"] += 1
                retired = []
                for uid in list(live):
                    if uid not in outs:
                        continue  # still mid-prefill; later rounds drain
                    prefilling.discard(uid)
                    nxt = int(outs[uid])
                    results[uid].append(nxt)
                    self.serving_counters["generated_tokens"] += 1
                    budget[uid] -= 1
                    done = budget[uid] <= 0 or (eos_token_id is not None and
                                                nxt == eos_token_id)
                    if done:
                        retired.append(uid)
                        live.remove(uid)
                stall = self._stall_total() - st0
                if stall:
                    mf["prefetch_stall_ms"] = round(stall, 3)
            self._flush_batch(retired)
            _stamp(retired)
        hub = get_hub()
        if hub.enabled:
            hub.emit("serving", engine="v2", **self.telemetry_snapshot())
            for hname in ("ttft_s", "tpot_s", "e2e_s"):
                hub.histogram_event(hname)
        return [results[i] for i in range(len(prompts))]

    def warmup(self, buckets: Sequence[int] = (32, 64, 128),
               max_new_tokens: int = 4, seed: int = 0) -> Dict[str, Any]:
        """Compile-and-pin pass over the bucketed program family: one tiny
        generate() per DISTINCT prompt bucket resolves the AUTO param
        layouts on the FIRST jitted dispatch (`_pin_param_layouts` —
        pin-once for the whole family), compiles the bucket's
        prefill/decode programs and registers their names with the
        RecompileDetector. Serving real prompts in
        these buckets afterwards (same max_new_tokens → same decode-scan
        key) reports ZERO detector misses — the acceptance check
        tests/unit/inference/test_fastgen_v2_modes.py pins. Buckets that
        don't fit the row capacity are skipped. Returns
        `telemetry_snapshot()`."""
        rng = np.random.RandomState(seed)
        vocab = int(self.model_cfg.vocab_size)
        cap = min(self.max_seq_len, self.cache.max_len)
        seen = set()
        for b in buckets:
            n = int(b)
            if n + max_new_tokens > cap or _bucket(n) in seen:
                continue
            seen.add(_bucket(n))
            prompt = rng.randint(1, vocab, size=(n,)).tolist()
            self.generate([prompt], max_new_tokens=max_new_tokens,
                          seed=seed)
        return self.telemetry_snapshot()
