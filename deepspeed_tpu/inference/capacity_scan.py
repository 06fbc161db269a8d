"""ZeRO-Inference capacity serve mode — layer-streamed decode with
double-buffered host→HBM prefetch (models LARGER than device memory).

An r5 probe (since deleted) measured outcome (b): XLA
will NOT auto-stage `pinned_host` params into compute ("memory_space of
all inputs passed to `gather` must be the same"), and even *slicing* a
host-memory-space jax Array enters compute with a host operand. So the
host tier here is plain host arrays (numpy — host RAM; on TPU the runtime
stages them through its pinned transfer buffer), and the staging is an
EXPLICIT `jax.device_put` of one layer's slice tree, driven by a host-side
layer loop over the shared `make_block_fn` block body (the same program
the resident layer-scan engine runs inside `lax.scan`, so parity is exact
by construction).

Double buffering: the transfer of layer *l+1* is dispatched BEFORE layer
*l*'s (already prefetched) slice is awaited and its block dispatched —
H2D DMA for the next layer overlaps the current layer's compute, so
steady-state decode runs at the PCIe-bandwidth bound instead of
stall-then-compute. The loop then awaits layer *l−1*'s block OUTPUT,
which throttles the host to device pace and bounds live slices to ~2:

    HBM peak ≈ resident (embed/norm/head) + 2·layer_slice + KV + workspace

(`CapacityPlan.peak_hbm_bytes` — asserted by the unit tests). Tiers:

  HBM   : embed_tokens / final norm / lm_head (read every step, small)
  host  : per-layer slices of every `layers` leaf, optionally
          int8-quantized via `quantize_layer_stacks` (halves PCIe bytes;
          the fused dequant-GEMM kernel then consumes int8 directly)
  NVMe  : the coldest `nvme_layers` layers ride the striped aio engine
          (`runtime/swap_tensor.AsyncTensorSwapper`) — disk reads for
          layer l+1 are queued right after its predecessor's H2D so the
          read overlaps compute too.

Scope: llama-layout trees (`layer_scan_supported`) on a single-device
mesh, exactly like the resident layer scan. Engine entry:
`init_inference(..., serve_mode="capacity", capacity={...})`; the `auto`
rule picks capacity when not even the int8 tree + KV + workspace fits
(docs/capacity_serving.md).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.inference.quantization import is_quantized_leaf
from deepspeed_tpu.resilience.faults import _emit_event, fault_point
from deepspeed_tpu.resilience.retry import Deadline, retry_call, watchdog_await
from deepspeed_tpu.telemetry.memory import get_plane, owner_for
from deepspeed_tpu.utils.logging import logger, warn_once


# ---------------------------------------------------------------- accounting
def round_up_len(n: int) -> int:
    """Cache-length rounding shared with the generate programs."""
    return -(-int(n) // 128) * 128


def _model_dims(model_cfg) -> Dict[str, int]:
    """(L, Hkv, D, hidden, inter, vocab) duck-typed over zoo config naming."""
    from deepspeed_tpu.inference.engine import _cache_dims
    layers, hkv, hd = _cache_dims(model_cfg)
    hidden = (getattr(model_cfg, "hidden_size", None)
              or getattr(model_cfg, "n_embd"))
    inter = (getattr(model_cfg, "intermediate_size", None) or 4 * hidden)
    vocab = getattr(model_cfg, "vocab_size")
    return {"layers": layers, "kv_heads": hkv, "head_dim": hd,
            "hidden": int(hidden), "inter": int(inter), "vocab": int(vocab)}


def kv_cache_bytes(model_cfg, batch: int, max_len: int, dtype,
                   kv_dtype: Optional[str] = None) -> int:
    """K + V cache bytes for a (batch, max_len) generate.

    `kv_dtype` is the at-rest cache element type (`kv_cache_dtype` config
    knob): "int8" is the quantized cache — 1-byte payload plus one f32
    scale per (kv-head, token slot), a 4/head_dim relative overhead (≈3%
    at D=128; docs/kv_cache.md has the formula). None (or the serving
    dtype) uses `dtype`'s width — the pre-r8 accounting unchanged.

    A model whose layers keep K and V of different KINDS counts them itself
    (`kv_bytes_by_kind`): the sum is what is held. `index_kv_bytes` is no
    kind of K and V: it lies BESIDE them (or beside the latent rows that
    stand in their place: both kinds are then named, and summed) and is
    added to either count."""
    kinds = kv_bytes_by_kind(model_cfg, batch, max_len, dtype)
    beside = kinds.pop("index_kv_bytes", 0)
    if kinds:
        return sum(kinds.values()) + beside
    d = _model_dims(model_cfg)
    slots = 2 * d["layers"] * batch * max_len * d["kv_heads"]
    if kv_dtype in ("int8", jnp.int8):
        return slots * (d["head_dim"] + 4) + beside
    item = jnp.dtype(dtype).itemsize
    return slots * d["head_dim"] * item + beside


def kv_bytes_by_kind(model_cfg, batch: int, max_len: int,
                     dtype) -> Dict[str, int]:
    """K + V bytes by the KIND of cache that holds them (docs/kv_cache.md),
    as the model's config counts them (`model_cfg.kv_bytes_by_kind`):
    `window_kv_bytes`, rings of a window's slots whatever `max_len`,
    `shared_kv_bytes`, full-length slabs that layers without a cache of
    their own read, `full_kv_bytes`, full-length rows of the full layers of
    a model that mixes them with window layers, `latent_kv_bytes`, the latent-attention layers' one
    row a token in place of K and V a head, and `index_kv_bytes`, the one
    index key a token a layer that a learned selection keeps beside K and V
    or beside the latent rows (a model may name both, as DeepSeek-sparse).
    Empty for a model of one kind of layer: `max_len` slots a layer, all of
    it `kv_cache_bytes`."""
    own = getattr(model_cfg, "kv_bytes_by_kind", None)
    if own is None:
        return {}
    return {name: int(n) for name, n in own(batch, max_len, dtype).items()}


def recurrent_state_bytes(model_cfg, batch: int, dtype) -> int:
    """Bytes the model's recurrent layers hold for `batch` sequences
    (`kv_cache.RecurrentState`): fixed a sequence, whatever its length, and
    counted apart from K and V. 0 for a model that has no such layer; one
    that has says so itself (`model_cfg.recurrent_state_bytes`)."""
    own = getattr(model_cfg, "recurrent_state_bytes", None)
    return int(own(batch, dtype)) if own is not None else 0


def decode_workspace_bytes(model_cfg, batch: int, max_len: int, dtype) -> int:
    """Transient activation bytes one generate keeps live beside weights and
    KV: the block body's widest activations (h, normed h, and the MLP
    gate/up pair — 2·hidden + 2·inter per token position, bounded by the
    prefill width max_len) plus one fp32 logits row in sampling. The
    documented workspace term of the capacity HBM formula."""
    d = _model_dims(model_cfg)
    item = jnp.dtype(dtype).itemsize
    return (batch * max_len * (2 * d["hidden"] + 2 * d["inter"]) * item
            + batch * d["vocab"] * 4)


def _leaf_bytes(tree) -> int:
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


@dataclasses.dataclass
class CapacityPlan:
    """The placement plan's byte accounting — what the unit tests assert
    the documented HBM-peak formula against."""
    num_layers: int
    slice_bytes: int        # largest per-layer H2D slice (what streams)
    resident_bytes: int     # embed/norm/head parked in device memory
    kv_bytes: int           # for the plan's (batch, max_len) shape
    workspace_bytes: int
    host_bytes: int         # RAM tier at rest
    nvme_bytes: int         # disk tier at rest
    nvme_layers: int
    double_buffer: bool

    @property
    def peak_hbm_bytes(self) -> int:
        """resident + 2 layer slices (the one computing + the one arriving)
        + KV cache + activation workspace."""
        return (self.resident_bytes + 2 * self.slice_bytes
                + self.kv_bytes + self.workspace_bytes)


# ------------------------------------------------------- test/override hooks
# The prefetch loop's two primitives, module-level so the dispatch-ordering
# unit test can observe the exact order they are issued in.
def _transfer(host_tree, sharding):
    """Stage one layer's host slices into device memory (async dispatch)."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), host_tree)


def _await_transfer(tree) -> None:
    """Block until a staged layer slice is device-resident (the prefetch
    stall — ~0 when the transfer overlapped the previous block)."""
    jax.block_until_ready(tree)


def _await_result(tree) -> None:
    """Block until a block output is computed — the loop's throttle: it
    keeps the host from queueing the whole tree's transfers ahead of the
    device, which is what bounds live slices to ~2."""
    jax.block_until_ready(tree)


# ------------------------------------------------------------------- runner
class CapacityRunner:
    """Engine-owned capacity-mode serving state + host-driven generate.

    Owns the ONLY reference to the param tiers (the r5 residency lesson:
    a second caller-held handle keeps freed forms alive). The engine's
    `params` attribute holds `params_view()` — the same leaves, so
    fingerprinting and byte accounting see the real tree."""

    def __init__(self, model_cfg, infer_cfg, params, mesh,
                 quantized: bool = False, group_size: int = 256,
                 options: Optional[dict] = None,
                 memory_owner: Optional[str] = None):
        from deepspeed_tpu.inference.quantized_layer_scan import (
            layer_scan_supported)
        if not layer_scan_supported(params):
            raise ValueError(
                "capacity serve mode needs a llama-layout param tree "
                "(stacked layers with self_attn/mlp projections)")
        options = dict(options or {})
        self.model_cfg = model_cfg
        self.infer_cfg = infer_cfg
        self.mesh = mesh
        self.quantized = bool(quantized)
        self.double_buffer = bool(options.get("double_buffer", True))
        self._memory_owner = memory_owner or owner_for(self, "capacity")
        # resilience knobs (docs/resilience.md): engine-level defaults from
        # config.resilience, per-runner overrides via the capacity options
        res = dict(getattr(infer_cfg, "resilience", None) or {})
        self.prefetch_watchdog_s = float(options.get(
            "prefetch_watchdog_s", res.get("prefetch_watchdog_s", 30.0)) or 0)
        self.dispatch_deadline_s = options.get(
            "dispatch_deadline_s", res.get("dispatch_deadline_s"))
        self.stage_retries = int(options.get(
            "stage_retries", res.get("stage_retries", 3)))
        self._sharding = NamedSharding(mesh, P())
        self._dtype = infer_cfg.dtype
        dims = _model_dims(model_cfg)
        self.num_layers = dims["layers"]

        # mirror the resident engine's placement cast (floats → serving
        # dtype BEFORE any quantization) so int8 values — and therefore
        # generate() outputs — are bit-identical to the resident modes;
        # all of this runs on the host backend so the dense tree never
        # stages into device memory
        cpu = jax.local_devices(backend="cpu")[0]

        def cast(x):
            if is_quantized_leaf(x):
                return x
            x = jnp.asarray(x)
            return x.astype(self._dtype) \
                if jnp.issubdtype(x.dtype, jnp.floating) else x

        with jax.default_device(cpu):
            params = jax.tree_util.tree_map(cast, dict(params),
                                            is_leaf=is_quantized_leaf)
            if quantized:
                # per-layer stacked layout — identical math and values to
                # the resident layer-scan engine, so parity holds
                from deepspeed_tpu.inference.quantized_layer_scan import (
                    quantize_layer_stacks)
                params = quantize_layer_stacks(params,
                                               group_size=group_size)

        # --- host tier: per-layer slice trees of every `layers` leaf ---
        layers = params["layers"]
        leaves, self._layer_treedef = jax.tree_util.tree_flatten(layers)
        self._ram: Dict[int, List[np.ndarray]] = {}
        for l in range(self.num_layers):
            # construction-time: this D2H copy IS how the host tier is
            # built — not a dispatch-loop fetch
            self._ram[l] = [np.ascontiguousarray(np.asarray(x[l]))  # tpulint: disable=no-hot-loop-fetch
                            for x in leaves]
        del leaves, layers

        # --- device tier: everything read every step stays resident ---
        def place(x):
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(self._dtype)
            return jax.device_put(x, self._sharding)
        self.resident = {k: jax.tree_util.tree_map(place, v)
                         for k, v in params.items() if k != "layers"}
        del params

        # --- NVMe tier: park the coldest layers on disk ---
        self._nvme = None
        self._nvme_meta: Dict[int, List[tuple]] = {}
        self._nvme_queued: set = set()
        self._nvme_queued_bufs: Dict[int, List[np.ndarray]] = {}
        nvme_layers = int(options.get("nvme_layers", 0) or 0)
        nvme_dir = options.get("nvme_dir")
        if nvme_layers > 0:
            if not nvme_dir:
                raise ValueError("capacity: nvme_layers > 0 needs nvme_dir")
            from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
            self._nvme = AsyncTensorSwapper(nvme_dir)
            # residency plane: the swapper's parking hook accounts every
            # swapped-out buffer under this runner's owner (nvme tier)
            self._nvme.plane_owner = self._memory_owner
            self._nvme.plane_component = "params"
            for l in range(max(0, self.num_layers - nvme_layers),
                           self.num_layers):
                meta = []
                for i, buf in enumerate(self._ram[l]):
                    name = f"cap_l{l}_{i}"
                    self._nvme.swap_out(name, buf)
                    meta.append((name, buf.shape, buf.dtype))
                self._nvme_meta[l] = meta
            self._nvme.synchronize()
            for l in self._nvme_meta:
                del self._ram[l]  # disk owns these bytes now

        # --- programs + prefetch state ---
        self._block = jax.jit(self._make_block())
        self._embed_jit = None
        self._head_jit = {}
        self._logits_jit = None
        self._buf0 = None  # next pass's layer-0 slice, prefetched at pass end
        self.last_h2d_bytes_step = self.h2d_bytes_pass()
        self.last_prefetch_stall_ms = 0.0
        # monotone lifetime accumulator (never reset, unlike the per-call
        # `last_` gauge): the v2 tracer delta-reads it around each wave to
        # attribute capacity staging stalls to request spans
        self.prefetch_stall_ms_total = 0.0

        self.plan = self._build_plan()
        # residency plane registration — construction-time only, never in
        # the streaming loop. The staging row is the formula's 2·slice
        # term (one slice computing + one arriving; 1 when synchronous);
        # kv_cache/workspace rows land per generate key in _generate.
        plane = get_plane()
        owner = self._memory_owner
        plane.register(f"{owner}:capacity_resident", component="params",
                       tier="hbm", nbytes=self.plan.resident_bytes,
                       owner=owner)
        plane.register(f"{owner}:capacity_host", component="params",
                       tier="host", nbytes=self.plan.host_bytes,
                       owner=owner)
        plane.register(f"{owner}:capacity_staging", component="staging",
                       tier="hbm", owner=owner,
                       nbytes=(2 if self.double_buffer else 1)
                       * self.plan.slice_bytes)
        logger.info(
            f"capacity serve: {self.num_layers} layers streamed "
            f"({self.plan.slice_bytes / 1e6:.1f} MB/slice"
            f"{', int8' if quantized else ''}"
            f"{f', {len(self._nvme_meta)} on NVMe' if self._nvme else ''}), "
            f"resident {self.plan.resident_bytes / 1e6:.1f} MB, "
            f"planned peak {self.plan.peak_hbm_bytes / 1e9:.2f} GB")

    # ------------------------------------------------------------- plumbing
    def _make_block(self):
        from deepspeed_tpu.inference.quantized_layer_scan import make_block_fn
        fused = getattr(self.infer_cfg, "fused_int8", None)
        if fused is None:
            fused = on_tpu()
        return make_block_fn(self.model_cfg, fused=bool(fused))

    def _layer_tree(self, bufs):
        return jax.tree_util.tree_unflatten(self._layer_treedef, bufs)

    def _host_slice(self, l: int) -> List[np.ndarray]:
        """Layer l's host leaves; NVMe-parked layers synchronize their
        queued disk reads here (queued one layer ahead by `_transfer_layer`
        so the read overlapped compute). Disk reads get bounded retries —
        a failed attempt discards any queued/staged state and re-reads
        fresh, so a transient aio failure costs one sweep of overlap, not
        the generate."""
        if l in self._ram:
            return self._ram[l]

        def read():
            bufs = self._nvme_queued_bufs.pop(l, None)
            if bufs is None:
                bufs = [self._nvme.swap_in(name, shape, dtype)
                        for name, shape, dtype in self._nvme_meta[l]]
            self._nvme.synchronize()
            return bufs

        try:
            return retry_call(read, what=f"capacity nvme read layer{l}",
                              retries=self.stage_retries)
        finally:
            self._nvme_queued.discard(l)

    def _queue_disk(self, l: int) -> None:
        """OPTIMISTIC read-ahead: a failure here must not kill the generate
        — drop the queued state (draining any partial submissions) and let
        `_host_slice`'s retried synchronous read be the authoritative
        attempt when the layer is actually needed."""
        if (self._nvme is None or l not in self._nvme_meta
                or l in self._nvme_queued):
            return
        try:
            self._nvme_queued_bufs[l] = [
                self._nvme.swap_in(name, shape, dtype)
                for name, shape, dtype in self._nvme_meta[l]]
            self._nvme_queued.add(l)
        except Exception as e:
            self._nvme_queued_bufs.pop(l, None)
            self._nvme_queued.discard(l)
            try:
                self._nvme.synchronize()
            except Exception:
                pass
            warn_once(("retry", "capacity nvme prefetch"),
                      f"capacity: nvme read-ahead of layer {l} failed "
                      f"({type(e).__name__}: {str(e)[:160]}); the layer "
                      "will be read synchronously with retries")
            _emit_event("retry", what=f"capacity nvme prefetch layer{l}",
                        attempt=1, delay_s=0.0,
                        error=f"{type(e).__name__}: {str(e)[:160]}")

    def _transfer_layer(self, l: int):
        """Dispatch layer l's H2D staging and queue the NEXT layer's disk
        read (if NVMe-parked) so it overlaps this transfer + compute.
        Staging gets bounded exponential-backoff retries (a transient
        transfer failure — or an injected `device_put` fault — is absorbed;
        a persistent one surfaces after `stage_retries` attempts)."""
        bufs = self._host_slice(l)
        nxt = (l + 1) % self.num_layers
        if nxt != l:
            self._queue_disk(nxt)
        tree = self._layer_tree(bufs)

        def stage():
            fault_point("device_put", label=f"layer{l}")
            return _transfer(tree, self._sharding)

        return retry_call(stage, what="capacity h2d staging",
                          retries=self.stage_retries)

    def _await_staged(self, buf, l: int):
        """Await one prefetched slice under the prefetch watchdog. On
        expiry the loop does NOT hang: it warns once, emits a `watchdog`
        telemetry event, and falls back to a fresh SYNCHRONOUS re-stage of
        the layer (the stalled transfer keeps running detached; its buffer
        is abandoned). The caller's timer around this call lands the whole
        episode in `last_prefetch_stall_ms`."""

        def body():
            fault_point("prefetch_await", label=f"layer{l}")
            _await_transfer(buf)

        if watchdog_await(body, timeout_s=self.prefetch_watchdog_s,
                          what="prefetch_await"):
            return buf
        warn_once(("watchdog", "prefetch_await"),
                  f"capacity: prefetch of layer {l} stalled past "
                  f"{self.prefetch_watchdog_s:g}s — re-staging "
                  "synchronously (docs/resilience.md; repeats go to "
                  "telemetry only)")
        _emit_event("watchdog", watchdog="prefetch_await", layer=l,
                    timeout_s=self.prefetch_watchdog_s,
                    fallback="sync_restage")
        fresh = _transfer(self._layer_tree(self._host_slice(l)),
                          self._sharding)
        _await_transfer(fresh)
        return fresh

    # --------------------------------------------------------- forward pass
    def _pass(self, h, aux, cache_k, cache_v):
        """One full layer sweep. Double-buffered: transfer l+1 is dispatched
        BEFORE layer l's slice is awaited; layer l−1's OUTPUT is awaited
        after dispatching block l (throttle → ≤2 live slices). Synchronous
        mode (`double_buffer: false`, the A/B baseline) stages, waits, and
        computes one layer at a time."""
        L = self.num_layers
        stall = 0.0
        if not self.double_buffer:
            for l in range(L):
                buf = self._transfer_layer(l)
                t0 = time.perf_counter()
                buf = self._await_staged(buf, l)
                stall += time.perf_counter() - t0
                h, (cache_k[l], cache_v[l]) = self._block(
                    h, buf, aux, (cache_k[l], cache_v[l]))
                _await_result(h)
            self.last_prefetch_stall_ms += stall * 1e3
            self.prefetch_stall_ms_total += stall * 1e3
            return h
        buf = self._buf0 if self._buf0 is not None else self._transfer_layer(0)
        self._buf0 = None
        prev_out = None
        for l in range(L):
            nxt = self._transfer_layer(l + 1) if l + 1 < L else None
            t0 = time.perf_counter()
            buf = self._await_staged(buf, l)
            stall += time.perf_counter() - t0
            h, (cache_k[l], cache_v[l]) = self._block(
                h, buf, aux, (cache_k[l], cache_v[l]))
            if prev_out is not None:
                _await_result(prev_out)
            prev_out = h
            buf = nxt
        # prefetch next pass's layer 0 while the head/sampling runs
        self._buf0 = self._transfer_layer(0)
        self.last_prefetch_stall_ms += stall * 1e3
        self.prefetch_stall_ms_total += stall * 1e3
        return h

    def _programs(self, max_len: int):
        cfg = self.model_cfg
        dtype = self._dtype
        hd = cfg.head_dim
        window = getattr(cfg, "sliding_window", None)
        embed = self.resident["embed_tokens"]
        if self._embed_jit is None:
            from deepspeed_tpu.inference.kv_cache import decode_mask
            from deepspeed_tpu.ops.attention import rope_cos_sin

            def embed_fn(ids_cur, index, mlen):
                bsz, sl = ids_cur.shape
                h = jnp.take(embed.astype(dtype), ids_cur, axis=0)
                positions = index[:, None] + jnp.arange(sl)[None, :]
                cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, dtype)
                mask = decode_mask(positions, mlen, window=window)
                return h, (cos, sin, index, mask)

            self._embed_jit = jax.jit(embed_fn, static_argnums=(2,))
        return self._embed_jit

    def logits_program(self):
        """One cached jit of the resident final-norm + head: `h → logits`.
        Shape-polymorphic (jit retraces per shape — cheap, resident-only
        weights). The v2 continuous-batching engine drives its capacity
        serve mode through this plus `_programs()`/`_pass()`, so its
        per-bucket logits come from the SAME compiled head program the v1
        capacity generate uses."""
        if self._logits_jit is None:
            from deepspeed_tpu.inference.quantized_layer_scan import _rmsnorm
            cfg, dtype = self.model_cfg, self._dtype
            eps = cfg.rms_norm_eps
            norm_w = self.resident["norm"]["weight"]
            embed = self.resident["embed_tokens"]
            head = self.resident.get("lm_head")

            def logits_fn(h):
                hn = _rmsnorm(h, norm_w, eps, dtype)
                if head is None:
                    return jnp.einsum("bsd,vd->bsv", hn, embed.astype(dtype))
                return hn @ head.astype(dtype)

            self._logits_jit = jax.jit(logits_fn)
        return self._logits_jit

    def _head_program(self, temperature, top_k, top_p, eos, pad):
        from deepspeed_tpu.inference.quantized_layer_scan import _rmsnorm
        from deepspeed_tpu.ops.sampling import sample_logits
        key = (temperature, top_k, top_p, eos, pad)
        if key not in self._head_jit:
            cfg, dtype = self.model_cfg, self._dtype
            eps = cfg.rms_norm_eps
            norm_w = self.resident["norm"]["weight"]
            embed = self.resident["embed_tokens"]
            head = self.resident.get("lm_head")

            def head_fn(h, rng_i, done):
                hn = _rmsnorm(h, norm_w, eps, dtype)
                if head is None:
                    logits = jnp.einsum("bsd,vd->bsv", hn,
                                        embed.astype(dtype))
                else:
                    logits = hn @ head.astype(dtype)
                nxt = sample_logits(logits[:, -1, :], rng_i,
                                    temperature=temperature, top_k=top_k,
                                    top_p=top_p)
                if eos is not None:
                    nxt = jnp.where(done, pad, nxt)
                    done = done | (nxt == eos)
                return nxt, done

            self._head_jit[key] = jax.jit(head_fn)
        return self._head_jit[key]

    # ------------------------------------------------------------ generate
    def bind_key(self, key):
        """Engine program-cache entry for one (b, s, new, sampling) key.
        Signature matches the jitted generates: (params, ids, rng) — the
        params argument is the engine's view of the tree this runner owns
        and is intentionally unused (the tiers are pre-staged)."""
        return lambda params, ids, rng: self._generate(key, ids, rng)

    def _generate(self, key, ids, rng):
        b, s, new, temperature, top_k, top_p, eos, pad = key
        cfg = self.model_cfg
        # wall-clock budget on the host-driven decode loop (None = off):
        # checked at step boundaries, so a wedged runtime fails loudly with
        # DeadlineExceeded instead of hanging the generate call forever
        deadline = Deadline(self.dispatch_deadline_s, "capacity generate")
        max_len = round_up_len(s + new)
        embed_jit = self._programs(max_len)
        head_jit = self._head_program(temperature, top_k, top_p, eos, pad)
        self.last_prefetch_stall_ms = 0.0
        cache_k = [jnp.zeros((b, max_len, cfg.num_key_value_heads,
                              cfg.head_dim), self.infer_cfg.dtype)
                   for _ in range(self.num_layers)]
        cache_v = [jnp.zeros_like(x) for x in cache_k]
        # per-key serving residency (generate-level, NOT per decode step):
        # the rows track the most recent generate's cache/workspace shape
        plane = get_plane()
        plane.register(f"{self._memory_owner}:kv_cache",
                       component="kv_cache", tier="hbm",
                       owner=self._memory_owner,
                       nbytes=sum(int(x.nbytes) for x in cache_k)
                       + sum(int(x.nbytes) for x in cache_v))
        plane.register(f"{self._memory_owner}:workspace",
                       component="workspace", tier="hbm",
                       owner=self._memory_owner,
                       nbytes=decode_workspace_bytes(
                           self.model_cfg, b, max_len, self._dtype))

        ids = jnp.asarray(ids, jnp.int32)
        index = jnp.zeros((b,), jnp.int32)
        h, aux = embed_jit(ids, index, max_len)
        h = self._pass(h, aux, cache_k, cache_v)
        rng, sub = jax.random.split(rng)
        done = jnp.zeros((b,), jnp.bool_)
        tok, done = head_jit(h, sub, done)

        keys = jax.random.split(rng, new - 1) if new > 1 else []
        toks = []
        index = jnp.full((b,), s, jnp.int32)
        for i in range(new - 1):
            deadline.check(f"decode step {i}")
            h, aux = embed_jit(tok[:, None], index, max_len)
            h = self._pass(h, aux, cache_k, cache_v)
            toks.append(tok)
            tok, done = head_jit(h, keys[i], done)
            index = index + 1
        toks.append(tok)
        return jnp.concatenate([ids, jnp.stack(toks, axis=1)], axis=1)

    def forward(self, ids):
        """Plain no-cache forward (logits) through the streamed layers —
        the capacity analog of the resident engine's `forward`."""
        ids = jnp.asarray(ids, jnp.int32)
        b, s = ids.shape
        max_len = round_up_len(s)
        logits_jit = self.logits_program()
        embed_jit = self._programs(max_len)
        cfg = self.model_cfg
        cache_k = [jnp.zeros((b, max_len, cfg.num_key_value_heads,
                              cfg.head_dim), self.infer_cfg.dtype)
                   for _ in range(self.num_layers)]
        cache_v = [jnp.zeros_like(x) for x in cache_k]
        h, aux = embed_jit(ids, jnp.zeros((b,), jnp.int32), max_len)
        h = self._pass(h, aux, cache_k, cache_v)
        return logits_jit(h)

    # ---------------------------------------------------------- accounting
    def params_view(self):
        """The engine-facing tree: device-resident leaves + the host/NVMe
        layer tiers (per-layer slice trees; NVMe layers appear as their
        (name, shape, dtype) metadata)."""
        layers = [self._layer_tree(self._ram[l]) if l in self._ram
                  else self._layer_tree(
                      [_NVMeLeaf(*m) for m in self._nvme_meta[l]])
                  for l in range(self.num_layers)]
        return dict(self.resident, layers=layers)

    def host_resident(self) -> bool:
        """True when every RAM-tier leaf is a plain host array — the
        'params verifiably host-resident between steps' contract."""
        return all(isinstance(x, np.ndarray)
                   for bufs in self._ram.values() for x in bufs)

    def slice_bytes(self, l: Optional[int] = None) -> int:
        if l is not None:
            if l in self._ram:
                return sum(x.nbytes for x in self._ram[l])
            return sum(int(np.prod(shape)) * np.dtype(dt).itemsize
                       for _, shape, dt in self._nvme_meta[l])
        return max(self.slice_bytes(l) for l in range(self.num_layers))

    def h2d_bytes_pass(self) -> int:
        """Host→device bytes one layer sweep streams (== one decode step)."""
        return sum(self.slice_bytes(l) for l in range(self.num_layers))

    def weight_bytes_step_pair(self):
        """(at-rest, dense-equivalent) weight bytes one decode step reads —
        the streamed slices plus the resident final norm + lm_head (the
        embedding is a B-row gather, excluded), mirroring the layer-scan
        accounting in `quantized_layer_scan.weight_bytes_per_step`."""
        item = jnp.dtype(self._dtype).itemsize

        def dense_eq(tree) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(
                    tree, is_leaf=is_quantized_leaf):
                if is_quantized_leaf(leaf):
                    total += int(np.prod(leaf["__q8__"].shape)) * item
                elif hasattr(leaf, "size"):
                    total += int(leaf.size) * item
            return total

        resident = _leaf_bytes(self.resident.get("norm", {}))
        resident += _leaf_bytes(self.resident.get("lm_head", {}))
        at_rest = self.h2d_bytes_pass() + resident
        view = self.params_view()
        dense = sum(dense_eq(lt) for lt in view["layers"]) + resident
        return int(at_rest), int(dense)

    def _build_plan(self) -> CapacityPlan:
        cfg = self.infer_cfg
        b = int(getattr(cfg, "max_batch_size", None) or 1)
        max_len = round_up_len(getattr(cfg, "max_out_tokens", 1024))
        return CapacityPlan(
            num_layers=self.num_layers,
            slice_bytes=self.slice_bytes(),
            resident_bytes=_leaf_bytes(self.resident),
            kv_bytes=kv_cache_bytes(self.model_cfg, b, max_len, cfg.dtype,
                                    kv_dtype=getattr(cfg, "kv_cache_dtype",
                                                     None)),
            workspace_bytes=decode_workspace_bytes(
                self.model_cfg, b, max_len, cfg.dtype),
            host_bytes=sum(x.nbytes for bufs in self._ram.values()
                           for x in bufs),
            nvme_bytes=sum(self.slice_bytes(l) for l in self._nvme_meta),
            nvme_layers=len(self._nvme_meta),
            double_buffer=self.double_buffer)

    def plan_for(self, batch: int, seq: int, new_tokens: int) -> CapacityPlan:
        """The plan re-accounted at one generate key's actual shapes."""
        max_len = round_up_len(seq + new_tokens)
        return dataclasses.replace(
            self.plan,
            kv_bytes=kv_cache_bytes(self.model_cfg, batch, max_len,
                                    self.infer_cfg.dtype,
                                    kv_dtype=getattr(self.infer_cfg,
                                                     "kv_cache_dtype", None)),
            workspace_bytes=decode_workspace_bytes(
                self.model_cfg, batch, max_len, self.infer_cfg.dtype))


class _NVMeLeaf:
    """Metadata stand-in for an NVMe-parked slice in `params_view` (the
    bytes live in the swap file; shape/dtype keep fingerprints stable)."""

    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name, shape, dtype):
        self.name, self.shape, self.dtype = name, tuple(shape), np.dtype(dtype)

    @property
    def size(self):
        return int(np.prod(self.shape))

    @property
    def nbytes(self):
        return self.size * self.dtype.itemsize

    def __repr__(self):
        return f"_NVMeLeaf({self.name}, {self.shape}, {self.dtype})"
