"""Inference engine v1 (reference `deepspeed/inference/engine.py:41`).

TPU-native redesign of DeepSpeed-Inference:
- kernel injection (`module_inject/replace_module.py:183`) is unnecessary —
  the zoo models already run the fused XLA/Pallas path, and tensor
  parallelism is declarative (logical→'model' axis rules in
  `utils/partitioning.py`) rather than imperative weight slicing;
- CUDA-graph capture (`inference/engine.py:519`) ≡ jit: the whole
  prefill+decode loop is one compiled program (`lax.scan` over steps), so
  there is no per-token Python/launch overhead at all;
- the KV cache is a static-shape pytree (`kv_cache.py`), the analog of the
  reference's workspace `inference_context.h`.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.kv_cache import KVCache
from deepspeed_tpu.resilience.faults import fault_point, is_oom_error
from deepspeed_tpu.telemetry import (RecompileDetector, annotate,
                                     compile_span, device_busy, get_hub,
                                     init_phase, init_span, jit_name,
                                     keep_program)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import logger, warn_once


# A generate program's counters (the model's `program_counters`) are int32
# and their sums over a call outgrow it (positions walked: 5e10 a batch of
# 32k-token rows): a sum is carried as (high, low) limbs of this many bits.
_LIMB = 16


def _wide_add(total, leaves):
    """`total` (high, low) plus every element of the int32 `leaves`, each
    split into limbs before it is summed."""
    high, low = total
    for leaf in leaves:
        high = high + jnp.sum(leaf >> _LIMB, dtype=jnp.int32)
        low = low + jnp.sum(leaf & ((1 << _LIMB) - 1), dtype=jnp.int32)
    return high + (low >> _LIMB), low & ((1 << _LIMB) - 1)


def _cache_dims(cfg) -> tuple:
    """(num_layers, kv_heads, head_dim) from a zoo model config (duck-typed
    over llama/gpt2/mixtral naming)."""
    layers = getattr(cfg, "num_kv_layers", None)   # a model of mixed layers
    if layers is None:
        layers = getattr(cfg, "num_hidden_layers", None) or getattr(cfg, "n_layer")
    heads = (getattr(cfg, "num_key_value_heads", None)
             or getattr(cfg, "num_kv_heads", None)  # falcon naming
             or getattr(cfg, "num_attention_heads", None) or getattr(cfg, "n_head"))
    head_dim = getattr(cfg, "head_dim", None)
    if head_dim is None:
        hidden = getattr(cfg, "hidden_size", None) or getattr(cfg, "n_embd")
        n_attn = (getattr(cfg, "num_attention_heads", None) or getattr(cfg, "n_head"))
        head_dim = hidden // n_attn
    return int(layers), int(heads), int(head_dim)


class InferenceEngine:
    """Generation wrapper over a zoo flax model + sharded params.

    Reference `InferenceEngine` (`inference/engine.py:41`): TP group creation
    `:249` ≡ the `model` mesh axis; `_apply_injection_policy:403` ≡ nothing
    (already fused); `forward:579` ≡ `forward`/`generate` below.
    """

    @init_span("v1")
    def __init__(self, model: Any, config: Optional[DeepSpeedInferenceConfig] = None,
                 params: Any = None):
        init_phase("plan")
        if config is None:
            config = DeepSpeedInferenceConfig()
        self._config = config
        kvd = getattr(config, "kv_cache_dtype", None)
        if kvd not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None or 'int8', got {kvd!r}")
        if isinstance(model, tuple):
            model, params = model
        self.module = model
        self.model_cfg = model.cfg

        # Topology: adopt the installed mesh, else build one with the
        # requested TP degree over local devices (reference :249).
        try:
            self.topology = groups.get_topology(create_default=False)
        except RuntimeError:
            tp = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
            # Claim exactly the TP group's devices (reference
            # `_create_model_parallel_group` :249); callers wanting DP/batch-
            # parallel inference install a wider topology first.
            self.topology = groups.initialize(
                tp=tp, dp=1, devices=jax.devices()[:tp])
        self.mesh = self.topology.mesh

        if params is None:
            raise ValueError(
                "init_inference needs params: pass init_inference(model=(module, "
                "params)) or init_inference(module, params=params). Use "
                "deepspeed_tpu.module_inject.load_hf_checkpoint() for HF weights.")
        part = init_phase("place_params")
        self.params = self._place_with_recovery(params)
        part["async"] = device_busy(self.params)
        init_phase("build_programs")
        if kvd == "int8" and self.serve_mode != "dequant":
            # the streamed modes carry raw (ck, cv, ix) array state through
            # _make_stack_forward — no QuantizedKVLayer seat there yet
            warn_once(("kv_int8_mode", self.serve_mode),
                      f"kv_cache_dtype='int8' only quantizes the dequant "
                      f"serve mode's KV cache (resolved: {self.serve_mode}) "
                      "— the layer-streamed modes keep dense KV")
        self._generate_jit = {}
        # generate key -> RecompileDetector program name, recorded at
        # dispatch (tools/tpuverify registration-coverage contract)
        self._program_names = {}
        self._forward_jit = None
        self._weight_bytes_cache = None
        # each (b, s, new_tokens, sampling) key is its own pinned program;
        # a signature miss within one key (e.g. relayouted/uncommitted
        # params) is a silent whole-loop recompile — warn loudly
        self.recompiles = RecompileDetector("serving_v1", pinned_default=True)
        self.last_decode_tok_s: Optional[float] = None
        # speculative decoding rides ON TOP of the resolved serve mode
        # (draft-and-verify — inference/speculative.py); None when off or
        # structurally unsupported here (warned, vanilla serving)
        from deepspeed_tpu.inference.speculative import SpeculativeDecoder
        self._spec = SpeculativeDecoder.maybe_create(self)
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(self.params))
        logger.info(f"InferenceEngine: {n_params/1e6:.1f}M params, "
                    f"{self.topology.describe()}, dtype={jnp.dtype(config.dtype).name}")

    # ---- param placement ----
    def _place_with_recovery(self, params):
        """Place params with OOM-driven serve-mode degradation: when
        placement for the resolved mode exhausts device memory — real
        RESOURCE_EXHAUSTED or an injected `param_placement` fault — walk
        the ladder dequant → layer_scan → capacity and re-place from the
        RAW tree (so the degraded mode is value-identical to choosing it
        up front). The retry happens AFTER the except block ends: Python
        then drops the exception (and the traceback frames holding the
        failed attempt's partially-placed tree), so the old placement
        frees BEFORE the next one allocates — the r5 residency lesson."""
        while True:
            try:
                return self._shard_params(params)
            except Exception as e:
                mode = getattr(self, "serve_mode", "dequant")
                if not self._degrade_enabled() or not is_oom_error(e):
                    raise
                nxt = self._degraded_mode(mode, params)
                if nxt is None:
                    raise
                self._note_degraded(mode, nxt, stage="placement", reason=e)
                self._capacity = None
                self._forced_mode = nxt
            # `e` and its traceback are gone here; the loop re-places

    def _degrade_enabled(self) -> bool:
        from deepspeed_tpu.inference.serve_modes import degrade_enabled
        return degrade_enabled(self._config)

    def _degraded_mode(self, mode: str, params) -> Optional[str]:
        """Next viable rung of the ladder (inference/serve_modes.py)."""
        from deepspeed_tpu.inference.serve_modes import degraded_mode
        return degraded_mode(self, mode, params)

    def _note_degraded(self, frm: str, to: str, stage: str,
                       reason: BaseException) -> None:
        from deepspeed_tpu.inference.serve_modes import note_degraded
        note_degraded("v1", frm, to, stage, reason)

    def _degrade_to(self, nxt: str) -> None:
        """Re-place the CURRENT tree for a lower serve mode after a
        compile/dispatch-time OOM. The engine's own references (params
        handle, program caches, speculative decoder, capacity runner) are
        dropped FIRST so the only live copy during re-placement is the
        local source tree — compiled programs take params as arguments
        (they don't close over leaves), so clearing the jit caches really
        does release them."""
        src, self.params = self.params, None
        self._spec = None
        self._generate_jit = {}
        self._program_names = {}
        self._forward_jit = None
        self._weight_bytes_cache = None
        self._capacity = None
        self._layouts_pinned = False
        self._forced_mode = nxt
        self.params = self._shard_params(src)
        del src
        from deepspeed_tpu.inference.speculative import SpeculativeDecoder
        self._spec = SpeculativeDecoder.maybe_create(self)

    def _shard_params(self, params):
        """Resolve the serve mode, then place params for it — the shared
        `serve_modes.place_params` (also what the v2 engine runs, with its
        own placement ownership since r11). Capacity mode parks the layer
        tiers HOST-side; the resident modes cast to the inference dtype
        and place with TP shardings."""
        from deepspeed_tpu.inference.serve_modes import place_params
        return place_params(self, params)

    def _resolve_serve_mode(self, params) -> str:
        """Serve-mode resolution (inference/serve_modes.py) — `auto`
        delegates to `config.choose_serve_mode` over the full serving
        residency accounting."""
        from deepspeed_tpu.inference.serve_modes import resolve_serve_mode
        return resolve_serve_mode(self, params)

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

    # ---- plain forward (no cache) ----
    def forward(self, input_ids, *args, **kwargs):
        if getattr(self, "serve_mode", "dequant") == "capacity":
            return self._capacity.forward(input_ids)
        if self._forward_jit is None:
            self._forward_jit = jax.jit(
                lambda p, ids: self.module.apply(
                    {"params": self._maybe_dequant(p)}, ids))
        return self._forward_jit(self.params, jnp.asarray(input_ids))

    __call__ = forward

    # ---- generation ----
    def generate(self, input_ids, max_new_tokens: int = 128,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: Optional[int] = None,
                 seed: int = 0, pad_token_id: int = 0):
        """Generate `max_new_tokens` continuations. `input_ids` (B, S) —
        left-aligned equal-length prompts. Greedy when temperature==0;
        otherwise temperature / top-k / top-p sampling ON DEVICE inside the
        decode scan (ops/sampling.py).

        One compiled program: prefill + `lax.scan` over decode steps
        (the jit analog of `_create_cuda_graph` `inference/engine.py:519`).

        An OOM while building/compiling/dispatching the program (real
        RESOURCE_EXHAUSTED, or an injected `program_compile` /
        `generate_dispatch` fault) walks the serve-mode degradation
        ladder (`_degrade_to`) and retries — bounded, since the ladder is
        finite and capacity has no next rung.
        """
        try:
            return self._generate_impl(
                input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, seed=seed,
                pad_token_id=pad_token_id)
        except Exception as e:
            mode = getattr(self, "serve_mode", "dequant")
            if not self._degrade_enabled() or not is_oom_error(e):
                raise
            nxt = self._degraded_mode(mode, self.params)
            if nxt is None:
                raise
            self._note_degraded(mode, nxt, stage="compile", reason=e)
        # out of the except block (traceback freed) before re-placing
        self._degrade_to(nxt)
        return self.generate(input_ids, max_new_tokens=max_new_tokens,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, eos_token_id=eos_token_id,
                             seed=seed, pad_token_id=pad_token_id)

    def _generate_impl(self, input_ids, max_new_tokens: int = 128,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, eos_token_id: Optional[int] = None,
                       seed: int = 0, pad_token_id: int = 0):
        if getattr(self, "_spec", None) is not None:
            # k-token draft-and-verify over this serve mode's weights
            # (inference/speculative.py) — same signature and output shape,
            # bit-exact at temperature 0
            return self._spec.generate(
                input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, seed=seed,
                pad_token_id=pad_token_id)
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        key = (b, s, int(max_new_tokens), float(temperature), int(top_k),
               float(top_p), eos_token_id, pad_token_id)
        rng = jax.random.PRNGKey(seed)
        if key in self._generate_jit:
            return self._dispatch_generate(key, input_ids, rng, b,
                                           int(max_new_tokens))
        # a new (b, s, new, sampling) key: its build, compile and first
        # dispatch are one `compile` span under the program's stable name
        with compile_span(self._program_name(key), "v1"):
            self._build_program(key, input_ids, rng)
            fn = self._generate_jit[key]
            if hasattr(fn, "trace"):
                # the tracing the dispatch below uses, kept for the
                # program map (the layout-pinning compile keeps its own;
                # the capacity runner's host loop is no one program)
                keep_program(self._program_name(key),
                             fn.trace(self.params, input_ids, rng),
                             mesh=self.mesh,
                             detector=self._detector_name(key))
            return self._dispatch_generate(key, input_ids, rng, b,
                                           int(max_new_tokens))

    def _build_program(self, key, input_ids, rng) -> None:
        if getattr(self, "serve_mode", "dequant") == "capacity":
            # host-driven layer-streamed loop (capacity_scan) — the runner
            # owns placement/layouts, so the AUTO-layout pin never applies
            fault_point("program_compile", label="capacity")
            self._generate_jit[key] = self._capacity.bind_key(key)
        elif self._auto_layouts() and not getattr(self, "_layouts_pinned",
                                                  False):
            # FIRST program pins the layouts; later (b, s) programs
            # compile against the now-custom layouts of the live params
            # (re-placing per program would invalidate earlier programs'
            # compiled input layouts)
            self._generate_jit[key] = self._compile_auto_layout(
                self._build_for_key(key, auto_layout=True), input_ids, rng,
                key)
            self._layouts_pinned = True
        else:
            self._generate_jit[key] = self._build_for_key(key)

    def _program_name(self, key) -> str:
        """Stable name of one generate key's program, as its `compile`
        span carries it and, through `telemetry.jit_name`, the device
        trace's module line and the program map: one name a key.
        Multi-device programs carry the mesh axes
        (`@model2` etc.), so a 1-device and an N-device run are told
        apart; single-device names are unchanged."""
        mode = getattr(self, "serve_mode", "dequant")
        prog = mode if mode in ("layer_scan", "capacity") else "generate"
        prog = self._kv_program_suffix(prog, mode)
        name = f"v1:{prog}:b{key[0]}_s{key[1]}_n{key[2]}"
        # a key that samples is another program of the same shape: greedy
        # names are bare, the others say how they differ
        sampling = [f"{tag}{value}" for tag, value, default in zip(
            ("t", "k", "p", "eos", "pad"), key[3:], (0.0, 0, 1.0, None, 0))
            if value != default]
        if sampling:
            name = f"{name}:{'_'.join(sampling)}"
        from deepspeed_tpu.ops.pallas.sharded import mesh_fingerprint
        fp = mesh_fingerprint(self.mesh)
        return f"{name}@{fp}" if fp else name

    def _detector_name(self, key) -> str:
        """The name the RecompileDetector knows one generate key's program
        by (`generate:(8, 128, 64, 0.0, ...)`; the mesh and an int8 cache
        add their suffixes to the part before the colon). The program map
        keeps it beside the span's name."""
        mode = getattr(self, "serve_mode", "dequant")
        program = mode if mode in ("layer_scan", "capacity") else "generate"
        program = self._kv_program_suffix(program, mode)
        from deepspeed_tpu.ops.pallas.sharded import mesh_fingerprint
        fp = mesh_fingerprint(self.mesh)
        if fp:  # mesh in the pinned-program identity (1-dev names stable)
            program = f"{program}@{fp}"
        return f"{program}:{key}"

    def _kv_program_suffix(self, prog: str, mode: str) -> str:
        """Append '@kv_int8' when the int8 cache is EFFECTIVE for this
        program (config asks AND the serve mode quantizes its cache) —
        quantized-cache programs are distinct programs, so the compile
        span and the RecompileDetector carry them under their own name.
        Dense/default names are unchanged (same stability contract as the
        mesh suffix)."""
        if mode == "dequant" and \
                getattr(self._config, "kv_cache_dtype", None) == "int8":
            return f"{prog}@kv_int8"
        return prog

    def _build_for_key(self, key, auto_layout: bool = False):
        """Build the generate program for one (b, s, new, sampling) key —
        the model-apply path, or the quantized layer scan when that serve
        mode is active (same program surface either way)."""
        fault_point("program_compile",
                    label=getattr(self, "serve_mode", "dequant"))
        if getattr(self, "serve_mode", "dequant") == "layer_scan":
            from deepspeed_tpu.inference.quantized_layer_scan import (
                build_layer_scan_generate)
            from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
            return build_layer_scan_generate(
                self.model_cfg, self._config, *key,
                fused=self._use_fused_int8(), auto_layout=auto_layout,
                mesh=self.mesh if nontrivial_axes(self.mesh) else None,
                name=jit_name(self._program_name(key)))
        return self._build_generate(*key, auto_layout=auto_layout)

    def _dispatch_generate(self, key, input_ids, rng, b, new_tokens):
        """Dispatch one generate program with serving telemetry: recompile
        fingerprinting, decode throughput (timed to host materialization —
        np.asarray is a real fetch, so the program has finished), and a
        'serving' hub event."""
        import time as _time
        mode = getattr(self, "serve_mode", "dequant")
        detector = self._detector_name(key)
        fault_point("generate_dispatch", label=detector.split(":", 1)[0])
        if mode != "capacity":  # the capacity runner registers its own
            self._register_serving_residency(key)
        self._program_names[key] = detector
        self.recompiles.observe(detector, (self.params, input_ids, rng))
        t0 = _time.perf_counter()
        with annotate("ds:generate"):
            out = self._generate_jit[key](self.params, input_ids, rng)
            counted = {}
            if isinstance(out, tuple):   # (sequences, the model's counters)
                out, counted = jax.device_get(out)   # one fetch for both
                counted = {k: (int(high) << _LIMB) + int(low)
                           for k, (high, low) in counted.items()}
            out = np.asarray(out)
        dt = _time.perf_counter() - t0
        self.last_decode_tok_s = (b * new_tokens / dt) if dt > 0 else None
        hub = get_hub()
        kv = self._kv_telemetry(b, key[1], key[2], token_loop=True)
        # gauges and counters update on a disabled hub too (hub.py): what a
        # benchmark reads of a call without the JSONL stream
        for name in ("kv_bytes", "state_bytes", "window_kv_bytes",
                     "shared_kv_bytes", "full_kv_bytes", "latent_kv_bytes",
                     "index_kv_bytes",
                     "dense_kv_slots_live", "dense_kv_slots_fetched",
                     "dense_decode_grid_steps"):
            if name in kv:
                hub.gauge(f"serving_v1/{name}", kv[name])
        for name, value in counted.items():
            hub.counter(f"serving_v1/{name}", value)
        if hub.enabled:
            wb, wb_dense = self._weight_bytes_per_step()
            extra = {}
            if mode == "capacity":
                # host-side accounting/timers only — no device fetches
                # beyond the generate's own output materialization
                extra = {
                    "h2d_bytes_step": self._capacity.last_h2d_bytes_step,
                    "prefetch_stall_ms": round(
                        self._capacity.last_prefetch_stall_ms, 3)}
            hub.emit("serving", engine="v1", queries=int(b),
                     new_tokens=new_tokens,
                     decode_tok_s=round(self.last_decode_tok_s, 1)
                     if self.last_decode_tok_s else None,
                     serve_mode=mode,
                     weight_bytes_step=wb,
                     weight_bytes_step_dense=wb_dense,
                     recompiles=self.recompiles.misses,
                     pinned_recompiles=self.recompiles.pinned_misses,
                     **kv, **counted, **extra)
        return out

    def _kv_telemetry(self, b, s, new_tokens, token_loop=False):
        """kv_dtype + kv_bytes for the serving event (docs/telemetry.md) —
        pure host arithmetic over the program shapes, zero device fetches.
        kv_dtype is the EFFECTIVE at-rest element type: 'int8' only when
        the config asks for it AND this serve mode quantizes its cache
        (the layer-streamed modes keep dense KV, engine __init__ warns).
        `token_loop`: the call is `_build_generate`'s one-token-a-step scan
        (not a speculative round), whose dense decode kernel is counted."""
        from deepspeed_tpu.inference.capacity_scan import (
            kv_bytes_by_kind, kv_cache_bytes, recurrent_state_bytes,
            round_up_len)
        mode = getattr(self, "serve_mode", "dequant")
        kvd = getattr(self._config, "kv_cache_dtype", None)
        eff = kvd if (kvd == "int8" and mode == "dequant") else None
        max_len = round_up_len(int(s) + int(new_tokens))
        try:
            kv_b = kv_cache_bytes(self.model_cfg, int(b), max_len,
                                  self._config.dtype, kv_dtype=eff)
        except Exception:
            return {}  # non-standard config dims: skip, never break serving
        # K and V of the ATTENTION layers, and by kind where the model keeps
        # more than one (rings, a shared slab, latent rows); what recurrent
        # layers hold is counted apart (0 for a model that has none)
        kinds = kv_bytes_by_kind(self.model_cfg, int(b), max_len,
                                 self._config.dtype)
        dense = {}
        if token_loop and mode == "dequant" and eff is None and not kinds \
                and getattr(self.module, "make_cache", None) is not None:
            dense = self._dense_decode_traffic(int(b), int(s),
                                               int(new_tokens), max_len)
        return {"kv_dtype": eff or jnp.dtype(self._config.dtype).name,
                "kv_bytes": int(kv_b), **kinds,
                "state_bytes": recurrent_state_bytes(
                    self.model_cfg, int(b), self._config.dtype),
                **dense}

    def _dense_decode_traffic(self, b, s, new_tokens, max_len):
        """What the dense decode kernel fetches and the grid steps it takes
        over a call's decode steps and attention layers, from the kernel's
        own plan (`decode_attention.decode_plan`; host arithmetic, nothing
        fetched): `dense_kv_slots_live` against `dense_kv_slots_fetched` (a
        slot is one token's place in one row of one layer) and
        `dense_decode_grid_steps`. For a model whose cache is the STACKED
        dense one (its `make_cache`; one kind of K and V), and only where a
        single-token step dispatches the kernel (`dense_decode_route`): {}
        otherwise. Decode step t of `new_tokens - 1` attends `s + t + 1`
        tokens a row, the step's own staged one among them."""
        from deepspeed_tpu.ops.attention import dense_decode_route
        from deepspeed_tpu.ops.pallas.decode_attention import (decode_plan,
                                                               plan_traffic)
        cfg = self.model_cfg
        layers, hkv, d = _cache_dims(cfg)
        kernel, mesh = dense_decode_route(
            getattr(cfg, "attn_impl", "auto"),
            getattr(cfg, "sliding_window", None),
            int(cfg.num_attention_heads), hkv)
        if not kernel or new_tokens < 2:
            return {}
        if mesh is not None:   # each shard's kernel holds its own KV heads
            hkv //= mesh.shape["model"]
        plan = decode_plan(b, hkv, max_len, d,
                           jnp.dtype(self._config.dtype).itemsize)
        lengths = np.broadcast_to(
            s + 1 + np.arange(new_tokens - 1)[:, None], (new_tokens - 1, b))
        live, fetched, steps = plan_traffic(plan, lengths, max_len)
        return {"dense_kv_slots_live": layers * live,
                "dense_kv_slots_fetched": layers * fetched,
                "dense_decode_grid_steps": layers * steps}

    def _register_serving_residency(self, key):
        """MemoryPlane rows for one generate key — the KV cache is created
        INSIDE the compiled program, so its bytes come from the same
        formulas the auto serve-mode accounting uses (host arithmetic
        only; generate-dispatch level, never per decode step)."""
        from deepspeed_tpu.inference.capacity_scan import (
            decode_workspace_bytes, kv_cache_bytes, recurrent_state_bytes,
            round_up_len)
        from deepspeed_tpu.telemetry.memory import get_plane, owner_for
        b, s, new_tokens = int(key[0]), int(key[1]), int(key[2])
        mode = getattr(self, "serve_mode", "dequant")
        kvd = getattr(self._config, "kv_cache_dtype", None)
        eff = kvd if (kvd == "int8" and mode == "dequant") else None
        try:
            max_len = round_up_len(s + new_tokens)
            kv_b = kv_cache_bytes(self.model_cfg, b, max_len,
                                  self._config.dtype, kv_dtype=eff)
            ws_b = decode_workspace_bytes(self.model_cfg, b, max_len,
                                          self._config.dtype)
        except Exception:
            return  # non-standard config dims: skip, never break serving
        owner = owner_for(self, type(self).__name__)
        plane = get_plane()
        plane.register(f"{owner}:kv_cache", component="kv_cache",
                       tier="hbm", nbytes=int(kv_b), owner=owner)
        plane.register(f"{owner}:workspace", component="workspace",
                       tier="hbm", nbytes=int(ws_b), owner=owner)
        state_b = recurrent_state_bytes(self.model_cfg, b, self._config.dtype)
        if state_b:
            plane.register(f"{owner}:recurrent_state",
                           component="recurrent_state", tier="hbm",
                           nbytes=state_b, owner=owner)

    def _weight_bytes_per_step(self):
        """(at-rest, dense-equivalent) weight bytes one decode step reads —
        the telemetry pair that makes 'is this serve mode weight-read-bound
        where it should be' a one-line check. Cached; llama-layout trees
        use the layer-scan accounting (embed gather excluded), other trees
        fall back to whole-tree byte counts."""
        if self._weight_bytes_cache is None:
            from deepspeed_tpu.inference import quantized_layer_scan as qls
            from deepspeed_tpu.inference.quantization import is_quantized_leaf
            if getattr(self, "serve_mode", "dequant") == "capacity":
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

    def _auto_layouts(self) -> bool:
        al = getattr(self._config, "auto_layouts", None)
        if al is not None:
            return bool(al)
        return on_tpu()

    def _compile_auto_layout(self, jfn, input_ids, rng, key):
        """AOT-compile with AUTO input layouts and RE-PLACE self.params in
        the program's preferred layouts, leaf-by-leaf (rebinding each leaf
        so the old copy frees before the next relayouts — a whole-tree
        device_put would hold both layouts and OOM exactly the big models
        this exists for). Without this, XLA copies mismatched weight
        stacks to its preferred tiling INSIDE the program: +3 GB for a 7B
        llama's q/k/v, the difference between fitting a v5e and OOM.
        NOTE: the leaf-wise free only works when the ENGINE owns the sole
        reference to the placed params — callers keeping their own handle
        to the tree hold every old-layout leaf alive and reintroduce the
        2× residency (benchmarks/hf7b_decode.py drops its handle)."""
        # lower on ABSTRACT avals: concrete params already carry committed
        # formats (engine placement device_puts them), and AUTO refuses
        # committed-layout arguments. The avals keep the SHARDINGS: without
        # them the program is compiled for replicated inputs and refuses a
        # tensor-parallel tree.
        from deepspeed_tpu.telemetry.recompile import abstract_args
        from deepspeed_tpu.utils.layouts import (compiled_input_formats,
                                                 relayout_leaves)
        traced = jfn.trace(
            abstract_args(self.params),
            jax.ShapeDtypeStruct(input_ids.shape, input_ids.dtype),
            jax.ShapeDtypeStruct(rng.shape, rng.dtype))
        compiled = traced.lower().compile()
        # this executable IS the program that runs: the map is read off it
        keep_program(self._program_name(key), traced, mesh=self.mesh,
                     detector=self._detector_name(key))
        fmts = compiled_input_formats(compiled)[0]
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        fmt_leaves = jax.tree_util.tree_leaves(fmts[0])
        self.params = None  # drop the tree ref; leaves list keeps each alive
        try:
            relayout_leaves(leaves, fmt_leaves)
        finally:
            # even a mid-loop OOM must leave the engine with a usable
            # (mixed-layout) tree, not params=None
            self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        return lambda p, ids, r: compiled(
            p, jax.device_put(jnp.asarray(ids, jnp.int32), fmts[1]),
            jax.device_put(r, fmts[2]))

    def _build_generate(self, b, s, max_new_tokens, temperature, top_k,
                        top_p, eos_token_id, pad_token_id,
                        auto_layout: bool = False):
        from deepspeed_tpu.ops.sampling import sample_logits
        model, cfg = self.module, self._config
        layers, kv_heads, head_dim = _cache_dims(self.model_cfg)
        # Round the cache up to a lane-friendly multiple; validity is masked.
        max_len = -(-(s + max_new_tokens) // 128) * 128

        def sample(logits, rng):
            return sample_logits(logits, rng, temperature=temperature,
                                 top_k=top_k, top_p=top_p)

        kv_int8 = getattr(cfg, "kv_cache_dtype", None) == "int8"
        # a model that has a say in its cache builds it (`make_cache`): the
        # stacked view of the dense cache, which its layers address by index
        # (llama), or more than K and V (kv_cache.HybridCache). Every other
        # model gets the per-layer view. A model may also count inside the
        # program: its `program_counters` are summed over the call and
        # returned with the sequences.
        make_cache = getattr(model, "make_cache", None)
        counted = tuple(getattr(model, "program_counters", ()))

        def forward(params, ids, cache, counts):
            if not counted:
                return model.apply({"params": params}, ids, cache=cache) \
                    + (counts,)
            (logits, cache), sown = model.apply(
                {"params": params}, ids, cache=cache, mutable=["counters"])
            sown = sown.get("counters", {})
            return logits, cache, {
                name: _wide_add(counts[name], [
                    v for path, v in jax.tree_util.tree_leaves_with_path(sown)
                    if any(getattr(p, "key", None) == name for p in path)])
                for name in counted}

        def gen(params, ids, rng):
            params = self._maybe_dequant(params)
            if make_cache is not None:
                cache = make_cache(b, max_len, dtype=cfg.dtype,
                                   quantized=kv_int8)
            else:
                cache = KVCache.create(layers, b, max_len, kv_heads, head_dim,
                                       dtype=cfg.dtype, quantized=kv_int8)
            counts = {name: (jnp.zeros((), jnp.int32),) * 2
                      for name in counted}
            # `prefill`, `decode`, `sample`: scope names the program map
            # reads off the compiled text (docs/telemetry.md); metadata
            # only, no instruction and no schedule changes with them
            with jax.named_scope("prefill"):
                logits, cache, counts = forward(params, ids, cache, counts)
                rng, sub = jax.random.split(rng)
                with jax.named_scope("sample"):
                    tok = sample(logits[:, -1, :], sub)
            done = jnp.zeros((b,), jnp.bool_)
            if eos_token_id is not None:
                done = tok == eos_token_id

            def step(carry, rng_i):
                cache, tok, done, counts = carry
                logits, cache, counts = forward(params, tok[:, None], cache,
                                                counts)
                with jax.named_scope("sample"):
                    nxt = sample(logits[:, -1, :], rng_i)
                if eos_token_id is not None:
                    nxt = jnp.where(done, pad_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                return (cache, nxt, done, counts), tok

            keys = jax.random.split(rng, max_new_tokens - 1) if max_new_tokens > 1 \
                else jnp.zeros((0, 2), jnp.uint32)
            with jax.named_scope("decode"):
                (cache, last, done, counts), toks = jax.lax.scan(
                    step, (cache, tok, done, counts), keys)
            new = jnp.concatenate([toks.T, last[:, None]], axis=1) \
                if max_new_tokens > 1 else last[:, None]
            out = jnp.concatenate([ids, new], axis=1)
            return (out, counts) if counted else out

        # `jit_ds_v1_generate_b8_s128_n64` on the device trace's `XLA
        # Modules` line: the `compile` span's name, one module a key
        gen.__name__ = gen.__qualname__ = jit_name(self._program_name(
            (b, s, max_new_tokens, temperature, top_k, top_p, eos_token_id,
             pad_token_id)))
        if auto_layout:
            from deepspeed_tpu.utils.layouts import auto_input_format
            return jax.jit(gen, in_shardings=auto_input_format())
        return jax.jit(gen)

    # reference engine surface
    @property
    def config(self):
        return self._config

    def eval(self):
        return self

    def half(self):
        return self
