"""Speculative decoding — k-token draft-and-verify, orthogonal to serve mode.

Every serve mode pays one full weight pass per emitted token, and decode is
weight-read bound at every scale measured (470M ~3.5k tok/s, 7B bf16
162 tok/s at ~80% of the 13.5 GB/step bound, capacity mode PCIe-bound).
This module breaks that coupling: a cheap DRAFT proposes k tokens, then the
target scores all k+1 candidate positions in ONE batched forward — one
weight pass now emits `E[accepted] + 1` tokens. Speedup model
(docs/speculative_decoding.md):

    tok/s ≈ base_tok/s · E[accepted + 1] / (1 + k · c_draft)

where c_draft is the draft/target cost ratio per forward.

Draft flavors (models/draft.py):
  draft='self'  — the target with its layer stack gathered at
                  `draft_layers` evenly-spaced indices (structural-
                  compression layer reduction, sharing the checkpoint);
                  embed/norm/head are shared, the gather is in-program and
                  loop-invariant.
  draft='model' — any zoo model with a matching vocab, passed as
                  `draft_model=(module, params)`; parked device-resident.

Verification (ops/sampling.py):
  greedy (temperature == 0) — accept while `draft == argmax(target)`;
    the emitted chain IS the target's greedy chain, bit-exact vs vanilla
    `generate()` (the parity contract tests pin).
  sampling — the Leviathan/Chen rejection rule over the FILTERED
    distributions (`filtered_probs` / `speculative_accept`): accept d_i
    w.p. min(1, p_t/p_d), residual draw on reject, bonus draw on
    all-accept — the emitted tokens are distributed exactly as vanilla
    sampling's.

Staged-KV mapping: the dense `KVCache` cursor semantics ARE the stage —
everything past `index` is uncommitted. The k+1 verify forward writes the
candidate window beyond the committed cursor in the usual single batched
scatter (`update_layer`); acceptance "commits" by rolling the cursor to
`c + accepted + 1` (`KVCache.truncate`); rejected tokens never become
attendable (causal `decode_mask`) and the next round's window overwrites
them before anything attends there. Fixed shapes throughout: accept-length
is a dynamic index into a length-k+1 window; the whole multi-round decode
is ONE compiled `lax.while_loop` program per (b, s, new, sampling) key —
no per-length recompiles (the r4 fixed-shape-scatter lesson).

Round protocol (the invariant the acceptance fuzz tests exercise): with
committed target cursor c and draft cursor dci, the draft is fed a
fixed-width-2 "pend" catch-up segment — `[bonus, 0]` (pl=1) after a
rejection, `[d_k, bonus]` (pl=2) after all-accept, so dci + pl == c + 1
always — then scans k−1 single-token steps. The target verifies
`[last_emitted, d_1..d_k]`, acceptance truncates both caches, and the
accepted-run + bonus tokens land in a fixed (B, max_new) output buffer via
a drop-mode scatter at per-row `out_len` cursors.

Serve-mode matrix: dequant (any family, GSPMD meshes OK — the program is
pure XLA), layer_scan and capacity (llama-layout, single-device — same
bound as the modes themselves; the draft rides the same
`make_block_fn`-shaped stack forward so layer_scan/capacity spec parity
is exact by construction). The v2/FastGen engine is untouched.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.ops.sampling import (filtered_probs, sample_logits,
                                        speculative_accept)
from deepspeed_tpu.telemetry import annotate, get_hub
from deepspeed_tpu.utils.logging import logger


class SpecUnsupported(RuntimeError):
    """Raised (and caught by `maybe_create`) when speculative decoding
    cannot run on this engine's mesh/serve-mode combination — the engine
    warns and serves vanilla. User-config errors raise ValueError."""


# --------------------------------------------------------------- pure pieces
def draft_propose(d_fwd, d_set_index, dstate, pend, pl, c, keys, *,
                  k: int, temperature: float, top_k: int, top_p: float):
    """One round's draft side: feed the width-2 catch-up segment `pend`
    (valid length `pl` in {1, 2}, positions dci..dci+pl−1 with
    dci + pl == c + 1), truncate the draft cursor to c+1, then scan k−1
    single-token steps. Returns (drafts (B, k), draft_probs (B, k, V) or
    None when greedy, dstate). `keys` (k, 2): keys[0] draws the first
    proposal, keys[1:] the scan steps."""
    dlog, dstate = d_fwd(dstate, pend)
    dstate = d_set_index(dstate, c + 1)
    # proposal logits sit at slot pl−1 (the last VALID fed token); slot pl
    # onward saw a junk token, but causality keeps it out of slot pl−1's
    # attention and the draft cursor rollback un-stages its KV
    row = jnp.take_along_axis(dlog, (pl - 1)[:, None, None], axis=1)[:, 0]
    sampling = temperature != 0.0
    first = sample_logits(row, keys[0], temperature=temperature,
                          top_k=top_k, top_p=top_p)
    firstp = filtered_probs(row, temperature, top_k, top_p) if sampling \
        else None

    def step(carry, key_j):
        dstate, tok = carry
        lg, dstate = d_fwd(dstate, tok[:, None])
        r = lg[:, -1]
        nxt = sample_logits(r, key_j, temperature=temperature,
                            top_k=top_k, top_p=top_p)
        ys = (nxt, filtered_probs(r, temperature, top_k, top_p)) \
            if sampling else nxt
        return (dstate, nxt), ys

    (dstate, _), ys = lax.scan(step, (dstate, first), keys[1:])
    if sampling:
        toks, probs = ys
        drafts = jnp.concatenate(
            [first[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
        dprobs = jnp.concatenate(
            [firstp[:, None], jnp.moveaxis(probs, 0, 1)], axis=1)
    else:
        drafts = jnp.concatenate(
            [first[:, None], jnp.moveaxis(ys, 0, 1)], axis=1)
        dprobs = None
    return drafts, dprobs, dstate


def accept_commit(vlogits, drafts, dprobs, rng_acc, c, done, *,
                  temperature: float, top_k: int, top_p: float,
                  eos_token_id: Optional[int], pad_token_id: int):
    """One round's verdict, pure cursor/token math shared by every serve
    flavor. `vlogits` (B, k+1, V) are the target logits over the candidate
    window `[last_emitted, d_1..d_k]`; position i scores token i+1 of the
    chain. Returns (emit (B, k+1) — accepted run + bonus, eos/done-masked
    to pad; count (B,) tokens emitted; acc (B,) accepted drafts;
    pend (B, 2) + pl (B,) — next round's catch-up segment; c_new (B,) the
    committed target cursor; dci_new (B,) the committed draft cursor;
    done (B,))."""
    b, k = drafts.shape
    if temperature == 0.0:
        # lossless greedy: accept while the draft IS the target argmax —
        # the emitted chain equals vanilla greedy's by induction
        tgt = jnp.argmax(vlogits.astype(jnp.float32), axis=-1).astype(jnp.int32)
        match = (drafts == tgt[:, :k]).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1).astype(jnp.int32)
        bonus = jnp.take_along_axis(tgt, acc[:, None], axis=1)[:, 0]
    else:
        tprobs = filtered_probs(vlogits, temperature, top_k, top_p)
        acc, bonus = speculative_accept(rng_acc, drafts, dprobs, tprobs)
    c_new = c + acc + 1
    # the draft cache holds d_1..d_k's KV at c+1..c+k; after accepting
    # `acc` drafts the first dci_new = c + min(acc+1, k) positions are
    # real context. All-accept leaves d_k itself un-cached draft-side —
    # pend re-feeds it (with the bonus) next round; otherwise pend is
    # just the bonus. Invariant either way: dci_new + pl_new == c_new + 1.
    dci_new = c + jnp.minimum(acc + 1, k)
    pl_new = c_new + 1 - dci_new                               # ∈ {1, 2}
    all_acc = acc == k
    pend_new = jnp.stack(
        [jnp.where(all_acc, drafts[:, -1], bonus),
         jnp.where(all_acc, bonus, jnp.zeros_like(bonus))], axis=1)
    pos = jnp.arange(k + 1)[None, :]
    drafts_p = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    emit = jnp.where(pos == acc[:, None], bonus[:, None], drafts_p)
    count = acc + 1
    valid = pos < count[:, None]
    if eos_token_id is not None:
        # vanilla semantics: the FIRST eos is emitted, everything after it
        # (and everything on already-done rows) pads
        is_eos = jnp.logical_and(emit == eos_token_id, valid)
        seen_prior = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                      - is_eos.astype(jnp.int32)) > 0
        keep = jnp.logical_and(valid, jnp.logical_not(
            jnp.logical_or(done[:, None], seen_prior)))
        done = jnp.logical_or(done, jnp.any(is_eos, axis=1))
    else:
        keep = valid
    emit = jnp.where(keep, emit, pad_token_id).astype(jnp.int32)
    return emit, count, acc, pend_new, pl_new, c_new, dci_new, done


def make_spec_loop(*, b: int, s: int, max_new: int, k: int,
                   temperature: float, top_k: int, top_p: float,
                   eos_token_id: Optional[int], pad_token_id: int,
                   t_fwd, t_set_index, d_fwd, d_set_index):
    """The full speculative generate as one traced function over two
    forward adapters: `*_fwd(state, tokens (B, S)) → (logits (B, S, V),
    state)` appending at the state's cursor, `*_set_index(state, (B,)
    int32) → state` rolling the cursor back (stage truncation). Returns
    `loop(tstate, dstate, ids, rng) → (out_ids (B, s+max_new),
    stats (3,) int32 [rounds, drafted, accepted])` — same output shape
    and prompt-prefix convention as the vanilla generates."""

    def sample(logits, rng):
        return sample_logits(logits, rng, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def loop(tstate, dstate, ids, rng):
        # target prefill + first token — identical to vanilla generate
        logits, tstate = t_fwd(tstate, ids)
        rng, sub = jax.random.split(rng)
        tok0 = sample(logits[:, -1, :], sub)
        _, dstate = d_fwd(dstate, ids)          # draft prefill (logits DCE'd)
        done = jnp.zeros((b,), jnp.bool_)
        if eos_token_id is not None:
            done = tok0 == eos_token_id
        out = jnp.full((b, max_new), pad_token_id,
                       jnp.int32).at[:, 0].set(tok0)
        out_len = jnp.ones((b,), jnp.int32)
        c = jnp.full((b,), s, jnp.int32)
        pend = jnp.stack([tok0, jnp.zeros_like(tok0)], axis=1)
        pl = jnp.ones((b,), jnp.int32)
        stats = jnp.zeros((3,), jnp.int32)      # rounds, drafted, accepted

        def cond(carry):
            return jnp.any(carry[6] < max_new)

        def body(carry):
            tstate, dstate, pend, pl, c, out, out_len, done, rng, stats = carry
            active = out_len < max_new
            live = jnp.logical_and(active, jnp.logical_not(done))
            keys = jax.random.split(rng, k + 2)
            rng, acc_key, prop_keys = keys[0], keys[1], keys[2:]
            drafts, dprobs, dstate = draft_propose(
                d_fwd, d_set_index, dstate, pend, pl, c, prop_keys,
                k=k, temperature=temperature, top_k=top_k, top_p=top_p)
            t_last = jnp.take_along_axis(pend, (pl - 1)[:, None], axis=1)
            cand = jnp.concatenate([t_last, drafts], axis=1)   # (B, k+1)
            vlogits, tstate = t_fwd(tstate, cand)
            emit, count, acc, pend, pl, c, dci, done = accept_commit(
                vlogits, drafts, dprobs, acc_key, c, done,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, pad_token_id=pad_token_id)
            tstate = t_set_index(tstate, c)
            dstate = d_set_index(dstate, dci)
            pos = jnp.arange(k + 1)[None, :]
            col = jnp.where(
                jnp.logical_and(pos < count[:, None], active[:, None]),
                out_len[:, None] + pos, max_new)               # → drop
            out = out.at[jnp.arange(b)[:, None], col].set(emit, mode="drop")
            out_len = jnp.where(
                active, jnp.minimum(out_len + count, max_new), out_len)
            live_i = live.astype(jnp.int32)
            stats = stats + jnp.stack(
                [jnp.int32(1), k * jnp.sum(live_i),
                 jnp.sum(acc * live_i)])
            return (tstate, dstate, pend, pl, c, out, out_len, done, rng,
                    stats)

        carry = lax.while_loop(
            cond, body,
            (tstate, dstate, pend, pl, c, out, out_len, done, rng, stats))
        return jnp.concatenate([ids, carry[5]], axis=1), carry[9]

    return loop


def spec_cache_len(s: int, max_new_tokens: int, k: int) -> int:
    """Cache length for a speculative generate: the committed chain plus
    one full un-truncated candidate window past it, lane-rounded."""
    return -(-(s + max_new_tokens + k + 1) // 128) * 128


def spec_draft_bytes(spec: dict, model_cfg, dense_bytes: int,
                     kv_bytes: int) -> int:
    """Extra serving residency the draft adds — what `choose_serve_mode`
    folds into its overhead term: the draft's weight copy (a gathered
    fraction of the layer stacks for draft='self' — conservatively
    accounted at the DENSE at-rest size in every mode — or the draft
    model's own bytes) plus the draft KV cache (the same layer fraction
    of the target's)."""
    from deepspeed_tpu.models.draft import num_layers_of, resolve_draft_layers
    num_layers = num_layers_of(model_cfg)
    if spec.get("draft", "self") == "model":
        dm = spec.get("draft_model")
        if not dm:
            return 0
        w = sum(int(getattr(x, "nbytes", 0))
                for x in jax.tree_util.tree_leaves(dm[1]))
        frac = num_layers_of(dm[0].cfg) / max(1, num_layers)
        return int(w + frac * kv_bytes)
    try:
        idx = resolve_draft_layers(num_layers, spec.get("draft_layers", 0.5))
    except (ValueError, TypeError):
        return 0
    frac = len(idx) / max(1, num_layers)
    return int(frac * (dense_bytes + kv_bytes))


def _make_stack_forward(model_cfg, cache_dtype, max_len: int, fused: bool,
                        mesh=None):
    """A layer-stack forward over explicit stacked leaves — the
    `build_layer_scan_generate` inner forward, parameterized by WHICH
    stacks it scans so the same program body serves the layer_scan target,
    the layer_scan/capacity self-draft (a gathered sub-stack), and the
    capacity accept head. `forward(stacks, embed, norm_w, head, ids_cur,
    cache_k, cache_v, index) → (logits, cache_k, cache_v)`; caches are raw
    (L', B, max_len, Hkv, D) arrays, any seq width."""
    from deepspeed_tpu.inference.kv_cache import decode_mask
    from deepspeed_tpu.inference.quantized_layer_scan import (
        _rmsnorm, make_block_fn)
    from deepspeed_tpu.ops.attention import rope_cos_sin

    cfg = model_cfg
    dtype = cfg.dtype
    hd = cfg.head_dim
    eps = cfg.rms_norm_eps
    window = getattr(cfg, "sliding_window", None)
    block = make_block_fn(cfg, fused=fused, mesh=mesh)

    def forward(stacks, embed, norm_w, head, ids_cur, cache_k, cache_v,
                index):
        bsz, sl = ids_cur.shape
        h = jnp.take(embed, ids_cur, axis=0)
        positions = index[:, None] + jnp.arange(sl)[None, :]
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, dtype)
        mask = decode_mask(positions, max_len, window=window)
        aux = (cos, sin, index, mask)

        def body(h, xs):
            lp, k_l, v_l = xs
            h, (k_new, v_new) = block(h, lp, aux, (k_l, v_l))
            return h, (k_new, v_new)

        h, (cache_k, cache_v) = lax.scan(body, h, (stacks, cache_k, cache_v))
        h = _rmsnorm(h, norm_w, eps, dtype)
        if head is None:
            logits = jnp.einsum("bsd,vd->bsv", h, embed)
        else:
            logits = h @ head.astype(dtype)
        return logits, cache_k, cache_v

    return forward


# ------------------------------------------------------------------ decoder
class SpeculativeDecoder:
    """Engine-owned speculative decode dispatcher. Built by the v1 engine
    when `speculative={"enabled": True, ...}`; `engine.generate` routes
    here, so spec decode inherits the engine's program-per-key caching,
    RecompileDetector pinning and serving
    telemetry (plus the spec fields — docs/telemetry.md).

    Config keys: `k` (draft depth, default 4), `draft` ('self' | 'model'),
    `draft_layers` (self flavor: float depth ratio, int count, or explicit
    index list — default 0.5), `draft_model` ((module, params), model
    flavor)."""

    def __init__(self, engine, spec: dict):
        from deepspeed_tpu.models.draft import (make_draft_module,
                                                num_layers_of,
                                                resolve_draft_layers)
        from deepspeed_tpu.ops.pallas.sharded import nontrivial_axes
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.engine = engine
        self.k = int(spec.get("k", 4))
        if self.k < 1:
            raise ValueError("speculative: k must be >= 1")
        self.flavor = str(spec.get("draft", "self"))
        if self.flavor not in ("self", "model"):
            raise ValueError(
                f"speculative: draft={self.flavor!r} (expected 'self' or "
                "'model')")
        mode = getattr(engine, "serve_mode", "dequant")
        nt = nontrivial_axes(engine.mesh)
        if nt and mode in ("layer_scan", "capacity"):
            # same bound as the modes' own kernels: the spec programs ride
            # pallas calls / a single device's host loop
            raise SpecUnsupported(
                f"serve_mode={mode!r} speculative decoding is "
                f"single-device (mesh axes {sorted(nt)} nontrivial)")
        self._jit = {}
        self._cap_jit = {}
        # generate key -> detector program name (tpuverify registration)
        self._program_names = {}
        self._draft_module = None
        self._draft_params = None
        self._draft_idx = None
        self._stack_key = None
        self.last_acceptance_rate: Optional[float] = None
        target_layers = num_layers_of(engine.model_cfg)
        if self.flavor == "model":
            dm = spec.get("draft_model")
            if not (isinstance(dm, tuple) and len(dm) == 2):
                raise ValueError(
                    "speculative: draft='model' needs "
                    "draft_model=(module, params)")
            dmod, dparams = dm
            if int(dmod.cfg.vocab_size) != int(engine.model_cfg.vocab_size):
                raise ValueError(
                    "speculative: draft model vocab_size "
                    f"{dmod.cfg.vocab_size} != target "
                    f"{engine.model_cfg.vocab_size}")
            self._draft_module = dmod
            sharding = NamedSharding(engine.mesh, P())

            def place(x):
                x = jnp.asarray(x)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(engine._config.dtype)
                return jax.device_put(x, sharding)

            self._draft_params = jax.tree_util.tree_map(place, dparams)
        else:
            self._draft_idx = resolve_draft_layers(
                target_layers, spec.get("draft_layers", 0.5))
            if mode == "dequant":
                from deepspeed_tpu.models.draft import layer_stack_key
                # detect on the DENSE tree shape — quantized at-rest trees
                # carry flat scales the shape probe would trip on
                dense_abs = jax.eval_shape(engine._maybe_dequant,
                                           engine.params)
                self._stack_key = layer_stack_key(dense_abs, target_layers)
                self._draft_module = make_draft_module(
                    engine.module, len(self._draft_idx))
            else:
                self._stack_key = "layers"   # llama layout by construction
        if mode == "capacity" and self.flavor == "self":
            self._cap_draft_stacks = self._gather_capacity_stacks()
        self._register_draft_residency()
        logger.info(
            f"speculative decoding: k={self.k}, draft={self.flavor}"
            + (f" layers={list(self._draft_idx)}" if self._draft_idx else "")
            + f", serve_mode={mode}")

    @classmethod
    def maybe_create(cls, engine) -> Optional["SpeculativeDecoder"]:
        """The engine's entry point: None when spec decoding is off or
        structurally unsupported here (warned — the engine serves
        vanilla); user-config errors still raise."""
        spec = getattr(engine._config, "speculative", None)
        if not (spec and spec.get("enabled")):
            return None
        try:
            return cls(engine, dict(spec))
        except SpecUnsupported as e:
            logger.warning(f"speculative decoding disabled: {e}")
            return None

    def _register_draft_residency(self):
        """MemoryPlane spec_draft rows under the ENGINE owner (released
        with the engine's placement). Only the flavors that hold EXTRA
        device arrays register bytes — resident self-draft slices the
        target's own stacks in-program, so its marginal residency is 0."""
        from deepspeed_tpu.telemetry.memory import (get_plane, owner_for,
                                                    tree_bytes)
        owner = owner_for(self.engine, type(self.engine).__name__)
        extra = None
        if self.flavor == "model":
            extra = self._draft_params
        elif getattr(self, "_cap_draft_stacks", None) is not None:
            extra = self._cap_draft_stacks
        if extra is not None:
            get_plane().register(f"{owner}:spec_draft",
                                 component="spec_draft", tier="hbm",
                                 nbytes=tree_bytes(extra), owner=owner)

    # -------------------------------------------------------- draft tiers
    def _gather_capacity_stacks(self):
        """Capacity mode's self-draft: the draft layers must be DEVICE
        resident (streaming them too would erase the whole win), so gather
        the per-layer host slices into leading-L_d stacks once. Costs
        `len(draft_layers)` slices of HBM — `spec_draft_bytes` accounts
        it; capacity stays for FIT, spec makes each stream worth k+1
        tokens."""
        runner = self.engine._capacity
        trees = [runner._layer_tree(
                    [jnp.asarray(x) for x in runner._host_slice(l)])
                 for l in self._draft_idx]
        stacks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        return jax.device_put(stacks, runner._sharding)

    # ----------------------------------------------------------- programs
    def _build_resident(self, key):
        """The fused draft+verify program for the device-resident serve
        modes (dequant: model.apply over the zoo module; layer_scan: the
        stack forward over quantized leaves). One jit per key, signature
        (params, draft_params_or_None, ids, rng)."""
        eng = self.engine
        b, s, new, temperature, top_k, top_p, eos, pad = key
        k = self.k
        mode = eng.serve_mode
        max_len = spec_cache_len(s, new, k)
        loop_kw = dict(b=b, s=s, max_new=new, k=k, temperature=temperature,
                       top_k=top_k, top_p=top_p, eos_token_id=eos,
                       pad_token_id=pad)
        flavor, dmod = self.flavor, self._draft_module
        from deepspeed_tpu.inference.engine import _cache_dims
        from deepspeed_tpu.inference.kv_cache import KVCache
        if dmod is not None:
            dl, dkv, dhd = _cache_dims(dmod.cfg)

        def kv_set(cache, ix):
            return cache.truncate(ix)

        if mode == "dequant":
            model, cfg = eng.module, eng._config
            tl, tkv, thd = _cache_dims(eng.model_cfg)
            # int8-at-rest KV composes: per-(head, slot) scales depend only
            # on each written token's own values, so the cache contents are
            # identical whether tokens land via verify chunks or one-by-one
            # — greedy spec stays bit-exact vs vanilla at the same kv dtype
            kv_int8 = getattr(cfg, "kv_cache_dtype", None) == "int8"
            idx_arr = (jnp.asarray(self._draft_idx, jnp.int32)
                       if self._draft_idx is not None else None)
            stack_key = self._stack_key

            def gen(params, dparams, ids, rng):
                tparams = eng._maybe_dequant(params)
                if dparams is None:
                    from deepspeed_tpu.models.draft import take_layer_stack
                    dparams = take_layer_stack(tparams, stack_key, idx_arr)
                t_fwd = lambda cache, toks: model.apply(
                    {"params": tparams}, toks, cache=cache)
                d_fwd = lambda cache, toks: dmod.apply(
                    {"params": dparams}, toks, cache=cache)
                loop = make_spec_loop(t_fwd=t_fwd, t_set_index=kv_set,
                                      d_fwd=d_fwd, d_set_index=kv_set,
                                      **loop_kw)
                return loop(
                    KVCache.create(tl, b, max_len, tkv, thd, dtype=cfg.dtype,
                                   quantized=kv_int8),
                    KVCache.create(dl, b, max_len, dkv, dhd, dtype=cfg.dtype,
                                   quantized=kv_int8),
                    ids, rng)

            return jax.jit(gen)

        # layer_scan
        mcfg, icfg = eng.model_cfg, eng._config
        dtype = mcfg.dtype
        nkv, hd = mcfg.num_key_value_heads, mcfg.head_dim
        num_layers = mcfg.num_hidden_layers
        fwd = _make_stack_forward(mcfg, icfg.dtype, max_len,
                                  fused=eng._use_fused_int8())
        idx_arr = (jnp.asarray(self._draft_idx, jnp.int32)
                   if self._draft_idx is not None else None)

        def arr_set(st, ix):
            return (st[0], st[1], ix)

        def gen(params, dparams, ids, rng):
            layers = params["layers"]
            embed = params["embed_tokens"].astype(dtype)
            norm_w = params["norm"]["weight"]
            head = params.get("lm_head")

            def stack_fwd(stacks):
                def f(st, toks):
                    ck, cv, ix = st
                    logits, ck, cv = fwd(stacks, embed, norm_w, head, toks,
                                         ck, cv, ix)
                    return logits, (ck, cv, ix + toks.shape[1])
                return f

            def arr_state(n_layers):
                z = jnp.zeros((n_layers, b, max_len, nkv, hd), icfg.dtype)
                return (z, jnp.zeros_like(z), jnp.zeros((b,), jnp.int32))

            if flavor == "self":
                # gathered ONCE at program top — loop-invariant, so the
                # while_loop reads a resident sub-stack, not a per-round
                # gather (int8 leaves gather as int8: f·int8 residency)
                dlayers = jax.tree_util.tree_map(
                    lambda x: jnp.take(x, idx_arr, axis=0), layers)
                d_fwd, d_set = stack_fwd(dlayers), arr_set
                dstate = arr_state(len(self._draft_idx))
            else:
                d_fwd = lambda cache, toks: dmod.apply(
                    {"params": dparams}, toks, cache=cache)
                d_set = kv_set
                dstate = KVCache.create(dl, b, max_len, dkv, dhd,
                                        dtype=icfg.dtype)
            loop = make_spec_loop(t_fwd=stack_fwd(layers),
                                  t_set_index=arr_set, d_fwd=d_fwd,
                                  d_set_index=d_set, **loop_kw)
            return loop(arr_state(num_layers), dstate, ids, rng)

        return jax.jit(gen)

    def _cap_programs(self, key):
        """Capacity flavor: the verify still streams layers through the
        runner's double-buffered `_pass`; the draft runs in three small
        device programs over the RESIDENT tier (prefill / propose /
        accept — the accept closes over norm/embed/head exactly like the
        runner's head program)."""
        if key in self._cap_jit:
            return self._cap_jit[key]
        eng = self.engine
        runner = eng._capacity
        b, s, new, temperature, top_k, top_p, eos, pad = key
        k = self.k
        mcfg = runner.model_cfg
        dtype = mcfg.dtype
        max_len = spec_cache_len(s, new, k)
        from deepspeed_tpu.inference.quantized_layer_scan import _rmsnorm
        embed = runner.resident["embed_tokens"].astype(dtype)
        norm_w = runner.resident["norm"]["weight"]
        head = runner.resident.get("lm_head")
        eps = mcfg.rms_norm_eps
        if self.flavor == "self":
            fwd = _make_stack_forward(mcfg, runner.infer_cfg.dtype, max_len,
                                      fused=eng._use_fused_int8())
            stacks = self._cap_draft_stacks
            nkv, hd = mcfg.num_key_value_heads, mcfg.head_dim
            n_draft = len(self._draft_idx)

            def d_fwd(st, toks):
                ck, cv, ix = st
                logits, ck, cv = fwd(stacks, embed, norm_w, head, toks,
                                     ck, cv, ix)
                return logits, (ck, cv, ix + toks.shape[1])

            def d_set(st, ix):
                return (st[0], st[1], ix)

            def d_init():
                z = jnp.zeros((n_draft, b, max_len, nkv, hd),
                              runner.infer_cfg.dtype)
                return (z, jnp.zeros_like(z), jnp.zeros((b,), jnp.int32))
        else:
            from deepspeed_tpu.inference.engine import _cache_dims
            from deepspeed_tpu.inference.kv_cache import KVCache
            dmod, dparams = self._draft_module, self._draft_params
            dl, dkv, dhd = _cache_dims(dmod.cfg)
            d_fwd = lambda cache, toks: dmod.apply(
                {"params": dparams}, toks, cache=cache)

            def d_set(cache, ix):
                return cache.truncate(ix)

            def d_init():
                return KVCache.create(dl, b, max_len, dkv, dhd,
                                      dtype=runner.infer_cfg.dtype)

        def prefill_fn(ids):
            _, dstate = d_fwd(d_init(), ids)
            return dstate

        def propose_fn(dstate, pend, pl, c, keys):
            drafts, dprobs, dstate = draft_propose(
                d_fwd, d_set, dstate, pend, pl, c, keys,
                k=k, temperature=temperature, top_k=top_k, top_p=top_p)
            t_last = jnp.take_along_axis(pend, (pl - 1)[:, None], axis=1)
            cand = jnp.concatenate([t_last, drafts], axis=1)
            return cand, drafts, dprobs, dstate

        def accept_fn(h, drafts, dprobs, key_acc, c, done):
            hn = _rmsnorm(h, norm_w, eps, dtype)
            logits = jnp.einsum("bsd,vd->bsv", hn, embed) if head is None \
                else hn @ head.astype(dtype)
            return accept_commit(logits, drafts, dprobs, key_acc, c, done,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p, eos_token_id=eos,
                                 pad_token_id=pad)

        progs = {"prefill": jax.jit(prefill_fn),
                 "propose": jax.jit(propose_fn),
                 "accept": jax.jit(accept_fn), "max_len": max_len}
        self._cap_jit[key] = progs
        return progs

    def _capacity_generate(self, key, ids, rng):
        """Host-driven spec rounds over the capacity runner: draft-propose
        on the resident tier, ONE streamed layer sweep verifies k+1
        positions — k accepted tokens per host→HBM weight stream is a
        direct multiplier on the PCIe-bound throughput model."""
        eng = self.engine
        runner = eng._capacity
        b, s, new, temperature, top_k, top_p, eos, pad = key
        k = self.k
        progs = self._cap_programs(key)
        max_len = progs["max_len"]
        embed_jit = runner._programs(max_len)
        head_jit = runner._head_program(temperature, top_k, top_p, eos, pad)
        runner.last_prefetch_stall_ms = 0.0
        mcfg = runner.model_cfg
        cache_k = [jnp.zeros((b, max_len, mcfg.num_key_value_heads,
                              mcfg.head_dim), runner.infer_cfg.dtype)
                   for _ in range(runner.num_layers)]
        cache_v = [jnp.zeros_like(x) for x in cache_k]
        ids = jnp.asarray(ids, jnp.int32)
        h, aux = embed_jit(ids, jnp.zeros((b,), jnp.int32), max_len)
        h = runner._pass(h, aux, cache_k, cache_v)
        rng, sub = jax.random.split(rng)
        tok0, done = head_jit(h, sub, jnp.zeros((b,), jnp.bool_))
        dstate = progs["prefill"](ids)
        out = np.full((b, new), int(pad), np.int32)
        out[:, 0] = np.asarray(tok0)
        out_len = np.ones((b,), np.int64)
        c = jnp.full((b,), s, jnp.int32)
        pend = jnp.stack([tok0, jnp.zeros_like(tok0)], axis=1)
        pl = jnp.ones((b,), jnp.int32)
        rounds = drafted = accepted = 0
        # same wall-clock budget as the runner's own decode loop: the spec
        # round loop is host-driven too and must fail loudly, not hang
        from deepspeed_tpu.resilience.retry import Deadline
        deadline = Deadline(runner.dispatch_deadline_s,
                            "speculative capacity generate")
        while np.any(out_len < new):
            deadline.check(f"round {rounds}")
            # host-driven round protocol: acceptance must land on host to
            # advance the cursors — this loop runs once per k+1 tokens,
            # not per token, and the batched fetch below is the one sync
            done_before = np.asarray(done)  # tpulint: disable=no-hot-loop-fetch
            keys = jax.random.split(rng, k + 2)
            rng, acc_key, prop_keys = keys[0], keys[1], keys[2:]
            cand, drafts, dprobs, dstate = progs["propose"](
                dstate, pend, pl, c, prop_keys)
            h, aux = embed_jit(cand, c, max_len)
            h = runner._pass(h, aux, cache_k, cache_v)
            emit, count, acc, pend, pl, c, dci, done = progs["accept"](
                h, drafts, dprobs, acc_key, c, done)
            # draft cursor rollback = stage truncation, host-side
            if isinstance(dstate, tuple):
                dstate = (dstate[0], dstate[1], dci)
            else:
                dstate = dstate.replace(index=dci)
            # the ONE batched per-round fetch (emit+count+acc together)
            emit_np, count_np, acc_np = jax.device_get((emit, count, acc))  # tpulint: disable=no-hot-loop-fetch
            active = out_len < new
            cols = out_len[:, None] + np.arange(k + 1)[None, :]
            valid = ((np.arange(k + 1)[None, :] < count_np[:, None])
                     & active[:, None] & (cols < new))
            r, p = np.nonzero(valid)
            out[r, cols[r, p]] = emit_np[r, p]
            out_len = np.where(active, np.minimum(out_len + count_np, new),
                               out_len)
            rounds += 1
            live = active & ~done_before
            drafted += int(k * live.sum())
            accepted += int(np.where(live, acc_np, 0).sum())
        full = np.concatenate([np.asarray(ids), out], axis=1)
        return full, np.array([rounds, drafted, accepted], np.int64)

    # ----------------------------------------------------------- dispatch
    def generate(self, input_ids, max_new_tokens: int = 128,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: Optional[int] = None,
                 seed: int = 0, pad_token_id: int = 0):
        eng = self.engine
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        key = (b, s, int(max_new_tokens), float(temperature), int(top_k),
               float(top_p), eos_token_id, pad_token_id)
        rng = jax.random.PRNGKey(seed)
        if eng.serve_mode == "capacity":
            self._cap_programs(key)
        elif key not in self._jit:
            self._jit[key] = self._build_resident(key)
        return self._dispatch(key, input_ids, rng)

    def _program_name(self, key) -> str:
        """Stable name of one spec generate key's program (the v1
        engine's `_program_name`, for the spec family)."""
        name = f"v1:spec:b{key[0]}_s{key[1]}_n{key[2]}"
        from deepspeed_tpu.ops.pallas.sharded import mesh_fingerprint
        fp = mesh_fingerprint(self.engine.mesh)
        return f"{name}@{fp}" if fp else name

    def _dispatch(self, key, input_ids, rng):
        import time as _time
        eng = self.engine
        b, new = key[0], key[2]
        mode = eng.serve_mode
        program = f"spec_{mode}"
        from deepspeed_tpu.ops.pallas.sharded import mesh_fingerprint
        fp = mesh_fingerprint(eng.mesh)
        if fp:
            program = f"{program}@{fp}"
        from deepspeed_tpu.resilience.faults import fault_point
        fault_point("generate_dispatch", label=program)
        self._program_names[key] = f"{program}:{key}"
        eng.recompiles.observe(f"{program}:{key}",
                               (eng.params, input_ids, rng))
        t0 = _time.perf_counter()
        with annotate("ds:spec_generate"):
            if mode == "capacity":
                out, stats = self._capacity_generate(key, input_ids, rng)
            else:
                out, stats = jax.device_get(self._jit[key](
                    eng.params, self._draft_params, input_ids, rng))
        dt = _time.perf_counter() - t0
        out = np.asarray(out)
        rounds, drafted, accepted = (int(x) for x in np.asarray(stats))
        eng.last_decode_tok_s = (b * new / dt) if dt > 0 else None
        self.last_acceptance_rate = (accepted / drafted) if drafted else None
        hub = get_hub()
        if hub.enabled:
            wb, wb_dense = eng._weight_bytes_per_step()
            extra = {}
            if mode == "capacity":
                extra = {"h2d_bytes_step": eng._capacity.last_h2d_bytes_step,
                         "prefetch_stall_ms": round(
                             eng._capacity.last_prefetch_stall_ms, 3)}
            hub.emit("serving", engine="v1", queries=int(b), new_tokens=new,
                     decode_tok_s=round(eng.last_decode_tok_s, 1)
                     if eng.last_decode_tok_s else None,
                     serve_mode=mode,
                     weight_bytes_step=wb,
                     weight_bytes_step_dense=wb_dense,
                     recompiles=eng.recompiles.misses,
                     pinned_recompiles=eng.recompiles.pinned_misses,
                     speculative=True, spec_k=self.k,
                     draft_tokens_step=round(drafted / rounds, 3)
                     if rounds else 0.0,
                     accepted_tokens_step=round(accepted / rounds, 3)
                     if rounds else 0.0,
                     acceptance_rate=round(accepted / drafted, 4)
                     if drafted else None,
                     **eng._kv_telemetry(b, key[1], key[2]),
                     **extra)
        return out
