"""What the v1 hybrid families share (`nemotron_h`, `phi4flash`,
`ling_linear`, `keye_sparse`, `deepseek_sparse`, `openpangu`, `afmoe`,
`qwen3_next`): a
decision several of them must know lives here ONCE, and no family file
imports another. How a prefill is CUT (`row_groups` under the family's
`PREFILL_TOKENS`, `prefill_walk` under its `PREFILL_CHUNK`); what a serving
pass hands the HEAD (`causal_lm`, the shell around a family's `Layers`, and
its pieces for the two families whose shell differs); how a held share of
experts is BUILT (`held_experts`, beside `DenseFFN`); the delta rule over a
sequence (`delta_chunked`: a decay a channel, Ling's, or a head, Qwen3-Next's;
`delta_prefill`: a serving prefill's choice between it and the kernel, the
norms a head round it included);
the entry points a
family's module binds (`entry_points`), every `make_cache`'s int8 refusal,
two initialisers, the index key's epsilon.

A family file holds its config, its mixers, its `Layers` walk, its
`make_cache` and its counters. `models/common.py` is what the llama-layout
files share, `models/latent.py` what the compressed-query MLA families do.
`tools/hybrid_lowered.py` lowers every family's three programs: a change
here that means to change no program proves it there.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import (abstract_specs, causal_lm_loss,
                                         make_causal_loss_fn, materialize)
from deepspeed_tpu.models.llama import RMSNorm, _dense
from deepspeed_tpu.utils.partitioning import BATCH_AXES, shard_along

F32 = jnp.float32
INDEX_NORM_EPS = 1e-6   # an index key's LayerNorm (the two learned selections)
DELTA_CHUNK = 32    # positions a block of the chunked form (`delta_chunked`)
L2_EPS = 1e-6       # under the root of a delta rule's q / k norm


def a_log_init(key, shape, dtype=F32):
    """`A` uniform in [1, 16), as its log (Mamba-2's; KDA's gate keeps it)."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(dtype)


def dt_bias_init(cfg):
    def init(key, shape, dtype=F32):
        # dt drawn log-uniform in [time_step_min, time_step_max], stored as
        # its inverse softplus (Mamba's own initialisation)
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def delta_dt_bias_init(key, shape, dtype=F32):
    """The inverse softplus of dt log-uniform in [0.001, 0.1] (Mamba's, which
    the open delta-rule implementations keep for their gates' bias)."""
    lo, hi = math.log(0.001), math.log(0.1)
    dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def l2_normalised(x):
    """x over its last axis' L2 norm (a delta rule's queries and keys)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def short_conv(conv_w, window, s: int):
    """The causal depthwise convolution of a delta-rule mixer, float32:
    `window` (B, S + K - 1, C) is the K - 1 inputs before the S new ones and
    those, `conv_w` (K, C). Returns (B, S, C)."""
    w32 = window.astype(F32)
    if s == 1:
        return jnp.einsum("kc,bkc->bc", conv_w, w32)[:, None]
    return sum(conv_w[j] * w32[:, j:j + s] for j in range(conv_w.shape[0]))


class DenseFFN(nn.Module):
    """`W_down(silu(W_gate x) * W_up x)` at `intermediate_size`."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate, up = (_dense(cfg.intermediate_size, ("embed", "mlp"), cfg.dtype,
                           name)(x) for name in ("gate_proj", "up_proj"))
        return _dense(cfg.hidden_size, ("mlp_in", "embed"), cfg.dtype,
                      "down_proj")(jax.nn.silu(gate) * up)


# What `moe/layer.MoE._held` counts a call, the head of every held-expert
# family's `program_counters` (the engine sums them inside its generate
# programs: docs/telemetry.md)
EXPERT_COUNTERS = ("assignments", "held_assignments", "held_wide_calls",
                   "experts_touched", "experts_held", "weight_tile_revisits")


def held_experts(cfg, name: str, *, held: int, activation: str, score_fn: str,
                 shared=None, shared_gate: bool = False):
    """The expert layer as `moe/layer.MoE` computes it: `held` experts (the
    family passes the number: the configs name it differently) of the
    `router_experts` the router scores (None: all are held) from
    `expert_offset` on, of `activation` (`relu2` / `silu`), a shared expert
    of width `shared` beside them (None: none; `shared_gate`: its result
    times a sigmoid gate a token), nothing dropped by capacity.
    `score_fn` `softmax`: the taken ones' weights over their sum. `sigmoid`:
    besides, the selection bias in the CHOICE only (none where the family's
    `router_bias_scale` is None), the choice limited by groups (`n_group` 1:
    the best of all at once), the weights times `routed_scaling_factor`."""
    from deepspeed_tpu.moe.layer import MoE
    router = {}
    if score_fn == "sigmoid":
        biased = cfg.router_bias_scale is not None
        router = dict(
            selection_bias=biased,
            bias_init=nn.initializers.normal(cfg.router_bias_scale) if biased
            else nn.initializers.zeros_init(),
            routed_scaling_factor=cfg.routed_scaling_factor,
            n_group=cfg.n_group, topk_group=cfg.topk_group)
    return MoE(
        hidden_size=cfg.hidden_size, num_experts=cfg.router_experts or held,
        k=cfg.num_experts_per_tok,
        intermediate_size=cfg.moe_intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob, drop_tokens=False,
        dtype=cfg.dtype, activation=activation,
        dispatch_impl=cfg.dispatch_impl, score_fn=score_fn,
        held_offset=cfg.expert_offset, held_experts=held,
        shared_intermediate_size=shared, shared_gate=shared_gate, name=name,
        **router)


# ------------------------------------------- the delta rule over a sequence


def _neumann_inverse(a):
    """`(I + a)^-1` for STRICTLY lower triangular `a` (..., C, C): the
    product of `I + (-a)^(2^i)`, exact since `a^C = 0`."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv, power = eye - a, a @ a
    for _ in range(max(0, math.ceil(math.log2(c)) - 1)):
        inv, power = inv + inv @ power, power @ power
    return inv


def delta_chunked(q, k, v, g, beta, s0, chunk: int = DELTA_CHUNK):
    """The gated delta rule (KDA's, a decay a channel; Gated DeltaNet's, a
    decay a head) over a sequence in CHUNKS: inside a block of
    `chunk` positions the delta rule's dependence of each token on the ones
    before it is a unit lower triangular system (the WY form), solved by
    matrix products; the state is carried between blocks. Exact against the
    recurrence (`ops/pallas/kda.kda_step` a position).

    q, k (B, S, H, dk) as they enter the recurrence; v (B, S, H, dv); g
    (B, S, H, dk) log-decay <= 0, or (B, S, H): a decay a HEAD; beta (B, S,
    H); s0 (B, H, dk, dv), a head's `S`: float32, products at `highest`. Returns (o (B, S, H, dv), the
    state after position S - 1). Any S: the tail of the last block is padded
    with g = 0, beta = 0, which leaves the state as it is.

    With `G_i` the cumulative log-decay inside a block, the decay between
    two of its positions, `exp(G_i - G_j)` a channel, is taken as
    `exp(G_i - r) exp(r - G_j)` about the block's MIDDLE `r`: each exponent
    is then at most `chunk / 2` steps' worth, 80 at the bound of -5 a step,
    inside float32 either way (a whole block's, 160, is not).

    A decay a HEAD is the cheaper special case: the decay between two
    positions is ONE number, `exp(G_i - G_j) <= 1` for `j <= i` taken as it
    stands (nothing to anchor: no exponent is positive), and it multiplies
    the `(C, C)` products of the plain keys and queries; the two products a
    channel (`up`, `down`) are gone."""
    bsz, s, nh, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (s + pad) // chunk
    # (nc, B, H, C, ...): the scan slices blocks, a head's rows are matrices
    blocks = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape((bsz, nc, chunk) + t.shape[2:]), (1, 3), (0, 2))
    head = g.ndim == beta.ndim                      # a decay a head
    q, k, v, g, beta = (blocks(t) for t in (q, k, v, g, beta))
    if head:
        g = g[..., None]                # (nc, B, H, C, 1): over the channels
    cum = jnp.cumsum(g, axis=-2)                               # G_i, inclusive
    if not head:
        mid = cum[..., chunk // 2 - 1:chunk // 2, :]
        up, down = jnp.exp(cum - mid), jnp.exp(mid - cum)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    with jax.default_matmul_precision("highest"):
        if head:
            between = jnp.exp(jnp.where(    # exp(G_i - G_j), j <= i; else 0
                lower, cum - jnp.swapaxes(cum, -1, -2), -jnp.inf))
            pairs = lambda t: jnp.einsum(  # noqa: E731
                "...ic,...jc->...ij", t, k) * between
        else:
            k_down = k * down
            pairs = lambda t: jnp.einsum(  # noqa: E731
                "...ic,...jc->...ij", t * up, k_down)
        # a[i, j] = beta_i sum_c k_i k_j exp(G_i - G_j), j < i
        a = jnp.where(lower & ~jnp.eye(chunk, dtype=bool), pairs(k), 0.0)
        solve = _neumann_inverse(a * beta[..., None])          # (I + A)^-1
        # p[i, j] = sum_c q_i k_j exp(G_i - G_j), j <= i
        p = jnp.where(lower, pairs(q), 0.0)
        decay = jnp.exp(cum)                                   # from the start
        k_in, q_in = k * decay, q * decay
        to_end = k * jnp.exp(cum[..., -1:, :] - cum)
        whole = decay[..., -1, :]                              # (nc, B, H, dk)

        def block(state, blk):
            k_in, q_in, v, beta, solve, p, to_end, whole = blk
            # u_i = beta_i (v_i - S'^T k_i): the rows of (I + A) U = rhs
            rhs = beta[..., None] * (v - k_in @ state)
            u = solve @ rhs                                    # (B, H, C, dv)
            o = q_in @ state + p @ u
            state = state * whole[..., :, None] + jnp.einsum(
                "...ic,...iv->...cv", to_end, u)
            return state, o

        s_last, o = jax.lax.scan(
            block, s0, (k_in, q_in, v, beta, solve, p, to_end, whole))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(bsz, nc * chunk, nh, dv)
    return o[:, :s], s_last


def recurrence_keys(q, k):
    """(q, k) as they enter a delta rule's recurrence: each over its last
    axis' L2 norm, q times `d_k ** -0.5`."""
    return l2_normalised(q) * q.shape[-1] ** -0.5, l2_normalised(k)


def head_norm(o, weight, eps: float):
    """The RMS norm over each head's d_v (a PLAIN weight), float32."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                             + eps) * weight.astype(F32)


def delta_prefill_reference(q, k, v, g, beta, s0, chunk: int, norm_weight,
                            norm_eps: float):
    """`delta_prefill` in plain `jax.numpy`: `recurrence_keys`, the keys
    repeated over their value heads, `delta_chunked`, `head_norm`."""
    q, k = recurrence_keys(q, k)
    if (rep := v.shape[2] // q.shape[2]) > 1:
        q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))
    o, last = delta_chunked(q, k, v, g, beta, s0, chunk)
    return head_norm(o, norm_weight, norm_eps), last


def delta_prefill(q, k, v, g, beta, s0, chunk: int, norm_weight,
                  norm_eps: float):
    """A SERVING prefill's delta rule (a mixer's `state is not None`, S > 1;
    never differentiated) between the convolution and the gate: the keys'
    norms, the chunked rule over (B, S) from the stored state `s0`, the
    heads' norm. q and k as the convolution leaves them, (B, S, Hk, dk), key
    head j serving value heads (Hv / Hk) j .. of v (B, S, Hv, dv); `g` of
    `beta`'s rank is a decay a head, one rank more a decay a channel.
    Returns (o normalised, the state after).

    On the chip in a one-device program, at whole lane tiles of d_k and
    d_v, ONE Pallas call that keeps a head's state in VMEM over the blocks
    and takes the two norms in on its way (`ops/pallas/delta_rule.py`: there
    a head's channels are a vreg's lanes; XLA re-lays each operand twice for
    them); elsewhere `delta_prefill_reference`. Which one a trace took is
    counted on the telemetry hub (`delta_prefill/kernel`,
    `delta_prefill/chunked`: counts of TRACES)."""
    from deepspeed_tpu.ops.attention import _one_device_kernel
    from deepspeed_tpu.ops.pallas import delta_rule
    from deepspeed_tpu.telemetry import get_hub
    kernel = not (q.shape[-1] % 128 or v.shape[-1] % 128 or chunk % 8) \
        and _one_device_kernel(delta_rule.KERNEL_NAME if g.ndim == beta.ndim
                               else delta_rule.CHANNEL_DECAY_NAME)
    get_hub().counter("delta_prefill/" + ("kernel" if kernel else "chunked"))
    if kernel:
        return delta_rule.delta_rule_prefill(
            q, k, v, g, beta, s0, chunk, norm_weight, l2_eps=L2_EPS,
            norm_eps=norm_eps)
    return delta_prefill_reference(q, k, v, g, beta, s0, chunk, norm_weight,
                                   norm_eps)


# ---------------------------------------------------------------- the shell


def embed_tokens(module):
    """The `embed_tokens` parameter of `module` (inside its compact call)."""
    cfg = module.cfg
    return module.param("embed_tokens", nn.with_logical_partitioning(
        nn.initializers.normal(0.02), ("vocab", "embed")),
        (cfg.vocab_size, cfg.hidden_size), F32)


def embedded(cfg, embed, ids):
    """The tokens' rows of `embed`, times the muP scale where the config
    says one (`mup_enabled`, `embed_scale`)."""
    h = jnp.take(embed.astype(cfg.dtype), ids, axis=0)
    if getattr(cfg, "mup_enabled", False):
        h = (h.astype(F32) * cfg.embed_scale).astype(cfg.dtype)
    return shard_along(h, BATCH_AXES, "sequence", None)


def lm_logits(module, h, eps: float, norm=RMSNorm):
    """`norm_f` (a `norm` module class `(eps, dtype)`), then the untied
    `lm_head` (inside `module`'s call)."""
    cfg = module.cfg
    h = norm(eps, cfg.dtype, name="norm_f")(h)
    lm_head = module.param("lm_head", nn.with_logical_partitioning(
        nn.initializers.normal(0.02), ("embed", "vocab")),
        (cfg.hidden_size, cfg.vocab_size), F32)
    return h @ lm_head.astype(cfg.dtype)


def lm_output(logits, input_ids, labels, cache):
    """(logits, cache) from a serving pass, the logits, or the loss."""
    if cache is not None:
        return logits, cache
    if labels is None:
        return logits
    return causal_lm_loss(logits, input_ids, labels)


# ----------------------------------------------------- how a prefill is cut


def rows_a_group(b: int, s: int, tokens: int) -> int:
    """The rows of a batch of `b` prompts of `s` that walk the layers
    together under a budget of `tokens`: the largest divisor of `b` that
    fits (one row where not even one does)."""
    return max((r for r in range(1, b + 1)
                if b % r == 0 and r * s <= tokens), default=1)


class RowGroups(nn.Module):
    """`layers` (a module class `(cfg)` called `(h, cache)`, whose result
    ENDS with the cache) for `rows` sequences of the batch at a time, the
    whole cache carried: the body of the scan a large prefill runs over its
    rows. It shares the layers' scope, so the parameters are the same tree.
    The group's tokens are embedded here (the whole batch's embedded prompt
    is 0.67 GB at 128 x 1024) unless they arrive embedded (`embed` None),
    and only each sequence's last position goes on to the head unless
    `every` one does. What goes on is every stream the layers return before
    the cache (one; Phi-4-mini-flash's two), a tuple."""
    cfg: Any
    layers: Any
    rows: int
    every: bool = False

    @nn.compact
    def __call__(self, cache, embed, group):
        x, start = group
        layers = self.layers(self.cfg)
        nn.share_scope(self, layers)
        if embed is not None:
            x = embedded(self.cfg, embed, x)
        *streams, part = layers(x, cache.rows(start, self.rows))
        return cache.with_rows(part, start), tuple(
            t if self.every else t[:, -1:] for t in streams if t is not None)


def row_groups(layers, cfg, cache, embed, x, rows: int, *, name="layers",
               every: bool = False):
    """A prefill as a scan over groups of `rows` rows (`RowGroups`), the
    parameters under `name`: (the cache filled and NOT advanced, the streams
    (B, 1 or S, hidden) the layers returned, a tuple). `x` (B, S) tokens, or
    (B, S, hidden) already embedded with `embed` None. What the layers sow
    under `counters` comes out stacked a group."""
    b = x.shape[0]
    walk = nn.scan(RowGroups, variable_broadcast="params",
                   variable_axes={"counters": 0},
                   split_rngs={"params": False},
                   in_axes=(nn.broadcast, 0), out_axes=0)
    cache, streams = walk(cfg, layers, rows, every, name=name)(
        cache, embed, (x.reshape((b // rows, rows) + x.shape[1:]),
                       jnp.arange(0, b, rows, dtype=jnp.int32)))
    return cache, tuple(t.reshape(b, t.shape[2], -1) for t in streams)


def prefill_chunks(s: int, chunk: int):
    """(size, starts): how a prompt of `s` tokens is walked `chunk` queries
    at a time, every length in chunks of whole 128-query tiles (the kernels'
    shape; a prompt under 128 is one chunk of its own length). A row's LAST
    chunk is drawn back to end at the row's end, and what it overlaps is
    computed and written again. A `chunk` that DIVIDES `s` is taken as it
    stands, whatever its tiles (`dividing_chunk`): nothing is walked twice."""
    size = chunk if s % chunk == 0 else min(chunk, s // 128 * 128 or s)
    return size, [min(i * size, s - size) for i in range(-(-s // size))]


def dividing_chunk(s: int, chunk: int, least: int = 64) -> int:
    """The chunk a family whose layers keep a RECURRENT state walks a prompt
    of `s` in: such a layer cannot walk a position twice, so no chunk may be
    drawn back and the size must divide `s`. The largest divisor of `s` up
    to `chunk`, in whole 128-query tiles where `s` has such a divisor; a
    prompt with no divisor of `least` or more under `chunk` (a prime
    length) walks its rows whole."""
    fits = [d for d in range(min(s, chunk), 0, -1) if s % d == 0]
    size = next((d for d in fits if d % 128 == 0), fits[0])
    return size if size >= min(least, s, chunk) else s


class Chunks(nn.Module):
    """The family's `layers` (a module class `(cfg)` called `(h, cache,
    row)`) for ONE chunk of ONE row, the whole cache carried: the body of
    the scan a prefill runs over (row, chunk) pairs. It shares the layers'
    scope, so the parameters are the same tree. The chunk's tokens are
    embedded here, its row's cursors move on by its length, and only its
    last position goes on (the head reads each row's last chunk's). A chunk
    drawn `back` over its row's last one starts that far before the cursor:
    those positions' K, V and index keys are written again (from the same
    tokens against the same cache) and the layers count them again."""
    cfg: Any
    layers: Any

    @nn.compact
    def __call__(self, cache, embed, chunk):
        ids, row, back = chunk                              # (1, C), (), ()
        layers = self.layers(self.cfg)
        nn.share_scope(self, layers)
        h, cache = layers(embedded(self.cfg, embed, ids),
                          cache.advance_row(row, -back), row)
        return cache.advance_row(row, ids.shape[1]), h[:, -1:]


def prefill_walk(layers, cfg, cache, embed, input_ids, chunk: int):
    """A prefill from the empty cache as a scan over (row, chunk) pairs
    (`prefill_chunks`, `Chunks`), the parameters under `layers`: (the cache
    filled AND advanced, each row's last position's hidden state (B, 1,
    hidden))."""
    b, s = input_ids.shape
    if s > cache.max_len:
        raise ValueError(f"a prefill of {s} positions into a cache of "
                         f"{cache.max_len}")
    size, starts = prefill_chunks(s, chunk)
    n = len(starts)
    back = jnp.asarray([i * size - at for i, at in enumerate(starts)],
                       jnp.int32)
    ids = jnp.stack([input_ids[:, at:at + size] for at in starts], 1)
    walk = nn.scan(Chunks, variable_broadcast="params",
                   variable_axes={"counters": 0},
                   split_rngs={"params": False},
                   in_axes=(nn.broadcast, 0), out_axes=0)
    cache, h = walk(cfg, layers, name="layers")(
        cache, embed, (ids.reshape(b * n, 1, size),
                       jnp.repeat(jnp.arange(b, dtype=jnp.int32), n),
                       jnp.tile(back, b)))
    return cache, h.reshape(b, n, 1, -1)[:, -1]     # each row's last chunk's


def causal_lm(module, layers, input_ids, labels, cache, *, eps: float,
              prefill_tokens: int = None, prefill_chunk: int = None,
              norm=RMSNorm):
    """A family's causal LM around its `layers` (the module class, looked up
    by the family when its call is traced), inside `module`'s compact call:
    `embed_tokens`, the layers under `layers`, `norm_f` (a `norm`) at `eps`,
    `lm_head`, the loss. A serving pass (`cache`) hands the head each row's LAST
    position and advances the cursors; its prefill (S > 1) is cut as the
    family says: `prefill_chunk` queries of one row at a time, from the
    empty cache, or as many rows together as `prefill_tokens` holds."""
    cfg = module.cfg
    embed = embed_tokens(module)
    b, s = input_ids.shape
    prefill = cache is not None and s > 1
    if prefill and prefill_chunk:
        cache, h = prefill_walk(layers, cfg, cache, embed, input_ids,
                                prefill_chunk)
    elif prefill and (rows := rows_a_group(b, s, prefill_tokens)) < b:
        cache, (h,) = row_groups(layers, cfg, cache, embed, input_ids, rows)
        cache = cache.advance(s)
    else:
        h, cache = layers(cfg, name="layers")(
            embedded(cfg, embed, input_ids), cache)
        if cache is not None:
            h = h[:, -1:]          # a serving pass samples the last one
            cache = cache.advance(s)
    return lm_output(lm_logits(module, h, eps, norm), input_ids, labels,
                     cache)


def entry_points(model_cls):
    """(`init_params_and_specs`, `materialize_params`, `<family>_loss_fn`):
    a family's module binds them under the names its callers import."""
    def init_params_and_specs(cfg, rng=None, seq_len: int = 8):
        model = model_cls(cfg)
        return model, abstract_specs(model, rng, seq_len)

    def materialize_params(cfg, rng=None, seq_len: int = 8, param_dtype=None):
        """(model, the whole tree on the device from the seed), ONE jitted
        call; `param_dtype` casts inside it (the cells' float32 trees are 11
        to 20 GB: none fits a chip beside its bf16 copy, most fit none)."""
        model = model_cls(cfg)
        return model, materialize(model, rng, seq_len, param_dtype)
    return init_params_and_specs, materialize_params, make_causal_loss_fn


def refuse_int8(model, quantized: bool):
    if quantized:       # every hybrid `make_cache`'s first line
        raise ValueError(
            f"{type(model).__name__}: an int8 cache is not implemented for "
            "a hybrid cache (kv_cache_dtype=None)")
