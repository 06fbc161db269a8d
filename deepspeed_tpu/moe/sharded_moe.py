"""MoE gating + expert-parallel dispatch.

Counterpart of the reference's `deepspeed/moe/sharded_moe.py` (`MOELayer:533`,
`TopKGate:449`, `top1gating:183`, `top2gating:290`, `topkgating:374`,
`_AllToAll:96`). Same semantics: softmax gate, top-k expert choice with a
capacity limit, load-balancing aux loss, dispatch/combine via one-hot einsums.

TPU mapping: the explicit `all_to_all` between the dispatch einsum and the
expert FFN becomes a sharding transition — token-major tensors are sharded
over ('data','expert') on the token dim, expert-major tensors over 'expert'
on the expert dim — and XLA inserts the all-to-all over the expert axis
(`_AllToAll:96`'s role). Everything is static-shape (capacity) and jit-safe.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.held_combine import held_combine
from deepspeed_tpu.utils.partitioning import shard_along


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int, k: int = 1) -> int:
    cap = int(num_tokens * k / num_experts * capacity_factor)
    cap = max(cap, min_capacity)
    # round up to a lane-friendly multiple
    return min(-(-cap // 8) * 8, num_tokens)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def limit_to_groups(chosen_by: jnp.ndarray, n_group: int,
                    topk_group: int) -> jnp.ndarray:
    """Routing limited by GROUPS (DeepSeek-V3's `n_group` / `topk_group`):
    the experts lie in `n_group` contiguous groups of equal size, a group's
    score is the sum of its best two entries of `chosen_by` (T, E), the best
    `topk_group` groups stay and every expert outside them can no longer be
    chosen (-inf). `n_group` 1: `chosen_by` as it is."""
    if n_group == 1:
        return chosen_by
    t, e = chosen_by.shape
    if e % n_group or not 0 < topk_group <= n_group or e // n_group < 2:
        raise ValueError(f"routing limited by groups: {e} experts in "
                         f"{n_group} groups of at least two, of which "
                         f"{topk_group} stay")
    grouped = chosen_by.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)   # (T, G)
    _, kept = jax.lax.top_k(group_score, topk_group)
    stays = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.int32), axis=1) > 0
    return jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(t, e)


def route_scores(logits: jnp.ndarray, score_fn: str = "softmax",
                 select_bias: Optional[jnp.ndarray] = None,
                 n_group: int = 1, topk_group: int = 1
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(scores, what the top-k is taken of), both (T, E) float32. `softmax`
    scores are chosen by their logits, as they always were; `sigmoid`
    scores (DeepSeek-V3, Nemotron-H) by the scores themselves, each expert
    scored alone. A `select_bias` (E,) is added for the CHOICE only
    (`e_score_correction_bias`): the weights stay the unbiased scores. With
    `n_group` above 1 the choice is limited by groups (`limit_to_groups`,
    on the biased scores): the experts of the groups that fell out are -inf
    in what the top-k is taken of."""
    logits = logits.astype(jnp.float32)
    if score_fn == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        chosen_by = logits if select_bias is None else scores
    elif score_fn == "sigmoid":
        chosen_by = scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score_fn {score_fn!r}: 'softmax' or 'sigmoid'")
    if select_bias is not None:
        chosen_by = chosen_by + select_bias.astype(jnp.float32)
    return scores, limit_to_groups(chosen_by, n_group, topk_group)


def route_topk(logits: jnp.ndarray, k: int, score_fn: str = "softmax",
               select_bias: Optional[jnp.ndarray] = None,
               norm_topk_prob: bool = True, scale: float = 1.0,
               n_group: int = 1, topk_group: int = 1
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing with no capacity: (weights (T, k) float32, expert ids
    (T, k)). The weights are the scores of the chosen experts, over their
    sum where `norm_topk_prob`, times `scale` (`routed_scaling_factor`);
    the choice is limited by groups where `n_group` is above 1."""
    scores, chosen_by = route_scores(logits, score_fn, select_bias, n_group,
                                     topk_group)
    gate_k, topk_idx = jax.lax.top_k(chosen_by, k)
    if chosen_by is not scores:
        # what the top-k was taken of is NOT the scores (logits, a selection
        # bias, groups): the chosen experts' scores by a select and a sum
        # over the expert axis, one term of which is not zero, so the
        # gathered value bit for bit without a gather of T x k scalars
        experts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, scores.shape[-1]),
                                           2)
        gate_k = jnp.sum(jnp.where(topk_idx[..., None] == experts,
                                   scores[:, None, :], 0.0), axis=-1)
    if norm_topk_prob:
        gate_k = gate_k / jnp.maximum(
            jnp.sum(gate_k, axis=-1, keepdims=True), 1e-20)
    return gate_k * scale, topk_idx


def _gating_core(logits: jnp.ndarray, k: int, capacity_factor: float,
                 min_capacity: int, drop_tokens: bool,
                 noise_rng, noisy_gate_policy, norm_topk_prob: bool = True,
                 score_fn: str = "softmax",
                 select_bias: Optional[jnp.ndarray] = None,
                 scale: float = 1.0, n_group: int = 1, topk_group: int = 1):
    """Shared top-k decisions. Returns (l_aux, gate_k (T,k), topk_idx (T,k),
    pos_k (T,k), kept (T,k), masks (T,k,E), cap). Both the einsum and the
    ragged dispatch consume exactly these decisions."""
    t, e = logits.shape
    cap = _capacity(t, e, capacity_factor, min_capacity, k)
    if not drop_tokens:
        cap = t  # every token can fit
    gates, select_from = route_scores(logits, score_fn, select_bias, n_group,
                                      topk_group)
    if score_fn == "softmax" and select_bias is None and n_group == 1:
        select_from = logits             # in the logits' own dtype, as before
    if noisy_gate_policy == "RSample" and noise_rng is not None:
        select_from = select_from + jax.random.gumbel(noise_rng, logits.shape)

    # top-k expert ids per token
    _, topk_idx = jax.lax.top_k(select_from, k)          # (T, k)
    masks = _one_hot(topk_idx, e)                        # (T, k, E)

    # load-balancing aux loss from the top-1 assignment (reference l_aux)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[:, 0, :], axis=0)
    l_aux = jnp.sum(me * ce) * e

    # position of each token within its expert's capacity, ordered by k-slot
    # then token index (reference cumsum over the flattened (k*T, E) mask).
    flat = masks.transpose(1, 0, 2).reshape(k * t, e)    # k-major like reference
    pos_flat = jnp.cumsum(flat, axis=0) - flat           # (k*T, E)
    pos = pos_flat.reshape(k, t, e).transpose(1, 0, 2)   # (T, k, E)
    within_cap = pos < cap
    masks = masks * within_cap.astype(masks.dtype)

    # combine weights: gate prob per selected expert, renormalized over kept
    gate_k = jnp.take_along_axis(gates, topk_idx, axis=-1)       # (T, k)
    kept = jnp.sum(masks, axis=-1)                               # (T, k) 0/1
    gate_k = gate_k * kept
    if norm_topk_prob:
        denom = jnp.sum(gate_k, axis=-1, keepdims=True)
        gate_k = gate_k / jnp.maximum(denom, 1e-9)
    if scale != 1.0:
        gate_k = gate_k * scale

    pos_k = jnp.sum(pos * masks, axis=-1).astype(jnp.int32)      # (T, k)
    return l_aux, gate_k, topk_idx, pos_k, kept, masks, cap


def topkgating(logits: jnp.ndarray,
               k: int,
               capacity_factor: float = 1.0,
               min_capacity: int = 8,
               drop_tokens: bool = True,
               noise_rng: Optional[jax.Array] = None,
               noisy_gate_policy: Optional[str] = None,
               norm_topk_prob: bool = True, **scoring
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Generalized top-k gating (reference topkgating:374; top1/top2 are k=1,2).

    logits: (T, E). Returns (l_aux, combine_weights (T,E,C), dispatch_mask
    (T,E,C) bool, capacity C). O(T·E·C) outputs — prefer `topkgating_ragged`
    at scale."""
    l_aux, gate_k, topk_idx, pos_k, kept, masks, cap = _gating_core(
        logits, k, capacity_factor, min_capacity, drop_tokens, noise_rng,
        noisy_gate_policy, norm_topk_prob, **scoring)
    loc = _one_hot(pos_k, cap)                                   # (T, k, C)
    combine = jnp.einsum("tk,tke,tkc->tec", gate_k, masks, loc)  # (T, E, C)
    dispatch = combine > 0
    return l_aux, combine, dispatch, cap


def topkgating_ragged(logits: jnp.ndarray,
                      k: int,
                      capacity_factor: float = 1.0,
                      min_capacity: int = 8,
                      drop_tokens: bool = True,
                      noise_rng: Optional[jax.Array] = None,
                      noisy_gate_policy: Optional[str] = None,
                      norm_topk_prob: bool = True):
    """Index-form gating for the scatter/gather dispatch: O(T·k) outputs
    instead of O(T·E·C) masks (the role of the reference's tutel/v2
    `top_k_gating` + `moe_scatter` kernel pair). Identical decisions to
    `topkgating` by construction (shared `_gating_core`)."""
    l_aux, gate_k, topk_idx, pos_k, kept, _, cap = _gating_core(
        logits, k, capacity_factor, min_capacity, drop_tokens, noise_rng,
        noisy_gate_policy, norm_topk_prob)
    return l_aux, gate_k, topk_idx, pos_k, kept, cap


def top1gating(logits, capacity_factor=1.0, min_capacity=8, drop_tokens=True,
               noise_rng=None, noisy_gate_policy=None):
    """Reference top1gating:183."""
    return topkgating(logits, 1, capacity_factor, min_capacity, drop_tokens,
                      noise_rng, noisy_gate_policy)


def top2gating(logits, capacity_factor=1.0, min_capacity=8, drop_tokens=True,
               noise_rng=None):
    """Reference top2gating:290."""
    return topkgating(logits, 2, capacity_factor, min_capacity, drop_tokens, noise_rng)


def dispatch_combine(x: jnp.ndarray,
                     combine: jnp.ndarray,
                     dispatch: jnp.ndarray,
                     expert_fn,
                     ) -> jnp.ndarray:
    """Dispatch tokens to experts, apply expert_fn, combine back.

    x: (T, D) token-major (sharded over tokens on ('data','expert')).
    expert_fn: (E, C, D) -> (E, C, D) expert-major (sharded over 'expert').
    Mirrors MOELayer.forward:586 einsum→a2a→expert→a2a→combine.
    """
    expert_inputs = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    # sharding transition = the all-to-all over the expert axis
    expert_inputs = shard_along(expert_inputs, "expert", None, None)
    expert_outputs = expert_fn(expert_inputs)
    expert_outputs = shard_along(expert_outputs, "expert", None, None)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_outputs)
    return out


def dispatch_combine_gmm(x: jnp.ndarray, gate_k: jnp.ndarray,
                         topk_idx: jnp.ndarray, num_experts: int,
                         grouped_fn) -> jnp.ndarray:
    """Sorted-rows dispatch for the grouped expert GEMM: the role of the
    reference's `cutlass_ops/moe_gemm` + `ragged_ops/moe_{scatter,gather}`
    kernel trio in ONE data layout. Tokens are stable-sorted by expert id
    (T·k rows, no (E, capacity) padding), `grouped_fn(rows, group_sizes)`
    runs the expert FFN as megablox grouped GEMMs, and the combine gathers
    back to token order weighted by the gate.

    Capacity-dropped slots are compute-included but WEIGHT-zeroed (gate_k
    is already masked by `kept` in `_gating_core`) — numerically identical
    to the buffer paths, and still fewer FLOPs than the (E, C) buffer
    whenever capacity_factor > 1. Sharding: megablox is a Pallas call
    GSPMD cannot partition, but pure expert-parallel meshes ride the
    shard_map EP wrapper (`ops/pallas/grouped_gemm.sharded_grouped_gemm`,
    per-shard `group_offset` + masked psum — `Experts` picks it via
    `_gmm_mesh`); any OTHER nontrivial mesh still routes to
    `dispatch_combine_ragged` from `MoE`'s auto rule.
    """
    t, d = x.shape
    k = topk_idx.shape[1]
    # `dispatch` / `combine`: scope names for the program map
    # (docs/telemetry.md); the experts' own work carries its flax name
    with jax.named_scope("dispatch"):
        flat_e = topk_idx.reshape(-1)                   # (T·k,)
        order = jnp.argsort(flat_e)                     # stable: token-order
        xs = jnp.take(x, order // k, axis=0)            # within each expert
        group_sizes = jnp.bincount(flat_e, length=num_experts)
    out_s = grouped_fn(xs, group_sizes)                 # (T·k, D)
    with jax.named_scope("combine"):
        out_k = jnp.take(out_s, jnp.argsort(order), axis=0).reshape(t, k, d)
        return jnp.einsum("tk,tkd->td", gate_k.astype(x.dtype), out_k)


def dispatch_combine_ragged(x: jnp.ndarray, gate_k: jnp.ndarray,
                            topk_idx: jnp.ndarray, pos_k: jnp.ndarray,
                            kept: jnp.ndarray, cap: int, num_experts: int,
                            expert_fn) -> jnp.ndarray:
    """Scatter/gather dispatch: O(T·k·D) data movement, no (T,E,C) tensor.

    The counterpart of the reference's ragged MoE kernels
    (`inference/v2/kernels/ragged_ops/{moe_scatter,moe_gather}`,
    `cutlass_ops/moe_gemm` grouped GEMM): tokens scatter into the (E, C, D)
    expert buffer at slot `expert·C + pos` (dropped tokens fall out of
    bounds), experts run as one batched matmul, and the combine is a gather
    back to token order weighted by the gate. Sharding transitions on the
    expert buffer are the all-to-all over the `expert` mesh axis.
    """
    t, d = x.shape
    k = topk_idx.shape[1]
    with jax.named_scope("dispatch"):
        dest = topk_idx * cap + pos_k                          # (T, k)
        dest = jnp.where(kept > 0, dest, num_experts * cap)    # dropped → OOB
        xk = jnp.broadcast_to(x[:, None], (t, k, d)).reshape(t * k, d)
        buf = jnp.zeros((num_experts * cap, d), x.dtype)
        # each (expert, slot) receives at most one token → add ≡ set, OOB
        # dropped
        buf = buf.at[dest.reshape(-1)].add(xk, mode="drop")
        expert_inputs = buf.reshape(num_experts, cap, d)
        expert_inputs = shard_along(expert_inputs, "expert", None, None)
    expert_outputs = expert_fn(expert_inputs)
    with jax.named_scope("combine"):
        expert_outputs = shard_along(expert_outputs, "expert", None, None)
        flat = expert_outputs.reshape(num_experts * cap, d)
        out_k = jnp.take(flat, dest, axis=0, mode="fill",
                         fill_value=0)                         # (T, k, D)
        return jnp.einsum("tk,tkd->td", gate_k.astype(x.dtype), out_k)


# ------------------------------------------------- a chip's share of experts


def held_assignments(topk_idx: jnp.ndarray, offset: int, count: int,
                     valid: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Of the (T, k) assignments, those that fall on the `count` experts
    from `offset` on, which this layer holds: (held (T, k) bool, the held
    expert's LOCAL id, `count` where absent). A row of `valid` (T,) that is
    False (padding) has no assignment at all."""
    local = topk_idx - offset
    held = jnp.logical_and(local >= 0, local < count)
    if valid is not None:
        held = jnp.logical_and(held, valid[:, None])
    return held, jnp.where(held, local, count)


def held_group_sizes(local: jnp.ndarray, count: int) -> jnp.ndarray:
    """How many assignments each of the `count` held experts received, from
    `held_assignments`' local ids: a compare and a sum over the values an id
    can take, one fused pass and no scatter into bins."""
    return jnp.sum(local.reshape(-1, 1) == jnp.arange(count, dtype=local.dtype),
                   axis=0, dtype=jnp.int32)


# `held_row_bound`'s three constants (the rule is in `held_dispatch_gmm`)
HELD_ROWS_MARGIN = 2        # the bound over the rows a share expects
HELD_NARROW_FROM = 1024     # assignments a call up to which none is set
HELD_NARROW_UNDER = 4       # ... and T x k over the largest bound that is


def held_row_tile(rows: int, num_experts: int) -> int:
    """The grouped GEMM's row tile for a call of `rows` = T x k assignments
    over a router of `num_experts`: two to four times the rows an expert
    expects. A tile is multiplied, masked, against every expert whose rows
    lie in it, and an expert's weights are read once a tile its rows touch;
    on this chip the two costs meet there (64 rows an expert: 256 against
    128, 512 and 64, PERF.md, PR 59). 16 (Mosaic's bf16 minimum) at decode,
    where an expert expects fewer than 8 rows. A tile is NOT one expert's
    alone there: the sorted rows lie contiguous from row 0, so a tile holds
    five experts of 3 rows and an expert of `s` rows straddles a boundary
    with probability `(s - 1) / 16`; at this tile the grouped GEMM takes the
    whole contraction as one K tile (`grouped_gemm.held_tiling`), so that
    the straddling expert's second visit finds its weights resident."""
    return max(16, min(512, 1 << (2 * rows // num_experts).bit_length()))


def held_row_bound(rows: int, count: int, num_experts: int, tile: int) -> int:
    """Of a call's `rows` = T x k assignments, how many sorted rows a layer
    that holds `count` of `num_experts` experts moves and multiplies: twice
    what its share expects, in whole row tiles of the grouped GEMM; `rows`
    itself (no bound) where that is over a quarter of them or the call is
    small."""
    expected = -(-rows * count // num_experts)
    bound = -(-HELD_ROWS_MARGIN * expected // tile) * tile
    if rows <= HELD_NARROW_FROM or HELD_NARROW_UNDER * bound > rows:
        return rows
    return bound


def held_dispatch_gmm(x: jnp.ndarray, gate_k: jnp.ndarray,
                      topk_idx: jnp.ndarray, offset: int, count: int,
                      grouped_fn, valid: Optional[jnp.ndarray] = None,
                      bound: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`dispatch_combine_gmm` for a layer that holds experts `offset ..
    offset + count - 1` of those the router scores: the rows of the held
    assignments sorted by held expert, the absent ones after them and
    OUTSIDE every group, so that they are no row of any GEMM (the grouped
    kernel's grid ends with the last group). Returns (this chip's part of
    the layer's result (T, D) float32, the number of held assignments,
    1 where they passed `bound` else 0).

    `bound` (static, `held_row_bound`; None: T x k): the sorted rows the
    call is SIZED for. With the held rows first, `order[:bound]` names every
    one of them whenever there are `bound` or fewer, and then the gather, the
    grouped FFN's operands and the way back to token order are `bound` rows
    and never T x k: the chip moves the rows it holds, as under expert
    parallelism the all-to-all would bring it no others. The way back is ONE
    Pallas pass over those rows as the grouped GEMM left them
    (`ops/pallas/held_combine.py`: the sort is stable and a token's k ids
    are distinct, so a tile of tokens needs `count` contiguous windows of
    the rows, one in each expert's group; each token's float32 sum of
    float32(row) x weight, its terms added by held expert and not in the
    choice's order: float32 rounding of the same sum). No second sort, no
    gather and no scatter: XLA's row gathers and shifted passes cost 217 ns
    a bound ROW whatever its width (PERF.md, PR 61), its row scatter-add 0.7
    to 11 ms for 2,048 rows by the width and by what the compiler fused
    into it. EXACT for every routing:
    a call whose held rows pass the bound (a skewed router, a hot chunk)
    takes the full-width body under `lax.cond`; nothing is dropped or
    capped. `grouped_fn` is a function of arrays alone (it is traced in both
    branches).

    The rule, by shape alone: bound = HELD_ROWS_MARGIN (2) x the share's
    expected rows, T x k x count / num_experts, in whole row tiles. It is
    set only where it is T x k / HELD_NARROW_UNDER (a quarter) or less, a
    share of an eighth: at a quarter held (Ling: 32,768 of 65,536 rows) no
    form of the narrow body beat the full-width one (13.1 ms a call against
    13.0-22.1), and at a half (Nemotron) the bound is T x k itself. And only
    past HELD_NARROW_FROM (1,024) assignments a call: a decode step's are
    fewer in every cell and its whole dispatch 0.03-0.19 ms
    (`moe_dispatch_ms.gen`). Elsewhere the program is the full-width body
    alone, no branch. The chip readings that fixed the three are in PERF.md,
    PR 59."""
    t, d = x.shape
    k = topk_idx.shape[1]
    with jax.named_scope("dispatch"):
        held, local = held_assignments(topk_idx, offset, count, valid)
        key = local.reshape(-1)                         # absent sort last
        order = jnp.argsort(key)                        # stable
        group_sizes = held_group_sizes(local, count)
        n_held = jnp.sum(group_sizes)

    def wide():
        with jax.named_scope("dispatch"):
            xs = jnp.take(x, order // k, axis=0)
        out_s = grouped_fn(xs, group_sizes)             # (T*k, D)
        with jax.named_scope("combine"):
            # rows past the last group were never written
            rows = jax.lax.broadcasted_iota(jnp.int32, (t * k, 1), 0)
            out_s = jnp.where(rows < n_held, out_s, 0)
            out_k = jnp.take(out_s, jnp.argsort(order),
                             axis=0).reshape(t, k, d)
            w = jnp.where(held, gate_k, 0.0)
            return jnp.einsum("tk,tkd->td", w, out_k.astype(jnp.float32))

    def narrow():
        with jax.named_scope("dispatch"):
            # every held row; `order` is a permutation, so in range
            xs = x.at[order[:bound] // k].get(mode="promise_in_bounds")
        out_s = grouped_fn(xs, group_sizes)             # (bound, D)
        with jax.named_scope("combine"):
            return held_combine(out_s, local, gate_k, count)

    if bound is None or bound >= t * k:
        return wide(), n_held, jnp.zeros((), jnp.int32)
    fits = n_held <= bound
    return (jax.lax.cond(fits, narrow, wide), n_held,
            1 - fits.astype(jnp.int32))


def held_dispatch_ragged(x: jnp.ndarray, gate_k: jnp.ndarray,
                         topk_idx: jnp.ndarray, offset: int, count: int,
                         expert_fn, valid: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same share through the (count, T, D) expert buffer and one
    batched matmul (XLA only, partitionable): every token fits, none is
    dropped, an absent assignment falls out of bounds. For small T: the
    buffer is `count x T` rows whatever was routed."""
    t, d = x.shape
    k = topk_idx.shape[1]
    with jax.named_scope("dispatch"):
        held, local = held_assignments(topk_idx, offset, count, valid)
        flat = _one_hot(local.reshape(-1), count)       # absent: a zero row
        pos = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, axis=-1)
        dest = jnp.where(held,
                         local * t + pos.reshape(t, k).astype(jnp.int32),
                         count * t)
        xk = jnp.broadcast_to(x[:, None], (t, k, d)).reshape(t * k, d)
        buf = jnp.zeros((count * t, d), x.dtype)
        buf = buf.at[dest.reshape(-1)].add(xk, mode="drop")
    out = expert_fn(buf.reshape(count, t, d)).reshape(count * t, d)
    with jax.named_scope("combine"):
        out_k = jnp.take(out, dest, axis=0, mode="fill", fill_value=0)
        w = jnp.where(held, gate_k, 0.0)
        return (jnp.einsum("tk,tkd->td", w, out_k.astype(jnp.float32)),
                jnp.sum(held.astype(jnp.int32)))
