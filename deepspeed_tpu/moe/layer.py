"""MoE layer + experts.

Counterpart of reference `deepspeed/moe/layer.py:17` (`MoE` — creates EP
groups at `:89`), `moe/experts.py` (`Experts`) and the `TopKGate` module.
EP "group creation" here is the `expert` mesh axis (utils/groups.py); expert
weights carry the 'expert' logical axis on dim 0 and are therefore sharded
across expert-parallel ranks, with ZeRO sharding them only over 'data'
(see ZeroShardingPlan.zero_axes — the expert-data-parallel split of
reference groups.py:117,188).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.sharded_moe import (
    _gating_core, dispatch_combine, dispatch_combine_gmm,
    dispatch_combine_ragged, held_assignments, held_dispatch_gmm,
    held_dispatch_ragged, held_group_sizes, held_row_bound, held_row_tile,
    route_topk, topkgating)
from deepspeed_tpu.utils.partitioning import BATCH_AXES, shard_along


def _unpartitioned_mesh() -> bool:
    """True when every mesh axis is trivial (or no topology exists yet) —
    the regime where the bare megablox grouped GEMM is safe: GSPMD cannot
    partition a Pallas call, so on a real mesh it would silently all-gather
    its operands; `auto` keeps those on the ragged buffer path."""
    import jax
    from deepspeed_tpu.utils import groups
    try:
        topo = groups.get_topology(create_default=False)
    except RuntimeError:
        # no topology: only trust a literally-single-device process — a
        # user jitting over their own Mesh without groups.initialize must
        # land on the partitionable path
        return len(jax.devices()) == 1
    return topo.world_size == 1


def _gmm_mesh(num_experts: int):
    """Where (and how) the grouped GEMM may run under the installed
    topology. Returns:

      (None, 1)    — every axis trivial: bare single-shard megablox.
      (mesh, ep)   — pure expert-parallel mesh with num_experts % ep == 0:
                     the shard_map EP wrapper (sharded_grouped_gemm), each
                     shard running gmm with its group_offset.
      (None, 0)    — partitioned but unsupported (mixed axes, indivisible
                     experts, or no jax.shard_map): callers fall back to
                     ragged / bare gmm and say so via kernel_fallback.
    """
    if _unpartitioned_mesh():
        return None, 1
    from deepspeed_tpu.ops.pallas.sharded import serving_mesh
    mesh, ep = serving_mesh("expert")
    if mesh is not None and ep > 1 and num_experts % ep == 0:
        return mesh, ep
    return None, 0


def is_moe_param_path(path) -> bool:
    """expert_param_fn for the engine: params under an 'experts' collection."""
    return any(getattr(p, "key", getattr(p, "name", None)) == "experts"
               for p in path)


def _activate(up, gate, activation: str):
    """The FFN's middle: `silu` is gated (mixtral-style), `gelu` plain,
    `relu2` is relu squared with no gate (Nemotron-H), squared in float32."""
    if activation == "silu":
        return nn.silu(gate) * up
    if activation == "gelu":
        return nn.gelu(up)
    if activation == "relu2":
        return jnp.square(nn.relu(up.astype(jnp.float32))).astype(up.dtype)
    raise ValueError(f"Experts.activation {activation!r}: 'silu' (gated), "
                     "'gelu' or 'relu2'")


class Experts(nn.Module):
    """Batched expert FFNs (E, ...) — reference moe/experts.py, computed as a
    single grouped matmul over the expert-sharded leading axis (the Pallas/
    megablocks grouped-GEMM slot; XLA batches it on the MXU)."""
    num_experts: int
    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16
    activation: str = "silu"  # silu: gated (mixtral-style); gelu, relu2: plain

    @nn.compact
    def __call__(self, x, group_sizes=None, tm: Optional[int] = None):
        """Batched form: x (E, C, D) → (E, C, D). Grouped form (when
        `group_sizes` is given): x (M, D) rows sorted by expert, each
        expert's span through its FFN as megablox grouped GEMMs — same
        params, no (E, C) padding; rows past the last group are no group's
        and are not computed. `tm`: the grouped kernel's row tile where the
        caller knows how many rows an expert expects (its default suits
        thousands of rows an expert). `x` None: the grouped form ITSELF,
        `(rows, group_sizes) -> rows` over the weights read here, a function
        of arrays alone that a caller may trace under `lax.cond`
        (`sharded_moe.held_dispatch_gmm`), where a module may not be called."""
        e, d, f = self.num_experts, self.hidden_size, self.intermediate_size
        init = nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                            ("expert", "embed", "mlp"))
        init_out = nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                                ("expert", "mlp_in", "embed"))
        w_up = self.param("up", init, (e, d, f), jnp.float32).astype(self.dtype)
        w_down = self.param("down", init_out, (e, f, d), jnp.float32).astype(self.dtype)
        w_gate = (self.param("gate", init, (e, d, f), jnp.float32)
                  .astype(self.dtype) if self.activation == "silu" else None)
        if group_sizes is not None or x is None:
            from jax.ad_checkpoint import checkpoint_name
            from deepspeed_tpu.ops.pallas.grouped_gemm import (
                grouped_gemm, held_tiling, sharded_grouped_gemm)
            from deepspeed_tpu.ops.pallas.sharded import kernel_fallback
            mesh, ep = _gmm_mesh(e)
            if ep == 0:
                # forced/auto gmm on a mesh the EP wrapper can't cover:
                # the bare call still computes (GSPMD gathers operands) —
                # never silently
                kernel_fallback(
                    "grouped_gemm",
                    f"partitioned mesh is not pure expert-parallel with "
                    f"{e} % ep == 0; running unsharded (operands gathered)")

            def gg(lhs, rhs, sizes):
                # named so remat policies can SAVE grouped-GEMM outputs:
                # a Pallas call is not a dot, so plain checkpoint_dots
                # recomputes the whole grouped FFN in backward
                # (remat_policy='checkpoint_dots_gmm' in models/llama.py)
                tiling = None if tm is None else held_tiling(
                    tm, lhs.shape[1], rhs.shape[2], rhs.dtype.itemsize)
                out = (sharded_grouped_gemm(lhs, rhs, sizes, mesh,
                                            tiling=tiling)
                       if mesh is not None
                       else grouped_gemm(lhs, rhs, sizes, tiling=tiling))
                return checkpoint_name(out, "moe_gmm")

            def grouped(rows, sizes):
                gate = None if w_gate is None else gg(rows, w_gate, sizes)
                return gg(_activate(gg(rows, w_up, sizes), gate,
                                    self.activation), w_down, sizes)
            if x is None:
                def scoped(rows, sizes):
                    # traced outside this module's call: the scope's name
                    # the program map knows the experts by (docs/telemetry.md)
                    with jax.named_scope(self.name):
                        return grouped(rows, sizes)
                return scoped
            return grouped(x, group_sizes)
        gate = None if w_gate is None else \
            jnp.einsum("ecd,edf->ecf", x, w_gate)
        h = _activate(jnp.einsum("ecd,edf->ecf", x, w_up), gate,
                      self.activation)
        return jnp.einsum("ecf,efd->ecd", h, w_down)


class TopKGate(nn.Module):
    """Reference sharded_moe.py:TopKGate:449."""
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 8
    drop_tokens: bool = True
    noisy_gate_policy: Optional[str] = None
    # False = qwen2-moe style: top-k weights stay raw softmax probabilities
    # (HF norm_topk_prob); True = mixtral/reference renormalize-over-kept
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    # how an expert is scored: 'softmax' over all of them, or 'sigmoid',
    # each alone (DeepSeek-V3, Nemotron-H)
    score_fn: str = "softmax"
    # a learned (E,) bias added to the scores for the CHOICE only, never to
    # the weights (`e_score_correction_bias`); the parameter `bias`
    selection_bias: bool = False
    bias_init: Callable = nn.initializers.zeros_init()
    scale: float = 1.0      # `routed_scaling_factor`, on the weights
    # routing limited by groups (`sharded_moe.limit_to_groups`): the best
    # `topk_group` of `n_group` contiguous groups of experts stay in the choice
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, x, train: bool = True, noise_rng=None,
                 ragged: bool = False, routed_only: bool = False):
        wg = self.param("wg", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", None)),
            (x.shape[-1], self.num_experts), jnp.float32)
        bias = self.param("bias", nn.with_logical_partitioning(
            self.bias_init, (None,)), (self.num_experts,), jnp.float32) \
            if self.selection_bias else None
        logits = (x.astype(jnp.float32) @ wg)
        if routed_only:
            # (weights (T, k), expert ids (T, k)) with no capacity: for a
            # layer that drops nothing
            return route_topk(logits, self.k, self.score_fn, bias,
                              self.norm_topk_prob, self.scale, self.n_group,
                              self.topk_group)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        policy = self.noisy_gate_policy if train else None
        scoring = dict(score_fn=self.score_fn, select_bias=bias,
                       scale=self.scale, n_group=self.n_group,
                       topk_group=self.topk_group)
        if ragged:
            l_aux, gate_k, topk_idx, pos_k, kept, _, cap = _gating_core(
                logits, self.k, cf, self.min_capacity, self.drop_tokens,
                noise_rng, policy, self.norm_topk_prob, **scoring)
            return l_aux, gate_k, topk_idx, pos_k, kept, cap
        return topkgating(logits, self.k, cf, self.min_capacity,
                          self.drop_tokens, noise_rng, policy,
                          self.norm_topk_prob, **scoring)


def shared_expert_gate(x, w):
    """`sigmoid(x . w)`, float32, one number a token: what a GATED shared
    expert's result is multiplied by (`MoE.shared_gate`)."""
    return jax.nn.sigmoid((x @ w).astype(jnp.float32))


class MoE(nn.Module):
    """Drop-in MoE FFN block — reference deepspeed/moe/layer.py:MoE.

    Input (B, S, D) → (B, S, D); also returns (l_aux, exp_counts-like None)
    via the `aux_loss` flax variable collection (summed by the engine loss
    when present).
    """
    hidden_size: int
    num_experts: int = 1
    ep_size: int = 1                      # schema parity; actual EP = mesh axis
    k: int = 1
    intermediate_size: Optional[int] = None
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    drop_tokens: bool = True
    noisy_gate_policy: Optional[str] = None
    norm_topk_prob: bool = True
    use_residual: bool = False            # PR-MoE (residual expert)
    dtype: Any = jnp.bfloat16
    activation: str = "silu"
    # 'auto' (default): 'gmm' on an unpartitioned mesh, else 'ragged'.
    # 'gmm': expert-sorted rows through the megablox grouped GEMM — no
    # (E, C) buffer, but a Pallas call GSPMD cannot shard. 'ragged':
    # scatter/gather into the (E, C, D) buffer, O(T·k·D) movement, fully
    # GSPMD-partitionable (the EP path). 'einsum': the dense one-hot
    # formulation, O(T·E·C·D) — kept as the golden reference.
    dispatch_impl: str = "auto"
    # the router (TopKGate): score function, selection bias, weight scale
    score_fn: str = "softmax"
    selection_bias: bool = False
    bias_init: Callable = nn.initializers.zeros_init()
    routed_scaling_factor: float = 1.0
    n_group: int = 1                      # routing limited by groups
    topk_group: int = 1
    # A layer that is TOLD which experts it holds: `held_experts` of the
    # `num_experts` the router scores, from `held_offset` on (one chip's
    # share under expert parallelism, the model-configs guide's section 4).
    # It routes over all of them, drops the assignments to absent experts
    # before dispatch, computes its own experts' part of the result and
    # stands in for nobody: what the absent ones would add is left out.
    # Nothing is dropped by capacity. None: every expert is held (above).
    held_offset: int = 0
    held_experts: Optional[int] = None
    # a shared expert of this width beside the routed ones, same activation,
    # run for every token and added once
    shared_intermediate_size: Optional[int] = None
    # the shared expert's result times `sigmoid(x . w)`, one number a token
    # (Qwen's `shared_expert_gate`; `models/qwen2_moe.py` has the dense form)
    shared_gate: bool = False

    @nn.compact
    def __call__(self, hidden_states, train: bool = True, valid=None):
        b, s, d = hidden_states.shape
        f = self.intermediate_size or 4 * d
        x = hidden_states.reshape(b * s, d)
        x = shard_along(x, BATCH_AXES, None)

        gate = TopKGate(self.num_experts, self.k, self.capacity_factor,
                        self.eval_capacity_factor, self.min_capacity,
                        self.drop_tokens, self.noisy_gate_policy,
                        self.norm_topk_prob, self.dtype, self.score_fn,
                        self.selection_bias, self.bias_init,
                        self.routed_scaling_factor, self.n_group,
                        self.topk_group, name="gate")
        noise_rng = self.make_rng("gating") if self.has_rng("gating") else None

        if self.held_experts is not None:
            out = self._held(x, gate, f, None if valid is None
                             else valid.reshape(b * s))
            return out.reshape(b, s, d)
        experts = Experts(self.num_experts, d, f, self.dtype,
                          self.activation, name="experts")
        impl = self.dispatch_impl
        if impl == "auto":
            # r5 on-chip A/B (a probe since deleted): gmm wins the
            # fwd-only layer 1.2x (2.79 vs 3.35 ms), but its bwd kernels
            # (transpose_rhs gmm + tgmm) lose the train step 1.03-1.04x
            # even with the named-save remat policy — so auto picks gmm
            # only for inference, and only where the kernel can actually
            # run sharded: off-mesh, or a pure expert-parallel mesh via
            # the shard_map EP wrapper (r7; _gmm_mesh). Tiny row counts
            # (single-token decode) stay on ragged: the grouped kernel
            # was validated on-chip at large m only, and sub-tile m just
            # pads to the Mosaic minimum for no win.
            want_gmm = not train and b * s * self.k >= 1024
            gmm_ok = want_gmm and _gmm_mesh(self.num_experts)[1] > 0
            if want_gmm and not gmm_ok:
                from deepspeed_tpu.ops.pallas.sharded import kernel_fallback
                kernel_fallback(
                    "grouped_gemm",
                    "auto would pick gmm but the mesh is not trivial or "
                    "pure expert-parallel — using ragged dispatch")
            impl = "gmm" if gmm_ok else "ragged"
        assignments = float(b * s * self.k)
        # `route`: the router's scores and choice, for the program map
        # (docs/telemetry.md); `dispatch` / `combine` are set where the
        # rows are sorted and gathered back (moe/sharded_moe.py)
        if impl == "gmm":
            with jax.named_scope("route"):
                l_aux, gate_k, topk_idx, pos_k, kept, cap = gate(
                    x, train, noise_rng, ragged=True)
            out = dispatch_combine_gmm(x, gate_k, topk_idx,
                                       self.num_experts, experts)
        elif impl == "ragged":
            with jax.named_scope("route"):
                l_aux, gate_k, topk_idx, pos_k, kept, cap = gate(
                    x, train, noise_rng, ragged=True)
            out = dispatch_combine_ragged(x, gate_k, topk_idx, pos_k, kept,
                                          cap, self.num_experts, experts)
        else:
            with jax.named_scope("route"):
                l_aux, combine, dispatch, _ = gate(x, train, noise_rng)
            out = dispatch_combine(x, combine, dispatch, experts)
        if impl in ("gmm", "ragged"):
            # router telemetry (pre-capacity): fraction of the T·k expert
            # assignments routed to each expert (sums to 1), and the
            # fraction dropped by the capacity limit
            router_load = jnp.sum(
                jax.nn.one_hot(topk_idx, self.num_experts,
                               dtype=jnp.float32), axis=(0, 1)) / assignments
            router_drop = 1.0 - jnp.sum(
                kept.astype(jnp.float32)) / assignments
        else:
            # einsum path exposes only the post-capacity dispatch mask, so
            # its load is post-drop (sums to 1 - drop)
            d32 = dispatch.astype(jnp.float32)
            router_load = jnp.sum(d32, axis=(0, 2)) / assignments
            router_drop = 1.0 - jnp.sum(d32) / assignments
        # a no-op unless the caller made the 'metrics' collection mutable
        # (the zoo loss fns do); reduce keeps plain arrays so nn.scan
        # stacks a clean (L, E)/(L,) per model
        self.sow("metrics", "router_load", router_load,
                 init_fn=lambda: jnp.zeros((self.num_experts,), jnp.float32),
                 reduce_fn=lambda a, b_: a + b_)
        self.sow("metrics", "router_drop", router_drop,
                 init_fn=lambda: jnp.zeros([], jnp.float32),
                 reduce_fn=lambda a, b_: a + b_)

        if self.use_residual:
            # PR-MoE: add a dense residual MLP, gated per-token (layer.py residual path)
            res = Experts(1, d, f, self.dtype, self.activation, name="residual_expert")(
                x[None].reshape(1, b * s, d))[0]
            coef = nn.Dense(2, dtype=self.dtype, name="coefficient")(x)
            coef = jax.nn.softmax(coef.astype(jnp.float32), axis=-1).astype(out.dtype)
            out = out * coef[:, :1] + res * coef[:, 1:]

        self.sow("aux_loss", "moe_l_aux", l_aux,
                 init_fn=lambda: jnp.zeros([], jnp.float32),
                 reduce_fn=lambda a, b_: a + b_)
        return out.reshape(b, s, d)

    def _held(self, x, gate, f, valid):
        """This chip's part of the layer for tokens x (T, D): the held
        experts' weighted outputs plus the shared expert. Sows the call's
        `assignments` and `held_assignments`, `held_wide_calls` (1 where the
        held rows passed the bound the call was sized for,
        `sharded_moe.held_row_bound`, and the full-width body ran), and
        `experts_touched` of `experts_held`: the held experts that received
        an assignment, whose weights the call reads, and
        `weight_tile_revisits`: the grid steps of a decode-sized call's
        grouped GEMM that found their expert's weights resident
        (`grouped_gemm.weight_tile_revisits`; collection `counters`)."""
        from deepspeed_tpu.ops.pallas.grouped_gemm import weight_tile_revisits
        t, d = x.shape
        count, k = self.held_experts, self.k
        experts = Experts(count, d, f, self.dtype, self.activation,
                          name="experts")
        with jax.named_scope("route"):
            gate_k, topk_idx = gate(x, routed_only=True)
        impl = self.dispatch_impl
        if impl == "auto":
            # The grouped GEMM wherever the bare kernel may run (one device),
            # at every size; a partitioned mesh takes the XLA buffer path.
            # Measured on the chip at the decode shape (64 rows, 64 held of
            # 128, top 6, 60 experts touched; PERF.md, PR 41): ALONE the buffer
            # path's batched matmul is quicker, 1.88 against 2.16 ms (read
            # again at PR 66, 63 touched: 1.73 against 1.92, and 1.79 with the
            # whole contraction as one K tile, `grouped_gemm.held_tiling`),
            # but in a program whose prefill runs the grouped GEMM it wants the
            # experts' weights in another layout and the compiler keeps a
            # second copy of them (temporaries 2.37 -> 5.88 GB beside 9.3 GB of
            # weights). At Ling's and DeepSeek's shares the grouped GEMM is
            # the quicker alone too (1.72 and 0.59 against 2.15 and 1.87 ms).
            impl = "gmm" if _unpartitioned_mesh() else "ragged"
        wide, tm = 0, None
        if impl == "gmm":
            tm = held_row_tile(t * k, self.num_experts)
            out, held, wide = held_dispatch_gmm(
                x, gate_k, topk_idx, self.held_offset, count,
                experts(None, tm=tm), valid,
                held_row_bound(t * k, count, self.num_experts, tm))
        elif impl == "ragged":
            out, held = held_dispatch_ragged(
                x, gate_k, topk_idx, self.held_offset, count,
                experts, valid)
        else:
            raise ValueError(f"a layer with held_experts dispatches by 'gmm' "
                             f"or 'ragged', not {impl!r}")
        if self.shared_intermediate_size:
            shared = Experts(1, d, self.shared_intermediate_size, self.dtype,
                             self.activation, name="shared_expert")
            shared = shared(x[None])[0].astype(jnp.float32)
            if self.shared_gate:
                w = self.param("shared_expert_gate", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", None)), (d, 1),
                    jnp.float32)
                shared = shared * shared_expert_gate(x, w.astype(self.dtype))
            out = out + shared
        total = t * k if valid is None else k * jnp.sum(valid.astype(jnp.int32))
        local = held_assignments(topk_idx, self.held_offset, count, valid)[1]
        sizes = held_group_sizes(local, count)
        for name, value in (("assignments", total), ("held_assignments", held),
                            ("held_wide_calls", wide),
                            ("experts_touched", jnp.sum(sizes > 0)),
                            ("experts_held", count),
                            ("weight_tile_revisits",
                             weight_tile_revisits(sizes, tm))):
            self.sow("counters", name, jnp.asarray(value, jnp.int32),
                     init_fn=lambda: jnp.zeros([], jnp.int32),
                     reduce_fn=lambda a, b_: a + b_)
        return out.astype(x.dtype)
