"""How an afmoe configuration file (Arcee Trinity) becomes the PROGRAM's
model: the one place the benchmark names the program's constructors for this
family. The widths come from the file, key for key; nothing here chooses a
size. The file's `num_experts` is what this chip HOLDS and `router_experts`
what the router scores (its `deployment` says which share). The source's
switches that select a FORM of a layer are checked against the one form the
program has (`assumed` in the file says which); a file that sets another is
refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`), the routers' and
the routed experts' served at a multiple of their seeded range (below);
`--seed` draws the prompts, as in the five other routed families' cells
(PERF.md, PR 41: twelve draws of the weights read `out_tok_s` 1.6% apart)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_dense_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "global_attn_every_n_layers",
         "sliding_window", "rope_theta", "num_experts", "router_experts",
         "expert_offset", "num_experts_per_tok", "moe_intermediate_size",
         "num_shared_experts", "route_norm", "route_scale", "mup_enabled",
         "rms_norm_eps", "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"hidden_act": "silu", "score_func": "sigmoid", "rope_scaling": None,
         "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
         "num_expert_groups": 1, "num_limited_groups": 1}

WEIGHTS_SEED = 60
# The router's weights are served at this multiple of their seeded range: the
# file's `assumed.router` has the reckoning, fixed before any table was read
# (`perfbench/traffic/generate-agent-8k.margin_sim.py` repeats it). The
# adapter scales the seeded tree in place; the program has no such option.
ROUTER_SPREAD = 4.0
# The routed experts' UP and DOWN projections are served at half their seeded
# range, a quarter of a routed expert's output, as Nemotron's and Ling's are
# (`nemotron_h_adapter`, `ling_linear_adapter`). A wider router widens the
# bf16 rounding of its logits with its margins: at 4.0 x, 18% of the positions
# at a routing margin of `MARGIN_SAFE` or more take another held expert than
# the float32 reference in some layer. As seeded a held expert's term is a
# third of its layer's result, one flip moves the first token's logits by
# 0.07 of their spread and cascades through the later routers to 0.1-0.25,
# and 5 of 1,385 such positions missed by more than `TIE_TOL` (the driver's
# check of PR 60 read one: seed 730477733, margin 0.0222, gap 0.078). At a
# quarter of the output 0 of 2,669 did, the widest gap 0.025 (the file's
# `assumed.routed_expert_damp` has the table; PERF.md, PR 60).
ROUTED_EXPERT_DAMP = 0.5


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.afmoe import AfmoeConfig
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    if other:
        raise ValueError(f"afmoe: the program has one form of each layer; "
                         f"the file asks for {other}")
    return AfmoeConfig(**{k: sizes[k] for k in _KEYS},
                       layer_types=tuple(sizes["layer_types"]), **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.afmoe import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def served(path, leaf):
        name = jax.tree_util.keystr(path[-2:])
        by = ROUTER_SPREAD if name == "['gate']['wg']" else \
            ROUTED_EXPERT_DAMP if name in ("['experts']['up']",
                                           "['experts']['down']") else None
        return leaf if by is None else (leaf * by).astype(leaf.dtype)
    # in place: the chip cannot hold the 5.67 GB tree twice beside a batch
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(served, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.afmoe import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.afmoe import afmoe_loss_fn
    return afmoe_loss_fn(model)
