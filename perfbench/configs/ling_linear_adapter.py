"""How a Ling-linear configuration file becomes the PROGRAM's model: the one
place the benchmark names the program's constructors for this family. The
widths come from the file, key for key; nothing here chooses a size. The
file's `num_experts` is what this chip HOLDS and `router_experts` what the
router scores (its `deployment` says which share). The source's switches
that select a FORM of a layer are checked against the one form the program
has (`assumed` in the file says which); a file that sets another is refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`); `--seed` draws the
prompts: which of the held experts a decode step touches is decided by the
seeded router and its bias, and on the other routed family twelve draws read
`out_tok_s` 1.6% apart (PERF.md, PR 41), more than the cell's bound can
tell."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
         "first_k_dense_replace", "layer_group_size", "published_layers",
         "num_attention_heads", "head_dim", "short_conv_kernel_size",
         "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts",
         "router_experts", "expert_offset", "num_experts_per_tok",
         "moe_intermediate_size", "moe_shared_expert_intermediate_size",
         "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group",
         "router_bias_scale", "rms_norm_eps", "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"q_lora_rank": None, "use_qk_norm": True, "use_mla_nope": False,
         "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
         "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
         "linear_silu": True, "use_nGPT": False, "scale_router_input": False,
         "value_norm": False, "up_proj_norm": False,
         "gated_attention_proj_granularity_type": "head_wise",
         "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True}

WEIGHTS_SEED = 47
# The routed experts' UP and DOWN projections are served at half their seeded
# range: a quarter of a routed expert's output. As seeded, a row's 1,023
# earlier tokens flip experts at their routers' near-ties under bf16
# rounding, the flips reach the judged position through the state and the
# latent rows, and 2 of 200 first tokens at a routing margin of their own
# over MARGIN_SAFE missed the float32 reference's by more than TIE_TOL (13
# of all 1,024 rows read). The builder's first-token table (PERF.md, PR 47;
# the file's `assumed` has the counts and the rules) took a quarter. The
# adapter scales the seeded tree in place; the program has no such option.
ROUTED_EXPERT_DAMP = 0.5


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.ling_linear import LingLinearConfig
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    if other or sizes["rotary_dim"] != sizes["qk_rope_head_dim"] or \
            sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError(f"ling_linear: the program has one form of each "
                         f"layer; the file asks for {other or 'other sizes'}")
    kept = sizes["published_layers"]
    clamps = [sizes[k][p] for k in ("expert_swiglu_limit_list",
                                    "share_expert_swiglu_limit_list")
              for p in kept]
    if any(clamps):
        raise ValueError("ling_linear: a kept layer has a swiglu limit; the "
                         "program has no clamp")
    return LingLinearConfig(**{k: sizes[k] for k in _KEYS}, **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.ling_linear import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def damp(path, leaf):
        routed = jax.tree_util.keystr(path[-2:]) in (
            "['experts']['up']", "['experts']['down']")
        return leaf * ROUTED_EXPERT_DAMP if routed else leaf
    # in place: the chip cannot hold the 8.8 GB tree twice
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(damp, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.ling_linear import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.ling_linear import ling_linear_loss_fn
    return ling_linear_loss_fn(model)
