"""How a DeepSeek-sparse configuration file becomes the PROGRAM's model: the
one place the benchmark names the program's constructors for this family.
The widths come from the file, key for key; nothing here chooses a size. The
file's `n_routed_experts` is what this chip HOLDS and `router_experts` what
the router scores (its `deployment` says which share; the share may cut a
group). The source's switches that select a FORM of a layer are checked
against the one form the program has (`assumed` in the file says which); a
file that sets another is refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`); `--seed` draws the
prompts, as in the three other routed families' cells (PERF.md, PR 41: twelve
draws of the weights read `out_tok_s` 1.6% apart)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
         "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_theta", "index_n_heads", "index_head_dim", "index_topk",
         "router_experts", "expert_offset", "num_experts_per_tok",
         "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
         "norm_topk_prob", "n_group", "topk_group", "router_bias_scale",
         "rms_norm_eps", "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
_YARN = ("factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "mscale", "mscale_all_dim")

WEIGHTS_SEED = 54
# The router's weights are served at this multiple of their seeded range: the
# file's `assumed.router` has the reckoning, fixed before any table was read.
# The adapter scales the seeded tree in place; the program has no such option.
ROUTER_SPREAD = 2.0
# The embedding's rows are served at this multiple of their seeded range
# (normal(0.02), a hundredth of what every later layer's input is normalised
# to): the file's `assumed.embedding` has the readings that forced it and
# the rule, written before the reading that followed. As seeded, layer 0's
# input is 97% its own attention's output, a sum of some 200 equally weighted
# value rows; the 9 or 10 of a query's 2,048 chosen positions that bf16
# swaps at the choice's boundary then move the first token's logits by a
# fifth of their spread, and `correct`, which allows a sixteenth, is a coin.
EMBED_SPREAD = 64.0


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.deepseek_sparse import (DeepseekSparseConfig,
                                                      YarnScaling)
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    rs = sizes.get("rope_scaling")
    if other or sizes["num_key_value_heads"] != sizes["num_attention_heads"] \
            or (rs and rs.get("type") != "yarn"):
        raise ValueError(f"deepseek_sparse: the program has one form of each "
                         f"layer; the file asks for {other or 'other sizes'}")
    return DeepseekSparseConfig(
        **{k: sizes[k] for k in _KEYS}, num_experts=sizes["n_routed_experts"],
        rope_scaling=YarnScaling(**{k: rs[k] for k in _YARN}) if rs else None,
        **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.deepseek_sparse import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def spread(path, leaf):
        name = jax.tree_util.keystr(path[-2:])
        by = ROUTER_SPREAD if name == "['gate']['wg']" else \
            EMBED_SPREAD if name == "['embed_tokens']" else None
        return leaf if by is None else (leaf * by).astype(leaf.dtype)
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(spread, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.deepseek_sparse import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.deepseek_sparse import deepseek_sparse_loss_fn
    return deepseek_sparse_loss_fn(model)
