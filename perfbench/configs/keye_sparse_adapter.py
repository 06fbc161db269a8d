"""How a Keye-sparse configuration file becomes the PROGRAM's model: the one
place the benchmark names the program's constructors for this family. The
widths come from the file, key for key; nothing here chooses a size. The
file's `num_experts` (and `num_local_experts`, the source's other name for
the same count) is what this chip HOLDS and `router_experts` what the router
scores (its `deployment` says which share). The source's switches that
select a FORM of a layer are checked against the one form the program has
(`assumed` in the file says which); a file that sets another is refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`); `--seed` draws the
prompts, as in the two other routed families' cells (PERF.md, PR 41: twelve
draws of the weights read `out_tok_s` 1.6% apart)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "rope_theta", "num_experts", "router_experts", "expert_offset",
         "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
         "rms_norm_eps", "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"attention_bias": False, "decoder_sparse_step": 1,
         "hidden_act": "silu", "mlp_only_layers": [], "sliding_window": None,
         "use_sliding_window": False, "tie_word_embeddings": False}

WEIGHTS_SEED = 51
# The router's weights are served at this multiple of their seeded range, so
# that its 128 logits have the spread of a TRAINED router's (a standard
# deviation near 4) and not a fresh draw's (0.9): the file's `assumed.router`
# has the reckoning, fixed before any table was read. The adapter scales the
# seeded tree in place; the program has no such option.
ROUTER_SPREAD = 4.4


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.keye_sparse import KeyeSparseConfig
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    sa = sizes["sa_config"]
    if other or sa["indexer_num_kv_heads"] != 1 or \
            sizes["num_local_experts"] != sizes["num_experts"] or \
            sizes["rope_scaling"].get("rope_type", "default") != "default":
        raise ValueError(f"keye_sparse: the program has one form of each "
                         f"layer; the file asks for {other or 'other sizes'}")
    return KeyeSparseConfig(
        **{k: sizes[k] for k in _KEYS},
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.keye_sparse import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def spread(path, leaf):
        router = jax.tree_util.keystr(path[-2:]) == "['gate']['wg']"
        return (leaf * ROUTER_SPREAD).astype(leaf.dtype) if router else leaf
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(spread, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.keye_sparse import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.keye_sparse import keye_sparse_loss_fn
    return keye_sparse_loss_fn(model)
