"""How a qwen3_next configuration file (Qwen3-Next-80B-A3B) becomes the
PROGRAM's model: the one place the benchmark names the program's
constructors for this family. The widths come from the file, key for key;
nothing here chooses a size. The file's `num_experts` is what this chip HOLDS
and `router_experts` what the router scores (its `deployment` says which
share). The source's switches that select a FORM of a layer are checked
against the one form the program has (`assumed` in the file says which); a
file that sets another is refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`), the routers' and
the routed experts' served at a multiple of their seeded range (below);
`--seed` draws the prompts, as in the six other routed families' cells
(PERF.md, PR 41: twelve draws of the weights read `out_tok_s` 1.6% apart)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "full_attention_interval", "num_attention_heads",
         "num_key_value_heads", "head_dim", "partial_rotary_factor",
         "rope_theta", "linear_conv_kernel_dim", "linear_key_head_dim",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_value_head_dim", "num_experts", "router_experts",
         "expert_offset", "num_experts_per_tok", "moe_intermediate_size",
         "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps",
         "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"hidden_act": "silu", "rope_scaling": None,
         "tie_word_embeddings": False, "decoder_sparse_step": 1,
         "mlp_only_layers": [], "use_sliding_window": False}

WEIGHTS_SEED = 64
# The router's weights are served at this multiple of their seeded range: the
# file's `assumed.router` has the reckoning, fixed before any table was read
# (`perfbench/traffic/generate-longctx-linear.margin_sim.py` repeats it). The
# adapter scales the seeded tree in place; the program has no such option.
ROUTER_SPREAD = 8.0
# The routed experts' UP and DOWN projections are served at half their seeded
# range, a quarter of a routed expert's output, as Nemotron's, Ling's and
# Trinity's are (`afmoe_adapter` has the readings that led there: a wider
# router widens the bf16 rounding of its logits with its margins, and at a
# quarter of the output a flipped expert moves the first token's logits by
# less than the comparison's tolerance).
ROUTED_EXPERT_DAMP = 0.5


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    if other:
        raise ValueError(f"qwen3_next: the program has one form of each "
                         f"layer; the file asks for {other}")
    pub = sizes.get("published_layers")
    return Qwen3NextConfig(**{k: sizes[k] for k in _KEYS},
                           published_layers=None if pub is None
                           else tuple(pub), **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.qwen3_next import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def served(path, leaf):
        name = jax.tree_util.keystr(path[-2:])
        by = ROUTER_SPREAD if name == "['gate']['wg']" else \
            ROUTED_EXPERT_DAMP if name in ("['experts']['up']",
                                           "['experts']['down']") else None
        return leaf if by is None else (leaf * by).astype(leaf.dtype)
    # in place: the chip cannot hold the 6.95 GB tree twice beside a batch
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(served, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.qwen3_next import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.qwen3_next import qwen3_next_loss_fn
    return qwen3_next_loss_fn(model)
