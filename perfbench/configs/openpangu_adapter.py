"""How an openPangu configuration file becomes the PROGRAM's model: the one
place the benchmark names the program's constructors for this family. The
widths come from the file, key for key; nothing here chooses a size. The
file's `n_routed_experts` is what this chip HOLDS and `router_experts` what
the router scores (its `deployment` says which share). The source's switches
that select a FORM of a layer are checked against the one form the program
has (`assumed` in the file says which); a file that sets another is refused.

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`); `--seed` draws the
prompts, as in the four other routed families' cells (PERF.md, PR 41: twelve
draws of the weights read `out_tok_s` 1.6% apart)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
         "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_theta", "router_experts", "expert_offset",
         "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
         "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
         "max_position_embeddings")
# the one form of each layer the program has, as the source's switches name it
_FORM = {"attention_bias": False, "hidden_act": "silu", "sandwich_norm": True,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
         "rope_scaling": None}

WEIGHTS_SEED = 58
# The router's weights are served AS SEEDED, 1.0 times their seeded range:
# nothing in the tree is scaled. The file's `assumed.router` has the
# reckoning (the simulation kept as
# `perfbench/traffic/generate-longctx-dense.margin_sim.py`, run before any
# table was read): with no groups and no selection bias 0.91 of rows are at a
# safe routing margin at the seeded spread, where DeepSeek's grouped router
# read 0.61 and was served at 2.0.
ROUTER_SPREAD = 1.0


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.openpangu import OpenPanguConfig
    del remat
    other = {k: sizes[k] for k, v in _FORM.items() if sizes.get(k, v) != v}
    if other or sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError(f"openpangu: the program has one form of each "
                         f"layer; the file asks for {other or 'other sizes'}")
    return OpenPanguConfig(**{k: sizes[k] for k in _KEYS},
                           num_experts=sizes["n_routed_experts"], **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.openpangu import materialize_params
    del seed
    return materialize_params(cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED),
                              param_dtype=dtype)


def partition_specs(cfg):
    from deepspeed_tpu.models.openpangu import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.openpangu import openpangu_loss_fn
    return openpangu_loss_fn(model)
