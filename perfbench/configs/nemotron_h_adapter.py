"""How a Nemotron-H configuration file becomes the PROGRAM's model: the one
place the benchmark names the program's constructors for this family. The
widths come from the file, key for key; nothing here chooses a size. The
file's `n_routed_experts` is what this chip HOLDS and `router_experts` what
the router scores (its `deployment` says which share).

The WEIGHTS are one draw for every run (`WEIGHTS_SEED`); `--seed` draws the
prompts. A routed model's speed is its router's: which of the held experts
a decode step touches is decided by the seeded router and its bias, and
twelve seeds' weights read `out_tok_s` 1.6% apart (PERF.md, PR 41), more
than the cell's bound can tell. One draw serves the same model on every
seed, as serve-chat serves one schedule (PR 40)."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "hybrid_override_pattern", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rope_theta", "attention_rotary",
         "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
         "conv_kernel", "chunk_size", "use_conv_bias", "mamba_proj_bias",
         "time_step_min", "time_step_max", "time_step_floor",
         "n_routed_experts", "router_experts", "expert_offset",
         "num_experts_per_tok", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "routed_scaling_factor",
         "norm_topk_prob", "n_group", "topk_group", "router_bias_scale",
         "norm_eps", "max_position_embeddings")

WEIGHTS_SEED = 41
# The routed experts' two projections are served at HALF their seeded range,
# an eighth of their output (relu(u W)^2 W'). At the family's 0.02 a seeded
# expert's output is as large as the residual stream, a routing near-tie
# that bf16 rounding flips moves the logits by a tenth and the flips cascade
# through the later routers: 1.9% of first tokens then miss the float32
# reference's by more than TIE_TOL whatever their own routing margin
# (PERF.md, PR 41; the file's `assumed` says the same).
ROUTED_EXPERT_DAMP = 0.5


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    del remat
    return NemotronHConfig(**{k: sizes[k] for k in _KEYS}, **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call, in the type it is served from; the same tree whatever `seed`."""
    import jax
    from deepspeed_tpu.models.nemotron_h import materialize_params
    del seed
    model, params = materialize_params(
        cfg, rng=jax.random.PRNGKey(WEIGHTS_SEED), param_dtype=dtype)

    def damp(path, leaf):
        routed = jax.tree_util.keystr(path[-2:]) in (
            "['experts']['up']", "['experts']['down']")
        return leaf * ROUTED_EXPERT_DAMP if routed else leaf
    # in place: the chip cannot hold the 9 GB tree twice
    return model, jax.jit(
        lambda tree: jax.tree_util.tree_map_with_path(damp, tree),
        donate_argnums=0)(params)


def partition_specs(cfg):
    from deepspeed_tpu.models.nemotron_h import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.nemotron_h import nemotron_h_loss_fn
    return nemotron_h_loss_fn(model)
