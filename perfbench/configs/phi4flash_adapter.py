"""How a Phi-4-mini-flash configuration file becomes the PROGRAM's model: the
one place the benchmark names the program's constructors for this family.
The widths come from the file, key for key; nothing here chooses a size. The
keys after `vocab_size` are what the source's `config.json` does not give:
the file states them, with the reason, under `assumed`.

`--seed` draws the weights and the prompts: nothing routes, so a draw of the
weights does not move the speed."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("hidden_size", "intermediate_size", "layer_norm_eps",
         "max_position_embeddings", "mb_per_layer", "num_attention_heads",
         "num_hidden_layers", "num_key_value_heads", "sliding_window",
         "tie_word_embeddings", "vocab_size",
         "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def model_config(sizes: Dict[str, Any], remat: bool = False, **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (dtype); `remat` is a training setting the
    serving path has no use for."""
    from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
    del remat
    if sizes["mlp_bias"] or sizes["lm_head_bias"]:
        raise ValueError("phi4flash: the program's FFN and head have no bias")
    return Phi4FlashConfig(**{k: sizes[k] for k in _KEYS if k in sizes},
                           **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call from the seed, in the type it is served from."""
    import jax
    from deepspeed_tpu.models.phi4flash import materialize_params
    return materialize_params(cfg, rng=jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              param_dtype=dtype)


def partition_specs(cfg):
    from deepspeed_tpu.models.phi4flash import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.phi4flash import phi4flash_loss_fn
    return phi4flash_loss_fn(model)
