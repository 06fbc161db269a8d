"""How a Qwen2.5 configuration file becomes the PROGRAM's model: the one
place the benchmark names the program's model constructors. The widths come
from the file, key for key; nothing here chooses a size."""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads",
         "max_position_embeddings", "rope_theta", "rms_norm_eps",
         "tie_word_embeddings", "attention_qkv_bias")


def model_config(sizes: Dict[str, Any], **overrides):
    """The program's config object for these sizes. `overrides` are run
    settings that change no width (remat, loss chunking, dtype)."""
    from deepspeed_tpu.models.qwen2 import Qwen2Config
    return Qwen2Config(**{k: sizes[k] for k in _KEYS}, **overrides)


def materialize(cfg, seed: int, dtype):
    """(model, weights): the whole tree made on the device in one jitted
    call from the seed, in the type it is served or trained from."""
    import jax
    from deepspeed_tpu.models.qwen2 import materialize_params
    return materialize_params(cfg, rng=jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              param_dtype=dtype)


def partition_specs(cfg):
    from deepspeed_tpu.models.qwen2 import init_params_and_specs
    return init_params_and_specs(cfg)[1]


def loss_fn(model):
    from deepspeed_tpu.models.qwen2 import llama_loss_fn
    return llama_loss_fn(model)
