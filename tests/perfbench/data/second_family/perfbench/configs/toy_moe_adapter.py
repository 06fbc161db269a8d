"""How the toy sparse-expert file becomes the PROGRAM's model: over
`deepspeed_tpu.models.mixtral` as it stands. Every size comes from the file."""

_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "num_local_experts",
         "num_experts_per_tok", "max_position_embeddings", "rope_theta",
         "rms_norm_eps")


def model_config(sizes, remat=False, remat_policy="nothing", dtype=None, **_):
    """`**_`: run settings of a dense recipe this model has no switch for
    (the chunked loss)."""
    from deepspeed_tpu.models.mixtral import MixtralConfig
    return MixtralConfig(**{k: sizes[k] for k in _KEYS}, remat=remat,
                         remat_policy=remat_policy, dtype=dtype)


def materialize(cfg, seed, dtype):
    import jax
    from deepspeed_tpu.models.mixtral import init_mixtral
    model, params, _ = init_mixtral(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    return model, jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def partition_specs(cfg):
    from deepspeed_tpu.models.mixtral import init_mixtral
    return init_mixtral(cfg)[2]


def loss_fn(model):
    from deepspeed_tpu.models.mixtral import mixtral_loss_fn
    return mixtral_loss_fn(model)
