"""Operations, parameters and KV bytes a token of the toy sparse-expert file
needs. `matmul_params` is the ACTIVE parameters: of a layer's experts only
the `num_experts_per_tok` a token is routed to, and the router."""


def _layer(cfg, experts):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = h // cfg["num_attention_heads"]
    attn = 2 * h * cfg["num_attention_heads"] * hd \
        + 2 * h * cfg["num_key_value_heads"] * hd
    return attn + experts * 3 * h * f + h * cfg["num_local_experts"]


def matmul_params(cfg):
    return cfg["num_hidden_layers"] * _layer(cfg, cfg["num_experts_per_tok"]) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg):
    h = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (_layer(cfg, cfg["num_local_experts"])
                                       + 2 * h) + h + 2 * h * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    return 6.0 * matmul_params(cfg) + 6.0 * cfg["num_hidden_layers"] \
        * cfg["hidden_size"] * seq


def kv_bytes_per_token(cfg, bytes_per=2):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd \
        * bytes_per
