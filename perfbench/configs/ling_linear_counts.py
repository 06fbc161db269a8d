"""Operations and bytes of a Ling-linear configuration, from its file's
sizes (`perfbench/flops.py` asks here first). `num_experts` is what the chip
HOLDS of the `router_experts` the router scores, so a token runs, on this
chip and on average, `num_experts_per_tok x held / scored` routed experts.
A layer's mixer is decided by its PUBLISHED index (`published_layers`)."""

from __future__ import annotations

from typing import Any, Dict


def _layers(cfg: Dict[str, Any]):
    """(KDA layers, MLA layers, dense-FFN layers, expert layers)."""
    kept = cfg.get("published_layers") or range(cfg["num_hidden_layers"])
    mla = sum((p + 1) % cfg["layer_group_size"] == 0 for p in kept)
    dense = cfg["first_k_dense_replace"]
    return len(kept) - mla, mla, dense, len(kept) - dense


def _inner(cfg):
    return cfg["num_attention_heads"] * cfg["head_dim"]


def _kda(cfg):
    """q, k, v, the decay gate, the output gate (full rank), beta, out."""
    d, di = cfg["hidden_size"], _inner(cfg)
    return d * (5 * di + cfg["num_attention_heads"]) + di * d


def _mla(cfg):
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d * nh * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                      + cfg["v_head_dim"]) \
        + d * nh + nh * cfg["v_head_dim"] * d


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _outside_experts(cfg):
    """An expert layer's router and shared expert."""
    return cfg["hidden_size"] * cfg["router_experts"] \
        + 3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def _dense_ffn(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _mixers_and_dense(cfg):
    k, a, f, _ = _layers(cfg)
    return k * _kda(cfg) + a * _mla(cfg) + f * _dense_ffn(cfg)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation, and the output head."""
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return int(_mixers_and_dense(cfg)
               + _layers(cfg)[3] * (_outside_experts(cfg)
                                    + held * _expert(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: every held expert whole, the embedding, the
    norms (two a layer and the last; an MLA layer's latent, query and rope-key
    norms) and the KDA layers' small vectors (three convolutions, `A_log`,
    `dt_bias`, the head norm's weight)."""
    k, a, _, e = _layers(cfg)
    d, di = cfg["hidden_size"], _inner(cfg)
    small = k * (3 * di * cfg["short_conv_kernel_size"]
                 + cfg["num_attention_heads"] + di + cfg["head_dim"]) \
        + a * (cfg["kv_lora_rank"] + cfg["qk_nope_head_dim"]
               + 2 * cfg["qk_rope_head_dim"]) \
        + e * cfg["router_experts"] + (2 * (k + a) + 1) * d
    return int(_mixers_and_dense(cfg)
               + e * (_outside_experts(cfg)
                      + cfg["num_experts"] * _expert(cfg))
               + 2 * d * cfg["vocab_size"] + small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, causal latent
    attention's scores (192 wide) and weighted sum (128 wide) in the
    expanded form, and the delta rule (a multiply-add each to decay, read by
    the key, correct and read by the query each element of a head's state,
    forward)."""
    k, a, _, _ = _layers(cfg)
    nh = cfg["num_attention_heads"]
    attn = 3.0 * a * nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                           + cfg["v_head_dim"]) * seq
    state = 3.0 * 8.0 * k * nh * cfg["head_dim"] ** 2
    return 6.0 * matmul_params(cfg) + attn + state


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """The LATENT rows of the MLA layers: the only cache that grows with the
    sequence (the KDA layers keep a fixed state, `kda_state_bytes`)."""
    return _layers(cfg)[1] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * bytes_per


def kda_state_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The KDA layers' float32 matrix states for `batch` sequences."""
    return 4 * _layers(cfg)[0] * batch * cfg["num_attention_heads"] \
        * cfg["head_dim"] ** 2


def kda_update_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The least `kda_state_update` must move in one decode step of `batch`
    sequences, all KDA layers: each layer's float32 state read once and
    written once. Its other operands (q, k, v, the decays: kilobytes a row)
    are left out, so the share of the roofline errs low."""
    return 2 * kda_state_bytes(cfg, batch)


def latent_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `mla_latent_decode` must move in one decode step at
    `context` positions a sequence: every MLA layer's latent rows read ONCE
    for all heads, 576 x 2 bytes a cached token, unpadded (the chip lays 576
    out as 640 lanes, so the share cannot pass 90%)."""
    return batch * context * kv_bytes_per_token(cfg)
