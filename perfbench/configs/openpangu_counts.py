"""Operations and bytes of an openPangu configuration, from its file's sizes
(`perfbench/flops.py` asks here first). `n_routed_experts` is what the chip
HOLDS of the `router_experts` the router scores, so a token runs, on this
chip and on average, `num_experts_per_tok x held / scored` routed experts
(the Keye rule), beside the shared expert."""

from __future__ import annotations

from typing import Any, Dict


def _qk(cfg):
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def _latent(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def _attention(cfg):
    """W_qa, W_qb, W_kva, W_kvb and W_o."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * _qk(cfg) \
        + d * _latent(cfg) + cfg["kv_lora_rank"] * nh * (
            cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + nh * cfg["v_head_dim"] * d


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _dense_ffn(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _router(cfg):
    return cfg["hidden_size"] * cfg["router_experts"]


def _layers(cfg):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation (8 x 16 / 256 of a token at the cell's sizes), the
    shared expert, and the output head."""
    dense, routed = _layers(cfg)
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    return int(cfg["num_hidden_layers"] * _attention(cfg)
               + dense * _dense_ffn(cfg)
               + routed * (_router(cfg) + (held + cfg["n_shared_experts"])
                           * _expert(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: every held expert whole, the embedding, and the
    small ones (FOUR norms a layer and the last; the norms of the two
    compressions)."""
    dense, routed = _layers(cfg)
    small = cfg["num_hidden_layers"] * (
        4 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) \
        + cfg["hidden_size"]
    return int(cfg["num_hidden_layers"] * _attention(cfg)
               + dense * _dense_ffn(cfg)
               + routed * (_router(cfg) + (cfg["n_routed_experts"]
                                           + cfg["n_shared_experts"])
                           * _expert(cfg))
               + 2 * cfg["hidden_size"] * cfg["vocab_size"] + small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, and attention's
    scores and weighted sum, in the expanded form, over the causal half of
    the sequence."""
    attn = 6.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * (_qk(cfg) + cfg["v_head_dim"]) * seq / 2.0
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """The latent row of every layer: 576 values, once for all heads."""
    return cfg["num_hidden_layers"] * bytes_per * _latent(cfg)


def latent_read_bytes(cfg: Dict[str, Any], batch: int,
                      context: float) -> float:
    """The least `mla_latent_decode` must move in one decode step at
    `context` positions a sequence: every layer's latent rows read once,
    576 x 2 bytes a cached token, unpadded, whatever the number of heads."""
    return batch * context * cfg["num_hidden_layers"] * 2 * _latent(cfg)


def latent_attn_flops(cfg: Dict[str, Any], batch: int,
                      context: float) -> float:
    """The least the ABSORBED attention must compute in one decode step:
    every head's score against every cached row (`rank + rope` wide) and its
    share of the weighted sum (`rank` wide), 2 a multiply-add."""
    return batch * context * cfg["num_hidden_layers"] \
        * cfg["num_attention_heads"] * 2 \
        * (_latent(cfg) + cfg["kv_lora_rank"])


def causal_prefill_flops(cfg: Dict[str, Any], batch: int,
                         prompt: float) -> float:
    """The least the EXPANDED attention must compute in one batch's
    prefill: every query of every row against the positions up to its own,
    a score `qk` wide and a weighted sum `v` wide a head, 2 a
    multiply-add."""
    pairs = prompt * (prompt + 1) / 2.0
    return batch * pairs * cfg["num_hidden_layers"] \
        * cfg["num_attention_heads"] * 2 * (_qk(cfg) + cfg["v_head_dim"])
