"""Operations and bytes of a Keye-sparse configuration, from its file's
sizes (`perfbench/flops.py` asks here first). `num_experts` is what the chip
HOLDS of the `router_experts` the router scores, so a token runs, on this
chip and on average, `num_experts_per_tok x held / scored` routed experts.
The indexer's sizes are the file's `sa_config`."""

from __future__ import annotations

from typing import Any, Dict


def _attention(cfg):
    """q, k, v and out."""
    d, w = cfg["hidden_size"], cfg["head_dim"]
    return d * w * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def _indexer(cfg):
    """The index queries, the one index key and the heads' weights."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _router(cfg):
    return cfg["hidden_size"] * cfg["router_experts"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation, and the output head."""
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return int(cfg["num_hidden_layers"] * (
        _attention(cfg) + _indexer(cfg) + _router(cfg) + held * _expert(cfg))
        + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: every held expert whole, the embedding, and the
    norms (two a layer and the last; a layer's q and k norms over a head's
    width; the index key's LayerNorm, weight and bias)."""
    small = cfg["num_hidden_layers"] * (
        2 * cfg["hidden_size"] + 2 * cfg["head_dim"]
        + 2 * cfg["sa_config"]["indexer_head_dim"]) + cfg["hidden_size"]
    return int(cfg["num_hidden_layers"] * (
        _attention(cfg) + _indexer(cfg) + _router(cfg)
        + cfg["num_experts"] * _expert(cfg))
        + 2 * cfg["hidden_size"] * cfg["vocab_size"] + small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, the indexer's
    scores over the causal half of the sequence, and attention's scores and
    weighted sum over the positions chosen (a program that reads the slab
    densely under a mask does more; the mathematics asks for these)."""
    sa = cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    index = 3.0 * layers * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * seq
    attn = 12.0 * layers * cfg["num_attention_heads"] * cfg["head_dim"] \
        * min(sa["topk"], seq / 2.0)
    return 6.0 * matmul_params(cfg) + index + attn


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of every layer and, beside them, its one index key."""
    return cfg["num_hidden_layers"] * bytes_per * (
        2 * cfg["num_key_value_heads"] * cfg["head_dim"]
        + cfg["sa_config"]["indexer_head_dim"])


def index_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `sparse_index_select` must move in one decode step at
    `context` positions a sequence: every layer's index keys read once, 64 x
    2 bytes a cached token, unpadded (the chip lays 64 out as 128 lanes, so
    the share cannot pass 50%)."""
    return batch * context * cfg["num_hidden_layers"] * 2 \
        * cfg["sa_config"]["indexer_head_dim"]


def selected_read_bytes(cfg: Dict[str, Any], batch: int,
                        context: float) -> float:
    """The least attention over the SELECTION must move in one decode step:
    K and V of the `min(topk, context)` chosen positions a row a layer,
    whatever the program reads to get them."""
    return batch * min(cfg["sa_config"]["topk"], context) \
        * cfg["num_hidden_layers"] * 2 * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"]
