"""Operations a model needs per token, from its configuration's sizes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Matrix multiplications only (2 per multiply-add); recomputed
operations are not counted; the embedding lookup is a gather and costs none,
the output projection is a matmul and is counted once (tied or not).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that take part in a matmul for every token: the layers'
    projections and the output head. Norms and biases are left out (under
    0.01% at these widths)."""
    h, f, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * hd
    kv = 2 * h * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * h
    return cfg["num_hidden_layers"] * (q + kv + o + 3 * h * f) \
        + h * cfg["vocab_size"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: `matmul_params`, the biases and norms, and an
    untied input embedding."""
    h, hd = cfg["hidden_size"], head_dim(cfg)
    bias = (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * hd \
        if cfg.get("attention_qkv_bias", True) else 0
    extra = cfg["num_hidden_layers"] * (bias + 2 * h) + h
    if not cfg.get("tie_word_embeddings", False):
        extra += h * cfg["vocab_size"]
    return matmul_params(cfg) + extra


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention's
    scores and weighted sum, 12 * layers * heads * head_dim * seq / 2."""
    attn = 6.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * head_dim(cfg) * seq
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * head_dim(cfg) * bytes_per


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks. An unknown kind is an error, never a
    default: a share of the wrong chip's peak is worse than none."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)}); add its published numbers "
                       "to perfbench/peaks.json")
    return table[device_kind]
