"""Operations and bytes of a qwen3_next configuration (Qwen3-Next-80B-A3B),
from its file's sizes (`perfbench/flops.py` asks here first). `num_experts`
is what the chip HOLDS of the `router_experts` the router scores, so a token
runs, on this chip and on average, `num_experts_per_tok x held / scored`
routed experts (the Keye rule), beside the gated shared expert. A layer's
mixer is decided by its PUBLISHED index (`published_layers`); `full_layers`
and `gdn_layers` are written out in the file: a kernel's share BY CALL
divides a step's count by them."""

from __future__ import annotations

from typing import Any, Dict


def _layers(cfg: Dict[str, Any]):
    """(GDN layers, full-attention layers)."""
    kept = cfg.get("published_layers") or range(cfg["num_hidden_layers"])
    full = sum((p + 1) % cfg["full_attention_interval"] == 0 for p in kept)
    return len(kept) - full, full


def _gdn_dims(cfg):
    """(key_dim, value_dim, value heads)."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"],
            cfg["linear_num_value_heads"])


def _gdn(cfg):
    """W_qkvz, W_ba, W_out."""
    kd, vd, nv = _gdn_dims(cfg)
    return cfg["hidden_size"] * (2 * kd + 2 * vd + 2 * nv) \
        + vd * cfg["hidden_size"]


def _gdn_small(cfg):
    """The convolution, `A_log`, `dt_bias`, the head norm's weight."""
    kd, vd, nv = _gdn_dims(cfg)
    return cfg["linear_conv_kernel_dim"] * (2 * kd + vd) + 2 * nv \
        + cfg["linear_value_head_dim"]


def _full(cfg):
    """W_q (query and gate), W_o at all heads; W_k, W_v at the KV heads."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return cfg["hidden_size"] * hd * (3 * nh + 2 * nkv)


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _outside_experts(cfg):
    """A layer's router, shared expert and the shared expert's gate."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation (10 x 64 / 512 of a token at the cell's sizes), the
    shared expert, and the output head."""
    g, a = _layers(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return int(g * _gdn(cfg) + a * _full(cfg)
               + (g + a) * (_outside_experts(cfg) + held * _expert(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: every held expert whole, the embedding, two
    norms a layer and the last, a full layer's query and key head norms, a
    GDN layer's small vectors."""
    g, a = _layers(cfg)
    d = cfg["hidden_size"]
    small = g * _gdn_small(cfg) + a * 2 * cfg["head_dim"] \
        + (2 * (g + a) + 1) * d
    return int(g * _gdn(cfg) + a * _full(cfg)
               + (g + a) * (_outside_experts(cfg)
                            + cfg["num_experts"] * _expert(cfg))
               + 2 * d * cfg["vocab_size"] + small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, the full layers'
    causal scores and weighted sums (`head_dim` wide each), and the delta
    rule (a multiply-add each to decay, read by the key, correct and read by
    the query each element of a value head's state, forward)."""
    g, a = _layers(cfg)
    attn = 3.0 * a * 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * (seq + 1) / 2.0
    state = 3.0 * 8.0 * g * _gdn_dims(cfg)[2] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]
    return 6.0 * matmul_params(cfg) + attn + state


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of the FULL layers: the only cache that grows with the
    sequence (a GDN layer keeps a fixed state, `gdn_state_bytes`)."""
    return 2 * _layers(cfg)[1] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * bytes_per


def gdn_state_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The GDN layers' float32 matrix states for `batch` sequences."""
    return 4 * _layers(cfg)[0] * batch * _gdn_dims(cfg)[2] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def gdn_update_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The least `gdn_state_update` must move in one decode step of `batch`
    sequences, all GDN layers: each layer's float32 state read once and
    written once. Its other operands (q, k, v, the decay: kilobytes a row)
    are left out, so the share of the roofline errs low."""
    return 2 * gdn_state_bytes(cfg, batch)


def full_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `self_attn_dense_decode` must move in one decode step at
    `context` positions a sequence: every full layer's rows of K and V read
    once."""
    return batch * context * kv_bytes_per_token(cfg)


def full_prefill_flops(cfg: Dict[str, Any], batch: int,
                       prompt: float) -> float:
    """The least the FULL layers' attention must compute in one batch's
    prefill: every query against the positions up to its own, a score and a
    weighted sum `head_dim` wide a head, 2 a multiply-add."""
    return batch * prompt * (prompt + 1) / 2.0 * _layers(cfg)[1] \
        * cfg["num_attention_heads"] * 4 * cfg["head_dim"]


def bytes_by_kind(cfg: Dict[str, Any], batch: int, max_len: int) -> dict:
    """What a batch of `batch` sequences of up to `max_len` positions holds
    beside the weights, by kind: the full layers' K and V (bf16), the GDN
    layers' float32 states, their convolution tails (bf16)."""
    g, _ = _layers(cfg)
    kd, vd, _ = _gdn_dims(cfg)
    return {"full_kv_bytes": batch * max_len * kv_bytes_per_token(cfg),
            "state_bytes": gdn_state_bytes(cfg, batch),
            "conv_bytes": g * batch * (cfg["linear_conv_kernel_dim"] - 1)
            * (2 * kd + vd) * 2}
