"""Operations and bytes of an afmoe configuration (Arcee Trinity), from its
file's sizes (`perfbench/flops.py` asks here first). `num_experts` is what
the chip HOLDS of the `router_experts` the router scores, so a token runs, on
this chip and on average, `num_experts_per_tok x held / scored` routed
experts (the Keye rule), beside the shared expert. `window_layers` and
`full_layers` are written out in the file: a kernel's share BY CALL divides
a step's count by them."""

from __future__ import annotations

from typing import Any, Dict


def _heads(cfg):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _attention(cfg):
    """W_q, W_g (the gate), W_o at all heads; W_k, W_v at the KV heads."""
    nh, nkv, hd = _heads(cfg)
    return cfg["hidden_size"] * hd * (3 * nh + 2 * nkv)


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _dense_ffn(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _router(cfg):
    return cfg["hidden_size"] * cfg["router_experts"]


def _layers(cfg):
    dense = cfg["num_dense_layers"]
    return dense, cfg["num_hidden_layers"] - dense


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation (8 x 16 / 128 of a token at the cell's sizes), the
    shared expert, and the output head."""
    dense, routed = _layers(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return int(cfg["num_hidden_layers"] * _attention(cfg)
               + dense * _dense_ffn(cfg)
               + routed * (_router(cfg) + (held + cfg["num_shared_experts"])
                           * _expert(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: every held expert whole, the router with its
    selection bias, the embedding, and the small ones (FOUR norms a layer
    and the last; a query and a key head norm a layer)."""
    dense, routed = _layers(cfg)
    small = cfg["num_hidden_layers"] * (
        4 * cfg["hidden_size"] + 2 * cfg["head_dim"]) + cfg["hidden_size"]
    return int(cfg["num_hidden_layers"] * _attention(cfg)
               + dense * _dense_ffn(cfg)
               + routed * (_router(cfg) + cfg["router_experts"]
                           + (cfg["num_experts"] + cfg["num_shared_experts"])
                           * _expert(cfg))
               + 2 * cfg["hidden_size"] * cfg["vocab_size"] + small)


def _band_pairs(cfg, seq: float) -> float:
    """(query, key) pairs a row of `seq` positions attends in ONE window
    layer: the causal triangle, less the triangle that lies a window back
    (so the first window's queries count only what precedes them)."""
    back = max(seq - cfg["sliding_window"], 0.0)
    return seq * (seq + 1) / 2.0 - back * (back + 1) / 2.0


def _pairs(cfg, seq: float) -> float:
    """The pairs a row attends, summed over the layers: a full layer the
    causal triangle, a window layer its band."""
    return cfg["full_layers"] * seq * (seq + 1) / 2.0 \
        + cfg["window_layers"] * _band_pairs(cfg, seq)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, and attention's
    scores and weighted sum over the pairs each kind of layer attends."""
    nh, _, hd = _heads(cfg)
    return 6.0 * matmul_params(cfg) + 3.0 * 4 * nh * hd * _pairs(cfg, seq) \
        / seq


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of the FULL layers, which keep every token; a window layer's
    ring is a fixed `sliding_window` slots a row whatever the length
    (`ring_read_bytes` counts what a step reads of it)."""
    _, nkv, hd = _heads(cfg)
    return 2 * cfg["full_layers"] * nkv * hd * bytes_per


def ring_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `self_attn_ring_decode` must move in one decode step at
    `context` positions a sequence: every window layer's live slots of K and
    V read once, `min(context, sliding_window)` of them a row."""
    _, nkv, hd = _heads(cfg)
    return batch * min(context, cfg["sliding_window"]) \
        * cfg["window_layers"] * 2 * nkv * hd * 2


def full_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `self_attn_dense_decode` must move in one decode step at
    `context` positions a sequence: every full layer's rows of K and V read
    once."""
    _, nkv, hd = _heads(cfg)
    return batch * context * cfg["full_layers"] * 2 * nkv * hd * 2


def band_prefill_flops(cfg: Dict[str, Any], batch: int,
                       prompt: float) -> float:
    """The least the WINDOW layers' attention must compute in one batch's
    prefill: every query against the positions of its window up to its own
    (the band's pairs alone: the causal triangle less the triangle a window
    back, so the first window's queries count only what precedes them), a
    score and a weighted sum `head_dim` wide a head, 2 a multiply-add."""
    nh, _, hd = _heads(cfg)
    return batch * _band_pairs(cfg, prompt) * cfg["window_layers"] \
        * nh * 4 * hd
