"""Operations and bytes of a Phi-4-mini-flash configuration, from its file's
sizes (`perfbench/flops.py` asks here first). The layer kinds by index are
the model's (`phi4flash_reference.py` has them): with `half = layers / 2`,
`half / 2 + 1` Mamba-1 layers, `half / 2` window-attention layers, one full
attention layer, and `(half - 2) / 2` each of memory units and cross
attention layers; every layer has the gated FFN; the head is the embedding."""

from __future__ import annotations

import math
from typing import Any, Dict


def _kinds(cfg: Dict[str, Any]):
    """(Mamba, window attention, full attention, memory unit, cross)."""
    half = cfg["num_hidden_layers"] // 2
    return half // 2 + 1, half // 2, 1, (half - 2) // 2, (half - 2) // 2


def _sizes(cfg):
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    di = cfg.get("mamba_expand", 2) * d
    rank = cfg.get("mamba_dt_rank") or math.ceil(d / 16)
    return d, hd, di, cfg.get("mamba_d_state", 16), rank


def _ffn(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _mamba(cfg):
    d, _, di, n, rank = _sizes(cfg)
    return d * 2 * di + di * (rank + 2 * n) + rank * di + di * d


def _attention(cfg, cross: bool):
    d, hd, *_ = _sizes(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = 0 if cross else 2 * cfg["num_key_value_heads"] * hd
    return d * (q + kv) + q * d


def _memory_unit(cfg):
    d, _, di, *_ = _sizes(cfg)
    return 2 * d * di


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that take part in a matmul for every token; the output head,
    which is the embedding, once. Dense: all of them are active."""
    m, w, f, g, c = _kinds(cfg)
    return int(cfg["num_hidden_layers"] * _ffn(cfg) + m * _mamba(cfg)
               + (w + f) * _attention(cfg, False) + g * _memory_unit(cfg)
               + c * _attention(cfg, True)
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: `matmul_params` (the tied embedding is the
    head) and the small vectors: two LayerNorms a layer and the last, the
    Mamba layers' convolution, dt bias, `A_log` and `D`, the attention
    layers' biases, four lambda vectors and the pair norm's weight."""
    m, w, f, g, c = _kinds(cfg)
    d, hd, di, n, _ = _sizes(cfg)
    kw = cfg.get("mamba_d_conv", 4)
    q = cfg["num_attention_heads"] * hd
    kv = 2 * cfg["num_key_value_heads"] * hd
    small = (2 * cfg["num_hidden_layers"] + 1) * 2 * d \
        + m * ((kw + 1) * di + di + n * di + di) \
        + (w + f) * (q + kv + d + 6 * hd) + c * (q + d + 6 * hd)
    return matmul_params(cfg) + int(small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per matmul weight; causal attention's scores
    and weighted sum over a pair's two softmaxes against a value twice as
    wide (1.5 x a plain head's), the window layers over at most the window;
    the recurrence (a multiply-add to update and one to read each element of
    a layer's state, forward)."""
    m, w, f, _, c = _kinds(cfg)
    d, hd, di, n, _ = _sizes(cfg)
    per_key = 6.0 * 1.5 * cfg["num_attention_heads"] * hd
    attn = per_key * ((f + c) * seq
                      + w * min(seq, 2 * cfg["sliding_window"]))
    return 6.0 * matmul_params(cfg) + attn + 12.0 * m * di * n


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of ONE full-length layer: the only cache that grows with the
    sequence (the window layers keep `sliding_window` slots whatever its
    length, the cross layers nothing)."""
    _, hd, *_ = _sizes(cfg)
    return 2 * cfg["num_key_value_heads"] * hd * bytes_per


def ssm_update_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The least `ssm_state_update*` must move in one decode step of `batch`
    sequences, all Mamba layers: each layer's float32 state read once and
    written once. Its other operands (x, dt, B, C, a layer's A) are left
    out, so the share of the roofline errs low."""
    _, _, di, n, _ = _sizes(cfg)
    return 2 * 4 * _kinds(cfg)[0] * batch * di * n


def window_read_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The least `diff_attn_window_decode` must move in one decode step past
    the window: every window layer's ring of K and V read once."""
    return _kinds(cfg)[1] * batch * cfg["sliding_window"] \
        * kv_bytes_per_token(cfg)


def shared_read_bytes(cfg: Dict[str, Any], batch: int, context: float) -> float:
    """The least `diff_attn_shared_decode` must move in one decode step at
    `context` positions a sequence: the one slab's K and V read once by the
    layer that writes it and once by each cross layer."""
    _, _, f, _, c = _kinds(cfg)
    return (f + c) * batch * context * kv_bytes_per_token(cfg)
