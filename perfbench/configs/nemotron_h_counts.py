"""Operations and bytes of a Nemotron-H configuration, from its file's sizes
(`perfbench/flops.py` asks here first). `n_routed_experts` is what the chip
HOLDS of the `router_experts` the router scores, so a token runs, on this
chip and on average, `num_experts_per_tok x held / scored` experts."""

from __future__ import annotations

from typing import Any, Dict


def _layers(cfg: Dict[str, Any]):
    pat = cfg["hybrid_override_pattern"]
    return pat.count("M"), pat.count("E"), pat.count("*")


def _mamba(cfg):
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    d_in = 2 * di + 2 * cfg["n_groups"] * cfg["ssm_state_size"] \
        + cfg["mamba_num_heads"]
    return cfg["hidden_size"] * d_in + di * cfg["hidden_size"]


def _attention(cfg):
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["hidden_size"] * (2 * q + 2 * kv)


def _expert(cfg):
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _outside_experts(cfg):
    """An expert layer's router and shared expert."""
    return cfg["hidden_size"] * cfg["router_experts"] \
        + 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """ACTIVE on this chip: weights that take part in a matmul for a token,
    the held experts counted at the share of a token's choices that falls on
    them in expectation, and the output head."""
    m, e, a = _layers(cfg)
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    return int(m * _mamba(cfg) + a * _attention(cfg)
               + e * (_outside_experts(cfg) + held * _expert(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter held: both projections of every held expert, the
    embedding, the norms and the Mamba layers' small vectors."""
    m, e, a = _layers(cfg)
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    small = m * ((cfg["conv_kernel"] + 1) * conv_dim
                 + 3 * cfg["mamba_num_heads"] + di) \
        + e * cfg["router_experts"] \
        + (m + e + a + 1) * cfg["hidden_size"]
    return int(m * _mamba(cfg) + a * _attention(cfg)
               + e * (_outside_experts(cfg)
                      + cfg["n_routed_experts"] * _expert(cfg))
               + 2 * cfg["hidden_size"] * cfg["vocab_size"] + small)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per active matmul weight, causal attention's
    scores and weighted sum, and the recurrence (a multiply-add to update and
    one to read each element of a head's state, forward)."""
    m, _, a = _layers(cfg)
    attn = 6.0 * a * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    state = 12.0 * m * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]
    return 6.0 * matmul_params(cfg) + attn + state


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of the ATTENTION layers only."""
    return 2 * _layers(cfg)[2] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * bytes_per


def ssm_update_bytes(cfg: Dict[str, Any], batch: int) -> int:
    """The least `ssm_state_update` must move in one decode step of `batch`
    sequences, all recurrent layers: each layer's float32 state read once
    and written once. Its other operands (x, dt, B, C: kilobytes a row) are
    left out, so the share of the roofline errs low."""
    return 2 * 4 * _layers(cfg)[0] * batch * cfg["mamba_num_heads"] \
        * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
