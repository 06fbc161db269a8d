"""Operations a model needs per token, from its configuration's sizes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Matrix multiplications only (2 per multiply-add); recomputed
operations are not counted; the embedding lookup is a gather and costs none,
the output projection is a matmul and is counted once (tied or not).

The four counts (each takes `manifest=`, where the files are) ask the
configuration first. A file that names `counts`
(`"counts": "<module>"`, found beside its adapter under `configs/`) brings
its family's own `matmul_params(cfg)`, `total_params(cfg)`,
`train_flops_per_token(cfg, seq)` and `kv_bytes_per_token(cfg, bytes_per)`:
for sparse experts `matmul_params` is the ACTIVE parameters, those that take
part in a matmul for every token. A file without `counts` is one dense FFN a
layer; one that states a number of experts and no `counts` is an error,
never a dense guess.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
EXPERT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts")
COUNTS = ("matmul_params", "total_params", "train_flops_per_token",
          "kv_bytes_per_token")     # what a `counts` module provides


def family_counts(cfg: Dict[str, Any], manifest=None) -> Optional[Any]:
    """The module the file names under `counts`, or None for the dense
    arithmetic here. `manifest` says where a later PR's (or a test's) files
    are; without one the checkout's own `BENCHMARK.json` does."""
    if "counts" not in cfg:
        stated = [k for k in EXPERT_KEYS if cfg.get(k)]
        if stated:
            raise ValueError(
                f"configuration {cfg.get('name')!r} states {stated[0]} and "
                "names no `counts` module: one dense FFN a layer is not its "
                "arithmetic")
        return None
    if manifest is None:
        from perfbench.manifest import Manifest
        manifest = Manifest()
    return manifest.module("configs", cfg["counts"])


def _asks_the_family(dense):
    """`count(cfg, ..., manifest=None)`: the function of this name in the
    file's `counts` module where it names one, else the dense one below."""
    @functools.wraps(dense)
    def count(cfg: Dict[str, Any], *args, manifest=None, **kw):
        own = family_counts(cfg, manifest)
        if own is not None:
            return getattr(own, dense.__name__)(cfg, *args, **kw)
        return dense(cfg, *args, **kw)
    return count


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


@_asks_the_family
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


@_asks_the_family
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


@_asks_the_family
def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention's
    scores and weighted sum, 12 * layers * heads * head_dim * seq / 2."""
    attn = 6.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * head_dim(cfg) * seq
    return 6.0 * matmul_params(cfg) + attn


@_asks_the_family
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
