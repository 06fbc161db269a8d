"""The Phi-4-mini-flash configuration and its cell, as new files only: the
file against its source, the family's counts against the issue's arithmetic
and the program's tree, the readers it brings on a run that has nothing for
them to read, and the builder's decode-logits tool at a toy size."""

import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops
from perfbench.manifest import Manifest, config_problems, problems

M = Manifest()
NAME = "phi4-mini-flash"
CELL = NAME + ".generate-reason-2k"
SIZES = M.config(NAME)
# the catalog's `config` for this model, key for key
PUBLISHED = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 10240, "layer_norm_eps": 1e-05,
             "max_position_embeddings": 262144, "mb_per_layer": 2,
             "model_type": "phi4flash", "num_attention_heads": 40,
             "num_hidden_layers": 32, "num_key_value_heads": 20,
             "resid_pdrop": 0, "sliding_window": 512,
             "tie_word_embeddings": True, "mlp_bias": False,
             "lm_head_bias": False, "vocab_size": 200064}


def test_the_manifest_may_be_sent_and_nothing_is_cut():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert SIZES["reduced"] == [] and SIZES["reduced_from"] == {}
    assert {k: SIZES[k] for k in PUBLISHED} == PUBLISHED
    # what the source does not give is stated, each with its reason
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank"):
        assert key in SIZES["assumed"] and key in SIZES
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and traffic["prompt"]["values"] == [2048]
    assert (traffic["batch"], traffic["new_tokens"]) == (64, 768)


def test_counts_are_the_issue_s_arithmetic():
    counts = flops.family_counts(SIZES, M)
    # 32 FFN x 78.6 M + 9 Mamba-1 x 41.2 M + 9 attention x 19.7 M + 7 memory
    # units x 26.2 M + 7 cross x 13.1 M + the 200,064-row head, once
    assert round(flops.matmul_params(SIZES, manifest=M) / 1e6, 1) == 3851.1
    assert round(flops.total_params(SIZES, manifest=M) / 1e9, 3) == 3.853
    # one full-length layer of 20 KV heads x 64, K and V, bf16
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 5120
    assert counts.ssm_update_bytes(SIZES, 64) == 9 * 2 * 4 * 64 * 5120 * 16
    assert counts.window_read_bytes(SIZES, 64) == 8 * 64 * 512 * 5120
    assert counts.shared_read_bytes(SIZES, 64, 2432) == 8 * 64 * 2432 * 5120
    assert flops.train_flops_per_token(SIZES, 2048, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    from deepspeed_tpu.models.phi4flash import Phi4FlashForCausalLM
    shapes = jax.eval_shape(Phi4FlashForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    # the cell's cache, by kind: 1.342 GB of rings, 0.923 GB shared (64 rows,
    # 2,816 positions), 0.206 GB of state; sixteen full caches would be 14.8
    kinds = cfg.kv_bytes_by_kind(64, 2816)
    assert kinds == {"window_kv_bytes": 8 * 64 * 512 * 5120,
                     "shared_kv_bytes": 64 * 2816 * 5120}
    assert cfg.recurrent_state_bytes(64) == 9 * 64 * (16 * 5120 * 4
                                                      + 3 * 5120 * 2)
    # the rehearsal has every kind of layer: two [Mamba, window] pairs, the
    # pair that publishes the memory and the shared K and V, one cross pair
    toy = adapter.model_config({**SIZES, **SIZES["rehearsal"]})
    assert (toy.front_pairs, toy.back_pairs, toy.sliding_window) == (2, 1, 8)


def test_a_seed_past_32_signed_bits_draws_weights():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.bfloat16)
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.bfloat16)
    _, other = adapter.materialize(cfg, 3, jnp.bfloat16)
    a, b = (t["decoder"]["mid"]["attn"]["Wqkv"]["kernel"] for t in (one, other))
    assert a.dtype == jnp.bfloat16 and bool(jnp.any(a != b))


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-reason-2k"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", [m["name"] for m in M.metrics_for(
    CELL, "per_layer") if m["workloads"] == [CELL]])
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric):
    """A parent commit has no such kernel, counter or gauge: the reader
    returns None and the line leaves the metric out; it never raises."""
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    set_hub(TelemetryHub(enabled=False))
    decl = M.metric(metric)
    assert M.reader(decl["reader"])(ctx_without_anything(),
                                    **decl.get("params", {})) is None


def test_the_shares_are_bytes_over_bandwidth_over_time():
    ops = [["diff_attn_shared_decode", 0.0, 3e6], ["fusion", 3e6, 5e6],
           ["diff_attn_window_decode", 8e6, 1e6],
           ["diff_attn_shared_decode.1", 9e6, 1e6]]   # ns: 4 ms and 1 ms
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 10e6), {"hbm_gbps": 819.0,
                                                "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "out_tok_s": 1800.0}
    shared = M.metric("shared_attn_roofline.gen")
    got = M.reader(shared["reader"])(ctx, **shared["params"])
    # the context is the traffic file's: 2048 + 768 / 2
    assert got == pytest.approx(
        100 * 2 * 8 * 64 * 2432 * 5120 / (819e9 * 4e-3))
    window = M.metric("window_attn_roofline.gen")
    assert M.reader(window["reader"])(ctx, **window["params"]) == \
        pytest.approx(100 * 2 * 8 * 64 * 512 * 5120 / (819e9 * 1e-3))
    ms = M.metric("shared_attn_ms.gen")
    assert M.reader(ms["reader"])(ctx, **ms["params"]) == pytest.approx(2.0)
    mfu = M.metric("mfu.gen")
    assert M.reader(mfu["reader"])(ctx, **mfu["params"]) == pytest.approx(
        100 * 1800 * 2 * flops.matmul_params(SIZES, manifest=M) / 197e12)


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/phi4flash_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32, where the served path IS the reference and a
    dropped lambda term is not."""
    import importlib.util
    import json
    import os
    from perfbench.manifest import CHECKOUT
    spec = importlib.util.spec_from_file_location(
        "decode_logits", os.path.join(CHECKOUT, "tools",
                                      "phi4flash_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5 < line["no_lambda"]["max"]
    # a ring's first slot is overwritten at position 16: judged either side
    assert {15, 16, 17} <= set(line["positions"])
