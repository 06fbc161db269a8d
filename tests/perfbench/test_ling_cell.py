"""The Ling-linear configuration and its cell, as new files only: the file
against its source and the issue's arithmetic, the family's counts against
the program's tree at the published widths, the readers it brings on a run
that has nothing for them to read, the rehearsal of the cell and the
builder's decode-logits tool at a toy size."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops
from perfbench.manifest import CHECKOUT, Manifest, config_problems, problems

M = Manifest()
NAME = "ling3-flash-l6-ep4"
CELL = NAME + ".generate-reason-1k"
SIZES = M.config(NAME)
# the widths the issue names, as published
PUBLISHED = {"hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
             "intermediate_size": 6144, "kv_lora_rank": 512,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "moe_intermediate_size": 768,
             "moe_shared_expert_intermediate_size": 768,
             "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
             "routed_scaling_factor": 2.5, "short_conv_kernel_size": 4,
             "kda_lower_bound": -5, "layer_group_size": 6,
             "rope_theta": 6000000, "rms_norm_eps": 1e-06,
             "q_lora_rank": None, "score_function": "sigmoid"}


def test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert {k: SIZES[k] for k in PUBLISHED} == PUBLISHED
    assert SIZES["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "first_k_dense_replace"]
    assert SIZES["reduced_from"] == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 4 * 39296,
        "first_k_dense_replace": 2}
    assert (SIZES["num_hidden_layers"], SIZES["num_experts"],
            SIZES["router_experts"], SIZES["vocab_size"],
            SIZES["first_k_dense_replace"]) == (6, 128, 512, 39296, 1)
    # a quarter of the experts (whole groups: 2 of 8) and of the vocabulary
    assert SIZES["num_experts"] * 4 == SIZES["router_experts"]
    assert SIZES["vocab_size"] * 4 == SIZES["reduced_from"]["vocab_size"]
    assert SIZES["published_layers"] == [0, 2, 3, 4, 5, 6]
    assert "EP4" in SIZES["deployment"] and "0-127" in SIZES["deployment"]
    # every kept layer's swiglu limit is 0: no clamp is in the cut
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(SIZES[key]) == 42
        assert [SIZES[key][p] for p in SIZES["published_layers"]] == [0] * 6
    for point in ("kda_gate", "qk_norm", "vision_and_mtp", "swiglu_limits",
                  "published_layers", "kda_state_dtype", "weights_seed",
                  "routed_expert_damp"):
        assert point in SIZES["assumed"], point
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and traffic["prompt"]["values"] == [1024]
    assert (traffic["batch"], traffic["new_tokens"],
            traffic["check_rows"]) == (128, 1024, 4)
    # the cell is on out_tok_s and on every .gen metric the issue lists
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", "decode_step_ms.gen", "idle_share.gen",
            "peak_hbm_gb.gen", "mfu.gen", "moe_gmm_ms.gen",
            "held_assign_share.gen", "recurrent_state_gb.gen",
            "kda_update_ms.gen", "kda_update_roofline.gen",
            "latent_attn_ms.gen", "latent_attn_roofline.gen",
            "latent_kv_gb.gen", "experts_touched_share.gen"} <= listed
    assert not {"ssm_update_ms.gen", "ssm_update_roofline.gen"} & listed


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("use_mla_nope", True), ("q_lora_rank", 1536),
                       ("kda_safe_gate", False), ("score_function", "softmax")):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})
    clamped = {**SIZES, "expert_swiglu_limit_list": [4] * 42}
    with pytest.raises(ValueError, match="swiglu"):
        adapter.model_config(clamped)


def test_counts_are_the_issue_s_arithmetic():
    counts = flops.family_counts(SIZES, M)
    # a KDA mixer 63.0 M, an MLA mixer 32.0 M, an expert 5.898 M
    assert round(counts._kda(SIZES) / 1e6, 1) == 63.0
    assert round(counts._mla(SIZES) / 1e6, 1) == 32.0
    assert counts._expert(SIZES) == 3 * 2560 * 768
    # 4.41 B held, 8.81 GB in bf16; about 0.6 B active a token on this chip
    # (2 of a token's 8 experts fall here on average, and the shared one)
    assert round(flops.total_params(SIZES, manifest=M) / 1e9, 2) == 4.41
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 8.81
    assert round(flops.matmul_params(SIZES, manifest=M) / 1e9, 2) == 0.59
    # one MLA layer's latent row: 576 values, 1,152 bytes in bf16
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 1152
    assert counts.kda_update_bytes(SIZES, 128) == \
        2 * 5 * 128 * 32 * 128 * 128 * 4                      # 2.68 GB
    assert counts.latent_read_bytes(SIZES, 128, 1536) == 128 * 1536 * 1152
    assert flops.train_flops_per_token(SIZES, 1024, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert cfg.kinds == "KKKKAK"
    from deepspeed_tpu.models.ling_linear import LingLinearForCausalLM
    shapes = jax.eval_shape(LingLinearForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    # the cell's cache, by kind: 0.30 GB of latent rows (128 sequences, 2,048
    # positions, 1,152 bytes), 1.34 GB of float32 matrix states and 0.05 GB
    # of convolution tails; K and V a head would be 4.3 GB for the one layer
    assert cfg.kv_bytes_by_kind(128, 2048) == {
        "latent_kv_bytes": 128 * 2048 * 1152}
    assert cfg.recurrent_state_bytes(128) == 5 * 128 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    toy = adapter.model_config({**SIZES, **SIZES["rehearsal"]})
    assert toy.kinds == "KKKKAK" and toy.latent_width == 40


def test_one_draw_of_the_weights_and_only_the_routed_up_and_down_are_damped():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.ling_linear import materialize_params
    _, raw = materialize_params(cfg, rng=jax.random.PRNGKey(
        adapter.WEIGHTS_SEED), param_dtype=jnp.float32)
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.float32)
    _, other = adapter.materialize(cfg, 3, jnp.float32)
    same = jax.tree_util.tree_map(lambda a, b: bool(jnp.all(a == b)), one,
                                  other)
    assert all(jax.tree_util.tree_leaves(same))      # --seed draws prompts
    scaled = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(jnp.max(
            jnp.abs(a - b)))), one, raw)
    moved = {name for name, gap in jax.tree_util.tree_leaves(
        scaled, is_leaf=lambda x: isinstance(x, tuple)) if gap > 0}
    assert moved == {f"['layers']['layer_{i}_mlp']['experts']['{side}']"
                     for i in range(1, 6) for side in ("up", "down")}
    mlp = "layer_3_mlp"
    assert bool(jnp.all(one["layers"][mlp]["experts"]["down"] ==
                        adapter.ROUTED_EXPERT_DAMP
                        * raw["layers"][mlp]["experts"]["down"]))


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-reason-1k"), manifest=M, chips=1)


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
    ops = [["kda_state_update", 0.0, 3e6], ["fusion", 3e6, 4e6],
           ["kda_state_update.1", 7e6, 5e6],
           ["mla_latent_decode", 12e6, 1e6]]          # ns: 8 ms and 1 ms
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 13e6), {"hbm_gbps": 819.0,
                                                "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "out_tok_s": 6000.0}
    kda = M.metric("kda_update_roofline.gen")
    assert M.reader(kda["reader"])(ctx, **kda["params"]) == pytest.approx(
        100 * 2 * 2 * 5 * 128 * 32 * 128 * 128 * 4 / (819e9 * 8e-3))
    latent = M.metric("latent_attn_roofline.gen")
    # the context is the traffic file's: 1024 + 1024 / 2
    assert M.reader(latent["reader"])(ctx, **latent["params"]) == \
        pytest.approx(100 * 2 * 128 * 1536 * 1152 / (819e9 * 1e-3))
    ms = M.metric("kda_update_ms.gen")
    assert M.reader(ms["reader"])(ctx, **ms["params"]) == pytest.approx(4.0)
    ms = M.metric("latent_attn_ms.gen")
    assert M.reader(ms["reader"])(ctx, **ms["params"]) == pytest.approx(0.5)
    mfu = M.metric("mfu.gen")
    assert M.reader(mfu["reader"])(ctx, **mfu["params"]) == pytest.approx(
        100 * 6000 * 2 * flops.matmul_params(SIZES, manifest=M) / 197e12)


def test_the_counters_and_the_gauge_are_read_from_the_hub():
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    hub = TelemetryHub(enabled=False)
    set_hub(hub)
    hub.gauge("serving_v1/latent_kv_bytes", 128 * 2048 * 1152)
    hub.counter("serving_v1/experts_touched", 77)
    hub.counter("serving_v1/experts_held", 100)
    ctx = ctx_without_anything()
    gb = M.metric("latent_kv_gb.gen")
    assert M.reader(gb["reader"])(ctx, **gb["params"]) == pytest.approx(0.302,
                                                                        abs=1e-3)
    share = M.metric("experts_touched_share.gen")
    assert M.reader(share["reader"])(ctx, **share["params"]) == \
        pytest.approx(77.0)
    set_hub(TelemetryHub(enabled=False))


def test_the_traced_rehearsal_of_the_cell_runs_on_the_cpu():
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 47),
           "--seconds", "1", "--trace", "1"]
    done = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True,
                          timeout=600, env={**os.environ,
                                            "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    # what the program counts is there off the chip too; device times are not
    assert {"latent_kv_gb.gen", "experts_touched_share.gen",
            "held_assign_share.gen", "recurrent_state_gb.gen"} <= set(got)
    assert not {"kda_update_ms.gen", "latent_attn_roofline.gen"} & set(got)
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/ling_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32, where the served path IS the reference, a
    bfloat16 state and a dropped term are not."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ling_decode_logits", os.path.join(CHECKOUT, "tools",
                                           "ling_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5 < line["no_dt_bias"]["min"]
    assert line["no_bias"]["min"] > 1e-3 < line["bf16_state"]["max"]
    assert line["no_head_gate"]["min"] > 1e-4
    assert line["served_safe"]["of"] == 4 * 6
    assert {39, 40, 41, 53} <= set(line["positions"])
