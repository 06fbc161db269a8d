"""The Qwen3-Next configuration and its cell, as new files only: the file
against its source (the catalog's config, key for key) and the issue's
arithmetic, the family's counts against a count by hand and the program's
tree at the published widths, the four new metrics over a recorded excerpt
(and on a run that has nothing for them to read), ONE traced rehearsal of
the cell, and the decode-logits tool at a toy size. No total of cells or
configurations is counted: a later one is no fault here."""

import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops
from perfbench.manifest import CHECKOUT, Manifest, config_problems, problems

M = Manifest()
NAME = "qwen3-next-80b-l12-ep8"
CELL = NAME + ".generate-longctx-linear"
SIZES = M.config(NAME)
# the issue's arithmetic at the published widths (a module's constants: the
# repo's linter reads a long-context run into a large literal in a test)
GDN_MIXER, FULL_MIXER, FULL_MATRICES = 33718464, 27263488, 27262976
EXPERT, ROUTER, EMBEDDING = 3145728, 1048576, 311164928
TOTAL_PARAMS, STATE_BYTES = 3473913024, 2097152     # a GDN layer's state a row
THETA, CONTEXT, VOCAB = 10000000, 262144, 151936
# every key of the catalog's `config`, as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": CONTEXT,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": THETA,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": VOCAB}
REDUCED = ["num_hidden_layers", "num_experts"]
NEW_METRICS = ["gdn_update_ms.gen", "gdn_update_roofline.gen",
               "full_prefill_mxu.gen", "full_kv_gb.gen"]
JOINED = ["decode_step_ms.gen", "decode_device_ms.gen", "prefill_share.gen",
          "idle_share.gen", "peak_hbm_gb.gen", "mfu.gen",
          "scope_unmatched_share.gen", "setup_compile_s.gen",
          "setup_compile_count.gen", "setup_trace_lower_s.gen",
          "setup_cache_miss_s.gen", "setup_engine_init_s.gen",
          "setup_unattributed_s.gen", "moe_gmm_ms.gen",
          "moe_gmm_decode_ms.gen", "moe_dispatch_ms.gen",
          "held_assign_share.gen", "experts_touched_share.gen",
          "recurrent_state_gb.gen", "dense_decode_attn_ms.gen",
          "full_decode_attn_roofline.gen"]


def test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert {k: SIZES[k] for k in PUBLISHED if k not in REDUCED} == \
        {k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert SIZES["reduced"] == REDUCED
    assert SIZES["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    # `reduced` names no width
    assert not any(word in key for key in REDUCED for word in (
        "size", "dim", "rank", "per_tok", "heads"))
    # published layers 0-11 as they are: three whole periods; an eighth of
    # the experts; the WHOLE vocabulary
    assert SIZES["published_layers"] == list(range(12))
    assert (SIZES["num_hidden_layers"], SIZES["num_experts"],
            SIZES["router_experts"], SIZES["expert_offset"],
            SIZES["gdn_layers"], SIZES["full_layers"]) == (12, 64, 512, 0, 9,
                                                           3)
    assert SIZES["num_experts"] * 8 == SIZES["router_experts"]
    assert "eight chips" in SIZES["deployment"] \
        and "0-11" in SIZES["deployment"] and "four pipeline stages" in \
        SIZES["deployment"]
    for point in ("source_of_form", "norms", "gdn_columns", "gdn_conv",
                  "gdn_gate", "gdn_recurrence", "full_attention", "experts",
                  "mtp", "precision", "weights", "router",
                  "routed_expert_damp"):
        assert point in SIZES["assumed"], point
    assert "published code wins" in SIZES["assumed"]["source_of_form"]
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "generate-longctx-linear"
    assert traffic["kind"] == "generate" and \
        traffic["prompt"]["values"] == [32768]
    assert (traffic["batch"], traffic["new_tokens"], traffic["check_rows"],
            traffic["trace_batches"]) == (8, 512, 2, 1)
    assert traffic["rehearsal"]["prompt"]["values"] == [40]
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", *JOINED, *NEW_METRICS} <= listed
    # it does NOT join what reads a ring, a latent row, a choice, a band or
    # a decay a channel
    assert not {"window_kv_gb.gen", "ring_decode_attn_ms.gen",
                "band_prefill_ms.gen", "latent_kv_gb.gen",
                "selected_share.gen", "kda_update_ms.gen",
                "kda_update_roofline.gen", "ssm_update_ms.gen"} & listed
    for name in NEW_METRICS:
        assert M.metric(name)["workloads"] == [CELL]
        assert next(m for m in M.doc["per_layer"]
                    if m["name"] == name)["workloads"] == [CELL]
        # no reader code is added, and none that reads scopes
        assert not M.metric(name)["reader"].startswith("scopes:")
    assert next(c for c in M.doc["configs"] if c["name"] == NAME)[
        "source"] == SIZES["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("tie_word_embeddings", True),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("use_sliding_window", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})


def test_counts_are_the_issue_s_arithmetic_and_a_count_by_hand():
    counts = flops.family_counts(SIZES, M)
    # a GDN mixer 33,718,464 with its small vectors, a full mixer 27,263,488
    # with its head norms, a router 1,048,576, the shared expert 3,145,728 +
    # 2,048, an expert 3,145,728, embedding and head 311,164,928 each
    assert counts._gdn(SIZES) + counts._gdn_small(SIZES) == GDN_MIXER
    assert counts._gdn(SIZES) == 2048 * (12288 + 64) + 4096 * 2048
    assert counts._gdn_small(SIZES) == 32768 + 64 + 128
    assert counts._full(SIZES) + 2 * 256 == FULL_MIXER
    assert counts._full(SIZES) == 2048 * 256 * (3 * 16 + 2 * 2) == FULL_MATRICES
    assert counts._expert(SIZES) == EXPERT
    assert counts._outside_experts(SIZES) == ROUTER + EXPERT + 2048
    assert 2048 * PUBLISHED["vocab_size"] == EMBEDDING
    # 3,473,913,024 held, 6.95 GB in bf16, 43% of the chip; 10 x 64 / 512 of
    # a token's experts fall here on average, beside the shared one
    assert flops.total_params(SIZES, manifest=M) == TOTAL_PARAMS
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 6.95
    assert 0.43 < 2 * flops.total_params(SIZES, manifest=M) / 16e9 < 0.44
    assert flops.matmul_params(SIZES, manifest=M) == \
        9 * counts._gdn(SIZES) + 3 * counts._full(SIZES) + 12 * (
            counts._outside_experts(SIZES) + 1.25 * counts._expert(SIZES)) \
        + 2048 * PUBLISHED["vocab_size"]
    assert round(flops.matmul_params(SIZES, manifest=M) / 1e6, 1) == 793.7
    # a token: 2,048 bytes a full layer, 6,144 over three; a sequence's
    # states 9 x 2.10 MB; a decode step rewrites all of them
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 6144
    assert counts.gdn_state_bytes(SIZES, 1) == 9 * 32 * 128 * 128 * 4
    assert counts.gdn_update_bytes(SIZES, 8) == 2 * 8 * 9 * STATE_BYTES
    assert round(counts.gdn_update_bytes(SIZES, 8) / 1e9, 2) == 0.30
    # the cell's cache, by kind: 1.64 GB of K and V, 0.151 GB of states,
    # 3.5 MB of convolution tails; every layer full would hold 6.54 GB
    kinds = counts.bytes_by_kind(SIZES, 8, 33280)
    assert kinds == {"full_kv_bytes": 8 * 33280 * 6144,
                     "state_bytes": 8 * 9 * STATE_BYTES,
                     "conv_bytes": 8 * 9 * 3 * 8192 * 2}
    assert (round(kinds["full_kv_bytes"] / 1e9, 2),
            round(kinds["state_bytes"] / 1e9, 3)) == (1.64, 0.151)
    assert round(12 * 8 * 33280 * 2048 / 1e9, 2) == 6.54
    # a decode step at the mean context 33,024: 1.62 GB of K and V
    assert counts.full_read_bytes(SIZES, 8, 33024) == 8 * 33024 * 6144
    assert round(counts.full_read_bytes(SIZES, 8, 33024) / 1e9, 2) == 1.62
    # a batch's prefill: 2.1e14 FLOP of full attention
    tri = lambda n: n * (n + 1) // 2  # noqa: E731
    assert counts.full_prefill_flops(SIZES, 8, 32768) == \
        8 * 3 * tri(32768) * 16 * 4 * 256
    assert round(counts.full_prefill_flops(SIZES, 8, 32768) / 1e14, 1) == 2.1
    assert counts.full_prefill_flops(SIZES, 1, 100) == 3 * tri(100) * 16384
    assert flops.train_flops_per_token(SIZES, 4096, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)
    # BY HAND at the toy size (hidden 64; GDN 2 key heads on 4 value heads of
    # 8; full 4 heads of 16 on 2; experts of 32: 4 held of 16, top 4; 4
    # layers, 1 full; vocabulary 256)
    toy = {**SIZES, **SIZES["rehearsal"]}
    gdn = 64 * (16 + 16 + 32 + 32 + 8) + 32 * 64
    full = 64 * 16 * (3 * 4 + 2 * 2)
    assert (counts._gdn(toy), counts._full(toy)) == (gdn, full)
    small = 3 * (4 * 64 + 8 + 8) + 2 * 16 + (2 * 4 + 1) * 64
    outside = 64 * 16 + 3 * 64 * 32 + 64
    assert counts.total_params(toy) == 3 * gdn + full + 4 * (
        outside + 4 * 3 * 64 * 32) + 2 * 64 * 256 + small
    assert counts.matmul_params(toy) == 3 * gdn + full + 4 * (
        outside + 4 * 4 / 16 * 3 * 64 * 32) + 64 * 256
    assert counts.kv_bytes_per_token(toy) == 2 * 1 * 2 * 16 * 2


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.rope_theta,
            cfg.rotary_dim, cfg.kinds) == (64, 512, THETA, 64,
                                           "GGGA" * 3)
    from deepspeed_tpu.models.qwen3_next import Qwen3NextForCausalLM
    model = Qwen3NextForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M) == TOTAL_PARAMS
    toy = {**SIZES, **SIZES["rehearsal"]}
    toy_shapes = jax.eval_shape(
        Qwen3NextForCausalLM(adapter.model_config(toy)).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        toy_shapes["params"])) == flops.total_params(toy, manifest=M)
    # the cell's cache: 32,768 + 512 = 33,280 slots a row
    from deepspeed_tpu.inference.capacity_scan import (kv_cache_bytes,
                                                       recurrent_state_bytes)
    counts = flops.family_counts(SIZES, M)
    kinds = counts.bytes_by_kind(SIZES, 8, 33280)
    assert cfg.kv_bytes_by_kind(8, 33280) == {
        "full_kv_bytes": kinds["full_kv_bytes"]}
    cache = jax.eval_shape(lambda: model.make_cache(8, 33280))
    assert cache.max_len == 33280
    assert cache.kv.k.stack.shape == (3, 8, 2, 33280, 256)
    assert cache.state.ssm.shape == (9, 8, 32, 128, 128)
    assert cache.state.conv.shape == (9, 8, 3, 8192)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(
        (cache.kv.k, cache.kv.v))) == kinds["full_kv_bytes"]
    assert kv_cache_bytes(cfg, 8, 33280, jnp.bfloat16) == \
        kinds["full_kv_bytes"]
    assert recurrent_state_bytes(cfg, 8, jnp.bfloat16) == \
        kinds["state_bytes"] + kinds["conv_bytes"]


def test_one_draw_of_the_weights_and_the_router_s_spread():
    """`--seed` draws the prompts, not the tree; and the adapter
    scales the routers' weights by `ROUTER_SPREAD`, the routed experts' up
    and down projections by `ROUTED_EXPERT_DAMP` (not their gate, not the
    shared expert nor its gate) and NOTHING else."""
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.qwen3_next import materialize_params
    _, raw = materialize_params(cfg, rng=jax.random.PRNGKey(
        adapter.WEIGHTS_SEED), param_dtype=jnp.float32)
    # whatever `--seed`: the tree drawn from `WEIGHTS_SEED` directly, scaled
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.float32)
    scaled = []
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(one),
                            jax.tree_util.tree_leaves(raw)):
        name = jax.tree_util.keystr(path)
        by = adapter.ROUTER_SPREAD if name.endswith("['gate']['wg']") else \
            adapter.ROUTED_EXPERT_DAMP if name.endswith(
                ("['experts']['up']", "['experts']['down']")) else 1.0
        assert bool(jnp.all(a == b * by)), name
        scaled.append(by)
    assert sorted(b for b in scaled if b != 1.0) == [0.5] * 8 + [8.0] * 4 \
        and adapter.WEIGHTS_SEED == 64
    assert "8.0 x" in SIZES["assumed"]["router"]
    assert SIZES["assumed"]["routed_expert_damp"].startswith(
        "0.5 (qwen3_next_adapter.ROUTED_EXPERT_DAMP)")


def test_the_margin_simulation_is_the_one_the_file_cites():
    """`generate-longctx-linear.margin_sim.py`: at the seeded spread a fifth
    of the rows are at a safe margin, at eight times it four fifths, and 2
    safe rows of 8 are found in all but one run in ten thousand."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "margin_sim", M.find("traffic",
                             "generate-longctx-linear.margin_sim.py"))
    sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sim)
    import numpy as np
    sim.ROWS = 500
    seeded = 0.02 * 2048 ** 0.5
    low = float((sim.margins(seeded, np.random.default_rng(64))
                 >= sim.SAFE).mean())
    high = float((sim.margins(8 * seeded, np.random.default_rng(64))
                  >= sim.SAFE).mean())
    assert 0.15 < low < 0.3 and 0.75 < high < 0.9
    assert sim.fewer_than(2, high, 8) < 1e-3
    assert (sim.E, sim.K, sim.HELD, sim.LAYERS) == (512, 10, 64, 12)


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-longctx-linear"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric):
    """A parent commit has no such kernel and no such counter: the reader
    returns None and the line leaves the metric out; it never raises."""
    decl = M.metric(metric)
    read = M.reader(decl["reader"])
    if decl["reader"].startswith("serving:"):
        from deepspeed_tpu.telemetry import get_hub
        hub = get_hub()
        kept = {k: hub.gauges.pop(k) for k in list(hub.gauges)
                if k in decl["params"].values()}
        try:
            assert read(ctx_without_anything(), **decl["params"]) is None
        finally:
            hub.gauges.update(kept)
        return
    assert read(ctx_without_anything(), **decl.get("params", {})) is None
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": [["fusion", 0.0, 4e6]],
                                   "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 4e6), {"hbm_gbps": 819.0,
                                               "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    assert not read(ctx, **decl.get("params", {}))


def test_the_new_metrics_read_a_recorded_excerpt():
    """Kernel times by name, the bounds from the family's counts: the state
    update's share of the bytes a step must move, the flash forward's share
    of the operations a batch's full-layer prefill must compute."""
    step = [[f"gdn_state_update.{i}", i * 1e6, 0.05e6] for i in range(9)] + [
        [f"self_attn_dense_decode.{i}", 9e6 + i * 1e6, 0.8e6]
        for i in range(3)]
    ops = [["while.2", 0.0, 40e6], *step,
           *[[n, 20e6 + t, d] for n, t, d in step],
           ["kda_state_update.1", 39e6, 1e6],           # another family's
           ["self_attn_flash_fwd.7", 40e6, 1500e6],
           ["self_attn_flash_fwd", 1540e6, 1500e6]]     # 3,000 ms a batch
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 3040e6), {"hbm_gbps": 819.0,
                                                  "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    read = lambda name: M.reader(M.metric(name)["reader"])(  # noqa: E731
        ctx, **M.metric(name)["params"])
    assert read("gdn_update_ms.gen") == pytest.approx(0.45)
    assert read("dense_decode_attn_ms.gen") == pytest.approx(2.4)
    counts = flops.family_counts(SIZES, M)
    assert read("gdn_update_roofline.gen") == pytest.approx(
        100 * counts.gdn_update_bytes(SIZES, 8) / 819e9 / 0.45e-3)
    assert 0 < read("gdn_update_roofline.gen") < 100
    full_s = counts.full_read_bytes(SIZES, 8, 33024) / 819e9
    assert read("full_decode_attn_roofline.gen") == pytest.approx(
        100 * full_s / 3 / 0.8e-3)
    assert read("full_prefill_mxu.gen") == pytest.approx(
        100 * counts.full_prefill_flops(SIZES, 8, 32768) / 197e12 / 3.0)
    assert 0 < read("full_prefill_mxu.gen") < 100
    from deepspeed_tpu.telemetry import get_hub
    hub = get_hub()
    kept = dict(hub.gauges)
    try:
        hub.gauge("serving_v1/full_kv_bytes", 8 * 33280 * 6144)
        assert read("full_kv_gb.gen") == pytest.approx(1.6358, 1e-4)
    finally:
        hub.gauges.clear()
        hub.gauges.update(kept)


def test_trinity_s_manifest_test_holds_but_for_the_list_this_cell_joined(
        monkeypatch):
    """`test_afmoe_cell.py` (the benchmark's, not this PR's to edit) ends on
    `full_decode_attn_roofline.gen` listing Trinity's cell ALONE, true until
    this cell joined it; `tests/conftest.py` holds that test as a strict
    expected failure until a `benchmark` PR words it, and every other
    assertion of it is made here meanwhile, by the test's own lines, on the
    manifest less this cell's entry in that one list."""
    import copy
    from tests.perfbench import test_afmoe_cell as theirs
    doc = copy.deepcopy(theirs.M.doc)
    joined = next(m for m in doc["per_layer"]
                  if m["name"] == "full_decode_attn_roofline.gen")
    assert joined["workloads"] == [theirs.CELL, CELL]
    joined["workloads"].remove(CELL)
    monkeypatch.setattr(theirs.M, "doc", doc)
    theirs.test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s()


def test_the_kernels_are_named_as_the_metrics_search_for_them():
    from deepspeed_tpu.ops.pallas import decode_attention, flash_attention, kda
    names = {"gdn_update_ms.gen": kda.HEAD_DECAY_NAME,
             "gdn_update_roofline.gen": kda.HEAD_DECAY_NAME,
             "kda_update_ms.gen": kda.KERNEL_NAME,
             "full_decode_attn_roofline.gen": decode_attention.DENSE_NAME,
             "full_prefill_mxu.gen": flash_attention.FWD_NAME}
    every = {kda.HEAD_DECAY_NAME, kda.KERNEL_NAME,
             decode_attention.RING_NAME, decode_attention.DENSE_NAME,
             flash_attention.FWD_NAME, "self_attn_flash_bwd",
             "ssm_state_update"}
    for metric, kernel in names.items():
        pattern = M.metric(metric)["params"]["pattern"]
        assert re.search(pattern, kernel + ".3"), (metric, kernel)
        # each reads its own kernel and no other
        assert not any(re.search(pattern, n) for n in every - {kernel}), metric
    # the accepted names did not move
    assert (kda.KERNEL_NAME, decode_attention.DENSE_NAME,
            flash_attention.FWD_NAME) == (
        "kda_state_update", "self_attn_dense_decode", "self_attn_flash_fwd")


def test_the_traced_rehearsal_of_the_qwen3_next_cell_runs_on_the_cpu():
    """A process of its own (the harness holds one trace directory a
    checkout) that compiles the reference, a prefill and a decode program:
    the counters, the gauges of the rows and the states, no device metric."""
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 64),
           "--seconds", "1", "--trace", "1"]
    done = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True,
                          timeout=600, env={**os.environ,
                                            "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    assert {"full_kv_gb.gen", "recurrent_state_gb.gen",
            "held_assign_share.gen", "experts_touched_share.gen"} <= set(got)
    assert not (set(NEW_METRICS) - {"full_kv_gb.gen"}) & set(got)
    # 8 rows of 128 slots (40 + 6, rounded), 1 full layer, 2 KV heads of 16,
    # K and V, bf16; 3 GDN layers of 4 x 8 x 8 float32 + 3 x 64 bf16 a row
    assert got["full_kv_gb.gen"]["value"] == pytest.approx(
        8 * 128 * 2 * 2 * 16 * 2 / 1e9)
    assert got["recurrent_state_gb.gen"]["value"] == pytest.approx(
        8 * 3 * (4 * 8 * 8 * 4 + 3 * 64 * 2) / 1e9)
    # experts 0-3 of 16 held: about a quarter of the assignments
    assert 10 < got["held_assign_share.gen"]["value"] < 45
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/qwen3_next_decode_logits.py --rehearsal`: the chip
    comparison's control flow, in float32 at toy widths, where the served
    path IS the reference, a dropped shared gate is not, and a bf16 state
    leaves the prefill's logits as they are and moves every decode step's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_decode_logits", os.path.join(
            CHECKOUT, "tools", "qwen3_next_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # at toy widths a dropped gate reads 0.06-0.10 (0.9 at the published
    # ones): the chip's limit of 0.15 is the published widths'
    assert tool.LIMIT == 0.15
    assert tool.main(["--rehearsal", "--limit", "0.03", "--passes",
                      "served,no_shared_gate,bf16_state"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5 and line["state_dtype"] == "float32"
    assert {"no_shared_gate", "bf16_state"} <= set(line["told_apart"])
    apart = line["bf16_state"]["against_served"]
    assert apart[0] == 0.0 and min(apart[1:]) > 1e-4
    assert set(line["step_ms"]) == {"served", "no_shared_gate", "bf16_state"}
    assert tool.SHOWS["rotary_whole_head"] == "reported"
    assert {39, 40, 41, 47} <= set(line["positions"])
    # the functions it replaced are the program's again
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
    from deepspeed_tpu.moe import layer
    from deepspeed_tpu.ops import attention
    assert Qwen3NextConfig().rotary_dim == 64
    assert layer.shared_expert_gate.__module__ == "deepspeed_tpu.moe.layer"
    assert attention.kda_update.__module__ == "deepspeed_tpu.ops.attention"
