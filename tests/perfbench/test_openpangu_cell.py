"""The openPangu configuration and its cell, as new files only: the file
against its source (the catalog's config, key for key) and the issue's
arithmetic, the family's counts against a count by hand and the program's
tree at the published widths, the three new metrics over a recorded excerpt
(and on a run that has nothing for them to read), ONE traced rehearsal of
the cell, and what `test_deepseek_cell.py` held of the manifest besides the
count of cells it was written at (`tests/conftest.py` says why that test is
expected to fail since this cell)."""

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
NAME = "openpangu-ultra-l5-ep16"
CELL = NAME + ".generate-longctx-dense"
DEEPSEEK_CELL = "deepseek-v3.2-l5-ep16.generate-longctx-latent"
SIZES = M.config(NAME)
# every key of the catalog's `config`, as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
NEW_METRICS = ["latent_dense_attn_roofline.gen", "latent_prefill_ms.gen",
               "latent_prefill_mxu.gen"]
JOINED = ["decode_step_ms.gen", "decode_device_ms.gen", "prefill_share.gen",
          "idle_share.gen", "peak_hbm_gb.gen", "mfu.gen",
          "scope_unmatched_share.gen", "setup_compile_s.gen",
          "setup_compile_count.gen", "setup_trace_lower_s.gen",
          "setup_cache_miss_s.gen", "setup_engine_init_s.gen",
          "setup_unattributed_s.gen", "latent_attn_ms.gen",
          "latent_kv_gb.gen", "moe_gmm_ms.gen", "moe_gmm_decode_ms.gen",
          "moe_dispatch_ms.gen", "held_assign_share.gen",
          "experts_touched_share.gen", "kv_expand_ms.gen"]


def test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert {k: SIZES[k] for k in PUBLISHED if k not in REDUCED} == \
        {k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert SIZES["reduced"] == REDUCED
    assert SIZES["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (SIZES["num_hidden_layers"], SIZES["first_k_dense_replace"],
            SIZES["n_routed_experts"], SIZES["router_experts"],
            SIZES["expert_offset"], SIZES["vocab_size"],
            SIZES["num_nextn_predict_layers"], SIZES["published_layers"]) == (
                5, 1, 16, 256, 0, 19200, 0, [0, 3, 4, 5, 6])
    assert SIZES["n_routed_experts"] * 16 == SIZES["router_experts"]
    assert SIZES["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "sixteen chips" in SIZES["deployment"] \
        and "0-15" in SIZES["deployment"]
    for point in ("router_score", "sandwich_norm", "rotary", "mtp",
                  "published_layers", "precision", "weights", "router"):
        assert point in SIZES["assumed"], point
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    # the lengths of `generate-longctx-latent`: the dense and the chosen form
    # of latent attention on the same shapes
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert traffic["prompt"]["values"] == M.traffic(
        "generate-longctx-latent")["prompt"]["values"] == [24576]
    assert (traffic["batch"], traffic["new_tokens"], traffic["check_rows"],
            traffic["trace_batches"]) == (8, 256, 2, 1)
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", *JOINED, *NEW_METRICS} <= listed
    # it does NOT join the roofline that counts bytes alone, nor what reads
    # a choice, index keys or a recurrent state
    assert not {"latent_attn_roofline.gen", "selected_share.gen",
                "index_kv_gb.gen", "recurrent_state_gb.gen",
                "latent_sparse_attn_ms.gen"} & listed
    for name in NEW_METRICS:
        assert M.metric(name)["workloads"] == [CELL]
        assert next(m for m in M.doc["per_layer"]
                    if m["name"] == name)["workloads"] == [CELL]
    # no total is counted: a later cell or configuration is no fault here
    assert CELL in {w["name"] for w in M.doc["workloads"]}
    assert NAME in {c["name"] for c in M.doc["configs"]}
    assert sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1


def test_what_the_deepseek_cell_s_manifest_test_held_besides_the_count():
    """`test_deepseek_cell.py::test_the_manifest_may_be_sent_...` ends on
    `len(workloads) == 9`, true until this cell; the benchmark's files are
    not a `model_config` PR's to edit, so it is held in `tests/conftest.py`
    and EVERY other assertion it makes is made here, line for line, from
    that file's own tables: nothing it checked goes unchecked."""
    from tests.perfbench import test_deepseek_cell as held
    published, reduced = held.PUBLISHED, held.REDUCED
    name, cell_name, sizes = held.NAME, held.CELL, held.SIZES
    assert cell_name == DEEPSEEK_CELL
    assert problems(M) == [] and config_problems(M, name) == []
    assert {k: sizes[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert sizes["reduced"] == reduced
    assert sizes["reduced_from"] == {k: published[k] for k in reduced}
    assert (sizes["num_hidden_layers"], sizes["first_k_dense_replace"],
            sizes["n_routed_experts"], sizes["router_experts"],
            sizes["expert_offset"], sizes["vocab_size"],
            sizes["num_nextn_predict_layers"], sizes["published_layers"]) == (
                5, 1, 16, 256, 0, 16160, 0, [0, 3, 4, 5, 6])
    # a sixteenth of the experts: HALF a group of 32; an eighth of the rows
    assert sizes["n_routed_experts"] * 16 == sizes["router_experts"]
    assert sizes["router_experts"] // sizes["n_group"] == \
        2 * sizes["n_routed_experts"]
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    assert "sixteen chips" in sizes["deployment"] \
        and "0-15" in sizes["deployment"]
    for point in ("index_key_norm", "index_rotary", "index_precision",
                  "selection", "yarn", "mtp", "published_layers", "weights",
                  "router"):
        assert point in sizes["assumed"], point
    cell = M.workload(cell_name)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and traffic["prompt"]["values"] == [24576]
    assert (traffic["batch"], traffic["new_tokens"], traffic["check_rows"],
            traffic["trace_batches"]) == (8, 256, 2, 1)
    # the rehearsal's choice really chooses: 8 of a 40-token prompt
    assert sizes["rehearsal"]["index_topk"] == 8
    assert traffic["rehearsal"]["prompt"]["values"] == [40]
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(cell_name, group)}
    assert {"out_tok_s", "setup_s", "decode_step_ms.gen", "idle_share.gen",
            "peak_hbm_gb.gen", "mfu.gen", "moe_gmm_ms.gen",
            "held_assign_share.gen", "experts_touched_share.gen",
            "setup_compile_s.gen", "setup_compile_count.gen",
            "setup_trace_lower_s.gen", "setup_cache_miss_s.gen",
            "setup_engine_init_s.gen", "setup_unattributed_s.gen",
            "latent_kv_gb.gen", "index_kv_gb.gen", "selected_share.gen",
            "sparse_select_ms.gen", "sparse_select_roofline.gen",
            *held.NEW_METRICS} <= listed
    # Ling's decode kernel and Keye's attention are not this cell's, nor
    # what this PR brings
    assert not {"latent_attn_ms.gen", "sparse_attn_ms.gen",
                "sparse_prefill_ms.gen", "recurrent_state_gb.gen",
                *NEW_METRICS} & listed
    for metric in held.NEW_METRICS:
        assert M.metric(metric)["workloads"] == [cell_name]
    assert sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("attention_bias", True), ("sandwich_norm", False),
                       ("num_nextn_predict_layers", 1),
                       ("num_key_value_heads", 8), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn", "factor": 4})):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})


def test_counts_are_the_issue_s_arithmetic_and_a_count_by_hand():
    counts = flops.family_counts(SIZES, M)
    # attention 196.58 M, the dense FFN 424.67 M, an expert 47.19 M, the
    # router 1.97 M
    assert counts._attention(SIZES) == 7680 * 1536 + 1536 * 24576 \
        + 7680 * 576 + 512 * 32768 + 16384 * 7680
    assert round(counts._attention(SIZES) / 1e6, 2) == 196.58
    assert round(counts._dense_ffn(SIZES) / 1e6, 2) == 424.67
    assert round(counts._expert(SIZES) / 1e6, 2) == 47.19
    assert round(counts._router(SIZES) / 1e6, 2) == 1.97
    # 4,919.1 M held, 9.84 GB in bf16, 61% of the chip; half of one of a
    # token's 8 experts falls here on average (8 x 16 / 256), beside the
    # shared one
    assert round(flops.total_params(SIZES, manifest=M) / 1e6, 1) == 4919.1
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 9.84
    assert 0.61 < 2 * flops.total_params(SIZES, manifest=M) / 16e9 < 0.62
    assert flops.matmul_params(SIZES, manifest=M) == \
        5 * counts._attention(SIZES) + counts._dense_ffn(SIZES) + 4 * (
            counts._router(SIZES) + counts._expert(SIZES) * 3 // 2) \
        + 7680 * 19200
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 5760
    # a decode step at the mean context: 1,152 bytes and 128 x 1,088 x 2 =
    # 278.5 kFLOP a cached token a layer, 242 FLOP a byte: the chip's ridge
    assert counts.latent_read_bytes(SIZES, 1, 24704) == 5 * 24704 * 1152
    assert counts.latent_attn_flops(SIZES, 1, 24704) == \
        5 * 24704 * 128 * 1088 * 2
    assert counts.latent_attn_flops(SIZES, 8, 100) \
        / counts.latent_read_bytes(SIZES, 8, 100) == pytest.approx(241.8, 1e-3)
    assert 197e12 / 819e9 == pytest.approx(240.5, 1e-3)
    assert round(counts.latent_read_bytes(SIZES, 8, 24704) / 1e9, 2) == 1.14
    # a batch's prefill: every query against the positions up to its own
    pairs = 24576 * 24577 // 2
    assert counts.causal_prefill_flops(SIZES, 8, 24576) == \
        8 * 5 * pairs * 128 * 640
    assert round(counts.causal_prefill_flops(SIZES, 8, 24576) / 1e14, 1) == 9.9
    assert flops.train_flops_per_token(SIZES, 4096, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)
    # BY HAND at the toy size (hidden 64, 4 heads of 16 + 8 / 16, q_lora 48,
    # kv_lora 32, FFN 96, experts of 32: 4 held of 16, top 4; 2 layers, 1
    # dense; vocabulary 256)
    toy = {**SIZES, **SIZES["rehearsal"]}
    attention = 64 * 48 + 48 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    assert counts._attention(toy) == attention == 18432
    norms = 2 * (4 * 64 + 48 + 32) + 64
    assert counts.total_params(toy) == 2 * attention + 3 * 64 * 96 + (
        64 * 16 + (4 + 1) * 3 * 64 * 32) + 2 * 64 * 256 + norms
    assert counts.matmul_params(toy) == 2 * attention + 3 * 64 * 96 + (
        64 * 16 + (4 * 4 / 16 + 1) * 3 * 64 * 32) + 64 * 256
    assert counts.kv_bytes_per_token(toy) == 2 * 2 * 40


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.n_group,
            cfg.router_bias_scale, cfg.rope_theta) == (
                16, 256, 1, None, PUBLISHED["rope_theta"])
    from deepspeed_tpu.models.openpangu import OpenPanguForCausalLM
    model = OpenPanguForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    toy = {**SIZES, **SIZES["rehearsal"]}
    toy_shapes = jax.eval_shape(
        OpenPanguForCausalLM(adapter.model_config(toy)).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        toy_shapes["params"])) == flops.total_params(toy, manifest=M)
    # the cell's cache: 24,576 + 256 = 24,832 positions are GIVEN ten blocks
    # of 2,560 slots: 8 rows hold 1.18 GB of latent rows and nothing else
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    assert cfg.kv_bytes_by_kind(8, 24832) == {
        "latent_kv_bytes": 5 * 8 * 25600 * 1152}
    cache = jax.eval_shape(lambda: model.make_cache(8, 24832))
    assert cache.max_len == 25600 and cache.index_keys is None
    assert kv_cache_bytes(cfg, 8, 24832, jnp.bfloat16) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(
            cache.latent.c)) == 8 * 25600 * flops.kv_bytes_per_token(
                SIZES, manifest=M)
    assert round(kv_cache_bytes(cfg, 8, 24832, jnp.bfloat16) / 1e9, 2) == 1.18


def test_one_draw_of_the_weights_served_as_seeded():
    """`--seed` draws the prompts: two seeds, one tree; and the adapter
    scales NOTHING (the margin simulation's verdict, `assumed.router`)."""
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.openpangu import materialize_params
    _, raw = materialize_params(cfg, rng=jax.random.PRNGKey(
        adapter.WEIGHTS_SEED), param_dtype=jnp.float32)
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.float32)
    _, other = adapter.materialize(cfg, 3, jnp.float32)
    for a, b in ((one, other), (one, raw)):
        assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda x, y: bool(jnp.all(x == y)), a, b)))
    assert adapter.ROUTER_SPREAD == 1.0 and "AS SEEDED" in \
        SIZES["assumed"]["router"]


def test_the_margin_simulation_is_the_one_the_file_cites():
    """`generate-longctx-dense.margin_sim.py` at the seeded spread: nine
    rows in ten at a safe margin, so 2 of 8 are found in all but one run in
    millions and the router is served as seeded."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "margin_sim", M.find("traffic", "generate-longctx-dense.margin_sim.py"))
    sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sim)
    import numpy as np
    sim.ROWS = 1000
    p = float((sim.margins(0.02 * 7680 ** 0.5, np.random.default_rng(58))
               >= sim.SAFE).mean())
    assert 0.88 < p < 0.94 and sim.fewer_than_two(p, 8) < 1e-5
    assert (sim.E, sim.K, sim.HELD, sim.LAYERS) == (256, 8, 16, 4)


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-longctx-dense"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric):
    """A parent commit has no such kernel: the reader returns None and the
    line leaves the metric out; it never raises."""
    decl = M.metric(metric)
    read = M.reader(decl["reader"])
    assert read(ctx_without_anything(), **decl.get("params", {})) is None
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": [["fusion", 0.0, 4e6]],
                                   "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 4e6), {"hbm_gbps": 819.0,
                                               "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    assert not read(ctx, **decl.get("params", {}))
    if decl["reader"].startswith(("sparse_roofline", "kernel_calls")):
        assert read(ctx, **decl["params"]) is None
        ctx.sizes = {k: v for k, v in SIZES.items()
                     if k != "num_hidden_layers"}
        assert read(ctx, **decl["params"]) is None


def test_the_new_metrics_read_a_recorded_excerpt():
    """Kernel times by name, the context from the traffic file (24,576 +
    256 / 2), the bounds from the family's counts: the decode kernel's share
    is the LARGER bound's (the operations', by a hair, at 128 heads), read BY
    CALL: a trace that lost the second step's op events (they come back as
    their `while`'s own time, as one traced run on the chip did) reads what
    the whole one reads, where a share by a counter of steps reads twice."""
    layers = SIZES["num_hidden_layers"]
    step = [[f"mla_latent_decode.{i}", i * 1e6, 0.8e6] for i in range(layers)]
    ops = [["while.2", 0.0, 11e6], *step, ["fusion", 5e6, 1e6],
           *[[n, 6e6 + t, d] for n, t, d in step],  # ns: 2 steps, 8 ms
           ["mla_dense_prefill.7", 12e6, 70e6],
           ["mla_sparse_prefill.2", 82e6, 9e6],     # DeepSeek's: not read
           ["mla_dense_prefill", 91e6, 30e6]]       # 100 ms
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 121e6), {"hbm_gbps": 819.0,
                                                 "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    read = lambda name: M.reader(M.metric(name)["reader"])(  # noqa: E731
        ctx, **M.metric(name)["params"])
    assert read("latent_attn_ms.gen") == pytest.approx(4.0)
    assert read("latent_prefill_ms.gen") == pytest.approx(100.0)
    by_bytes = 8 * 5 * 24704 * 1152 / 819e9
    by_flops = 8 * 5 * 24704 * 128 * 1088 * 2 / 197e12
    assert by_flops > by_bytes > 0.99 * by_flops
    whole = read("latent_dense_attn_roofline.gen")
    assert whole == pytest.approx(100 * 2 * by_flops / 8e-3) \
        == pytest.approx(100 * by_flops / layers / 0.8e-3)
    counts = flops.family_counts(SIZES, M)
    assert read("latent_prefill_mxu.gen") == pytest.approx(
        100 * counts.causal_prefill_flops(SIZES, 8, 24576) / 197e12 / 100e-3)
    # the accepted bytes-only share would read this cell at half: not joined
    by_steps = lambda: M.reader(M.metric(  # noqa: E731
        "latent_attn_roofline.gen")["reader"])(
            ctx, **M.metric("latent_attn_roofline.gen")["params"])
    assert by_steps() == pytest.approx(100 * 2 * by_bytes / 8e-3)
    # the second step's ops lost, its time the `while`'s own
    del ops[2 + layers:2 + 2 * layers]
    assert read("latent_attn_ms.gen") == pytest.approx(2.0)
    assert read("latent_dense_attn_roofline.gen") == pytest.approx(whole)
    assert by_steps() == pytest.approx(100 * 2 * by_bytes / 4e-3)
    # a call cut by the window's edge is no call
    ctx.trace_window = (0.5e6, 121e6)
    assert read("latent_dense_attn_roofline.gen") == pytest.approx(whole)
    ctx.trace_window = (12e6, 121e6)
    assert read("latent_dense_attn_roofline.gen") is None


def test_the_kernels_are_named_as_the_metrics_search_for_them():
    from deepspeed_tpu.ops.pallas import mla, mla_sparse
    names = {"latent_attn_ms.gen": mla.KERNEL_NAME,
             "latent_dense_attn_roofline.gen": mla.KERNEL_NAME,
             "latent_prefill_ms.gen": mla_sparse.DENSE_PREFILL_NAME,
             "latent_prefill_mxu.gen": mla_sparse.DENSE_PREFILL_NAME}
    for metric, kernel in names.items():
        pattern = M.metric(metric)["params"]["pattern"]
        assert re.search(pattern, kernel + ".3"), (metric, kernel)
        # and neither reads DeepSeek's kernels, nor DeepSeek's metrics these
        assert not any(re.search(pattern, n) for n in (
            mla_sparse.DECODE_NAME, mla_sparse.PREFILL_NAME))
    for metric in ("latent_sparse_prefill_ms.gen",
                   "latent_sparse_prefill_mxu.gen",
                   "latent_sparse_attn_ms.gen"):
        assert not re.search(M.metric(metric)["params"]["pattern"],
                             mla_sparse.DENSE_PREFILL_NAME)


def test_the_traced_rehearsal_of_the_openpangu_cell_runs_on_the_cpu():
    """A process of its own (the harness holds one trace directory a
    checkout) that compiles the reference, a prefill and a decode program:
    the counters, the gauge of the one kind of cache, no device metric."""
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 58),
           "--seconds", "1", "--trace", "1"]
    done = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True,
                          timeout=600, env={**os.environ,
                                            "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    assert {"latent_kv_gb.gen", "held_assign_share.gen",
            "experts_touched_share.gen"} <= set(got)
    assert not set(NEW_METRICS) & set(got) and "index_kv_gb.gen" not in got
    # 8 rows of 128 slots (40 + 6 rounded up), 2 layers, bf16
    assert got["latent_kv_gb.gen"]["value"] == pytest.approx(
        2 * 8 * 128 * 40 * 2 / 1e9)
    # experts 0-3 of 16 held: about a quarter of the assignments
    assert 10 < got["held_assign_share.gen"]["value"] < 45
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/openpangu_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32 at toy widths, where the served path IS the
    reference, a dropped pair of post norms and a halved cache are not, and
    bf16 angles still are (48 positions are whole numbers in bf16: the chip
    run at 24,576 is where that fault shows)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "openpangu_decode_logits", os.path.join(
            CHECKOUT, "tools", "openpangu_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5
    assert {"no_post_norm", "half_cache"} <= set(line["told_apart"])
    assert line["half_cache"]["min"] < 1e-5 < 0.1 < \
        line["half_cache"]["decode_min"]       # a prefill reads it whole
    assert line["no_rope_key"]["decode_min"] > 100 * line["served"]["max"]
    assert set(line["step_ms"]) == {"served", *tool.FAULTS}
    assert {39, 40, 41, 47} <= set(line["positions"])
    # the functions it replaced are the program's again
    from deepspeed_tpu.models import latent, openpangu
    from deepspeed_tpu.models.llama import RMSNorm
    assert openpangu.RMSNorm is RMSNorm and latent.project.__module__ == \
        "deepspeed_tpu.models.latent"
