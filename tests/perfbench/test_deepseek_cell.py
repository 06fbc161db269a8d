"""The DeepSeek-sparse configuration and its cell, as new files only: the file
against its source (the catalog's config, key for key) and the issue's
arithmetic, the family's counts against the program's tree at the published
widths, the readers over what the cell brings (the new roofline reader on a
recorded excerpt, and on a run that has nothing for it to read), and ONE
traced rehearsal of the cell for what `test_rehearsal.py`'s loop does not
assert: the counters, both gauges of the one engine, the kernels the program
would name on the chip."""

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
NAME = "deepseek-v3.2-l5-ep16"
CELL = NAME + ".generate-longctx-latent"
SIZES = M.config(NAME)
# every key of the catalog's `config`, as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
NEW_METRICS = ["latent_sparse_attn_ms.gen", "latent_sparse_prefill_ms.gen",
               "latent_sparse_attn_roofline.gen",
               "latent_sparse_prefill_mxu.gen"]


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
                5, 1, 16, 256, 0, 16160, 0, [0, 3, 4, 5, 6])
    # a sixteenth of the experts: HALF a group of 32; an eighth of the rows
    assert SIZES["n_routed_experts"] * 16 == SIZES["router_experts"]
    assert SIZES["router_experts"] // SIZES["n_group"] == \
        2 * SIZES["n_routed_experts"]
    assert SIZES["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "sixteen chips" in SIZES["deployment"] \
        and "0-15" in SIZES["deployment"]
    for point in ("index_key_norm", "index_rotary", "index_precision",
                  "selection", "yarn", "mtp", "published_layers", "weights",
                  "router"):
        assert point in SIZES["assumed"], point
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    # the issue's two fallbacks, both taken (PERF.md, PR 54, has the
    # readings that forced them): 12 x `index_topk` tokens, 256 new
    assert cell["chips"] == 1 and traffic["prompt"]["values"] == [24576]
    assert (traffic["batch"], traffic["new_tokens"], traffic["check_rows"],
            traffic["trace_batches"]) == (8, 256, 2, 1)
    # the rehearsal's choice really chooses: 8 of a 40-token prompt
    assert SIZES["rehearsal"]["index_topk"] == 8
    assert traffic["rehearsal"]["prompt"]["values"] == [40]
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", "decode_step_ms.gen", "idle_share.gen",
            "peak_hbm_gb.gen", "mfu.gen", "moe_gmm_ms.gen",
            "held_assign_share.gen", "experts_touched_share.gen",
            "setup_compile_s.gen", "setup_compile_count.gen",
            "setup_trace_lower_s.gen", "setup_cache_miss_s.gen",
            "setup_engine_init_s.gen", "setup_unattributed_s.gen",
            "latent_kv_gb.gen", "index_kv_gb.gen", "selected_share.gen",
            "sparse_select_ms.gen", "sparse_select_roofline.gen",
            *NEW_METRICS} <= listed
    # Ling's decode kernel and Keye's attention are not this cell's
    assert not {"latent_attn_ms.gen", "sparse_attn_ms.gen",
                "sparse_prefill_ms.gen", "recurrent_state_gb.gen"} & listed
    for name in NEW_METRICS:
        assert M.metric(name)["workloads"] == [CELL]
    assert len(M.doc["workloads"]) == 9 and sum(
        w["chips"] == 4 for w in M.doc["workloads"]) == 1


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("attention_bias", True), ("scoring_func", "softmax"),
                       ("num_nextn_predict_layers", 1),
                       ("num_key_value_heads", 8), ("moe_layer_freq", 2),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})
    with pytest.raises(ValueError, match="one form"):
        adapter.model_config({**SIZES, "rope_scaling": {
            **SIZES["rope_scaling"], "type": "linear"}})


def test_counts_are_the_issue_s_arithmetic():
    counts = flops.family_counts(SIZES, M)
    # attention 187.11 M, indexer 13.96 M, the dense FFN 396.36 M, an expert
    # 44.04 M, the router 1.84 M
    assert round(counts._attention(SIZES) / 1e6, 2) == 187.11
    assert round(counts._indexer(SIZES) / 1e6, 2) == 13.96
    assert round(counts._dense_ffn(SIZES) / 1e6, 2) == 396.36
    assert round(counts._expert(SIZES) / 1e6, 2) == 44.04
    assert round(counts._router(SIZES) / 1e6, 2) == 1.84
    # 4,635.5 M held, 9.27 GB in bf16; half of one of a token's 8 experts
    # falls here on average, beside the shared one
    assert round(flops.total_params(SIZES, manifest=M) / 1e6, 1) == 4635.5
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 9.27
    assert flops.matmul_params(SIZES, manifest=M) == 5 * (
        counts._attention(SIZES) + counts._indexer(SIZES)) \
        + counts._dense_ffn(SIZES) + 4 * (
            counts._router(SIZES) + counts._expert(SIZES) * 3 // 2) \
        + 7168 * 16160
    # a token: 1,152 bytes of latent row and 256 of index key a layer
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 7040
    # a decode step at the mean context, a row a layer: 8.45 MB of index
    # keys, 2.36 MB of chosen latent rows, 0.57 GFLOP over them
    assert counts.index_read_bytes(SIZES, 1, 33024) == 5 * 33024 * 256
    assert counts.selected_read_bytes(SIZES, 1, 33024) == 5 * 2048 * 1152
    assert round(counts.selected_read_bytes(SIZES, 1, 33024) / 5e6, 2) == 2.36
    assert counts.selected_attn_flops(SIZES, 1, 33024) == \
        5 * 128 * 1088 * 2 * 2048
    assert round(counts.selected_attn_flops(SIZES, 1, 33024) / 5e9, 2) == 0.57
    assert counts.selected_attn_flops(SIZES, 8, 100) == \
        8 * 5 * 128 * 1088 * 2 * 100
    # the two bounds meet at 128 heads: 2.88 and 2.90 us a row a layer
    assert 2048 * 1152 / 819e9 == pytest.approx(0.5704e9 / 197e12, rel=0.01)
    # a batch's prefill attention under the choice: a query's 2,048 pairs
    # (fewer for the first 2,048), 128 heads x 320 x 2 a pair
    pairs = 2048 * 2049 // 2 + (32768 - 2048) * 2048
    assert counts.selected_prefill_flops(SIZES, 8, 32768) == \
        8 * 5 * pairs * 128 * 640
    assert flops.train_flops_per_token(SIZES, 32768, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.num_experts, cfg.router_experts) == (64, 128, 2048, 16, 256)
    assert cfg.rope_scaling.factor == 40 and \
        cfg.rope_scaling.original_max_position_embeddings == 4096
    from deepspeed_tpu.models.deepseek_sparse import DeepseekSparseForCausalLM
    model = DeepseekSparseForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    # the cell's cache: 8 rows of 33,280 slots, 1.53 GB of latent rows and
    # 0.34 of index keys, nothing padded in the count
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    kinds = cfg.kv_bytes_by_kind(8, 33280)
    assert kinds == {"latent_kv_bytes": 5 * 8 * 33280 * 1152,
                     "index_kv_bytes": 5 * 8 * 33280 * 256}
    cache = jax.eval_shape(lambda: model.make_cache(8, 33280))
    assert kv_cache_bytes(cfg, 8, 33280, jnp.bfloat16) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(
            (cache.latent.c, cache.index_keys.c))) == \
        8 * 33280 * flops.kv_bytes_per_token(SIZES, manifest=M)
    assert round(kv_cache_bytes(cfg, 8, 33280, jnp.bfloat16) / 1e9, 2) == 1.87
    # the cell's own length, 24,576 + 256 = 24,832 = 128 x 2 x 97, is GIVEN
    # ten blocks of 2,560 slots (a row of 33,280 is thirteen as it stands):
    # 1.44 GB held and counted
    assert (cfg.cache_slots(24832), cfg.cache_slots(33280),
            cfg.cache_slots(128)) == (25600, 33280, 128)
    assert jax.eval_shape(lambda: model.make_cache(8, 24832)).max_len == 25600
    assert round(kv_cache_bytes(cfg, 8, 24832, jnp.bfloat16) / 1e9, 2) == 1.44
    toy = adapter.model_config({**SIZES, **SIZES["rehearsal"]})
    assert (toy.index_topk, toy.num_experts, toy.router_experts) == (8, 4, 16)


def test_one_draw_of_the_weights_and_what_the_adapter_spreads():
    """Three draws of the toy tree, each one jitted init: `--seed` draws the
    prompts; the adapter moves the routers' weights and the embedding's rows
    and nothing else, in the seeded tree and not in the program."""
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.deepseek_sparse import materialize_params
    _, raw = materialize_params(cfg, rng=jax.random.PRNGKey(
        adapter.WEIGHTS_SEED), param_dtype=jnp.float32)
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.float32)
    _, other = adapter.materialize(cfg, 3, jnp.float32)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), one, other)))
    ratio = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(
            jnp.max(jnp.abs(a)) / jnp.max(jnp.abs(b)))), one, raw)
    moved = {name: r for name, r in jax.tree_util.tree_leaves(
        ratio, is_leaf=lambda x: isinstance(x, tuple)) if abs(r - 1) > 1e-6}
    assert moved == pytest.approx({
        "['layers']['layer_1_mlp']['gate']['wg']": adapter.ROUTER_SPREAD,
        "['embed_tokens']": adapter.EMBED_SPREAD})
    assert "embedding" in SIZES["assumed"]


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-longctx-latent"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric):
    """A parent commit has no such kernel: the reader returns None and the
    line leaves the metric out; it never raises."""
    decl = M.metric(metric)
    read = M.reader(decl["reader"])
    assert read(ctx_without_anything(), **decl.get("params", {})) is None
    # a trace without the kernel, peaks and counters there: no share (the
    # accepted `trace:op_ms_per` reads 0 ms of it)
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": [["fusion", 0.0, 4e6]],
                                   "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 4e6), {"hbm_gbps": 819.0,
                                               "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    assert not read(ctx, **decl.get("params", {}))
    if decl["reader"].startswith("sparse_roofline"):
        assert read(ctx, **decl["params"]) is None


def test_the_roofline_is_the_larger_of_its_two_bounds():
    """The new reader on a recorded excerpt: kernel times by name, the
    context from the traffic file (24576 + 256 / 2), the two bounds from the
    family's counts."""
    ops = [["mla_sparse_decode", 0.0, 3e6], ["fusion", 3e6, 4e6],
           ["mla_sparse_decode.1", 7e6, 5e6],
           ["sparse_attn_prefill_select.3", 12e6, 30e6],
           ["mla_sparse_prefill.7", 42e6, 70e6],
           ["mla_latent_decode", 112e6, 9e6]]     # ns: 8 ms, 100 ms; Ling's
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 121e6), {"hbm_gbps": 819.0,
                                                 "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    read = lambda name: M.reader(M.metric(name)["reader"])(  # noqa: E731
        ctx, **M.metric(name)["params"])
    assert read("latent_sparse_attn_ms.gen") == pytest.approx(4.0)
    assert read("latent_sparse_prefill_ms.gen") == pytest.approx(100.0)
    by_bytes = 8 * 5 * 2048 * 1152 / 819e9
    by_flops = 8 * 5 * 2048 * 128 * 1088 * 2 / 197e12
    assert by_flops > by_bytes          # by a hair: the flops are the bound
    assert read("latent_sparse_attn_roofline.gen") == pytest.approx(
        100 * 2 * by_flops / 8e-3)
    counts = flops.family_counts(SIZES, M)
    assert read("latent_sparse_prefill_mxu.gen") == pytest.approx(
        100 * counts.selected_prefill_flops(SIZES, 8, 24576) / 197e12 / 70e-3)
    # a family whose counts lack a function that was asked for: no number
    from perfbench.readers import sparse_roofline
    assert sparse_roofline.share_at_context(
        ctx, "^mla_sparse_decode", "traced_decode_steps",
        bytes="selected_read_bytes", flops="no_such_count") is None
    # the choice's roofline at a whole lane row: Keye's reader, this family's
    # bytes (256 a cached token a layer)
    ctx.trace["devices"]["0"]["ops"].append(["sparse_index_select", 121e6, 4e6])
    ctx.trace_window = (0.0, 125e6)
    assert read("sparse_select_roofline.gen") == pytest.approx(
        100 * 2 * 8 * 5 * 24704 * 256 / (819e9 * 4e-3))


def test_the_traced_rehearsal_of_the_deepseek_cell_runs_on_the_cpu():
    """A process of its own (the harness holds one trace directory a
    checkout) that compiles the reference, a prefill and a decode program:
    the ONE rehearsal beside `test_rehearsal.py`'s, for the counters, both
    gauges and the kernels' names."""
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 55),
           "--seconds", "1", "--trace", "1"]
    done = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True,
                          timeout=600, env={**os.environ,
                                            "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    # BOTH gauges of the one engine, and what the program counts, are there
    # off the chip too; device times are not
    assert {"latent_kv_gb.gen", "index_kv_gb.gen", "selected_share.gen",
            "held_assign_share.gen", "experts_touched_share.gen"} <= set(got)
    assert not set(NEW_METRICS) & set(got)
    # 8 rows of 128 slots (40 + 6 rounded up), 2 layers, bf16
    assert got["latent_kv_gb.gen"]["value"] == pytest.approx(
        2 * 8 * 128 * 40 * 2 / 1e9)
    assert got["index_kv_gb.gen"]["value"] == pytest.approx(
        2 * 8 * 128 * 16 * 2 / 1e9)
    # 8 of up to 46 positions: a prefill of 40 and 5 decode steps a batch
    seen = list(range(1, 46))
    assert got["selected_share.gen"]["value"] == pytest.approx(
        100 * sum(min(n, 8) for n in seen) / sum(seen))
    # experts 0-3 of 16 held: about a quarter of the assignments
    assert 10 < got["held_assign_share.gen"]["value"] < 45
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_kernels_are_named_as_the_metrics_search_for_them():
    """The names the cell's trace metrics match, from the modules that name
    the kernels (`tests/unit/ops/test_chip_compile.py` holds the compiled
    calls to them); none starts with Ling's `mla_latent_decode`."""
    import re
    from deepspeed_tpu.ops.pallas import mla, mla_sparse, sparse_select
    names = {"latent_sparse_attn_ms.gen": [mla_sparse.DECODE_NAME],
             "latent_sparse_attn_roofline.gen": [mla_sparse.DECODE_NAME],
             "latent_sparse_prefill_ms.gen": [
                 mla_sparse.PREFILL_NAME, sparse_select.PREFILL_SELECT_NAME],
             "latent_sparse_prefill_mxu.gen": [mla_sparse.PREFILL_NAME],
             "sparse_select_ms.gen": [sparse_select.SELECT_NAME]}
    for metric, kernels in names.items():
        pattern = M.metric(metric)["params"]["pattern"]
        for kernel in kernels:
            assert re.search(pattern, kernel + ".3"), (metric, kernel)
    ling = M.metric("latent_attn_ms.gen")["params"]["pattern"]
    assert re.search(ling, mla.KERNEL_NAME)
    assert not any(re.search(ling, n) for n in (mla_sparse.DECODE_NAME,
                                                mla_sparse.PREFILL_NAME))
    # the prefill's attention metric does not count the choice twice
    assert not re.search(M.metric("latent_sparse_prefill_mxu.gen")["params"][
        "pattern"], sparse_select.PREFILL_SELECT_NAME)


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/deepseek_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32 at toy widths, where both forms of the decode
    read ARE the reference and a pass without the selection is not."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "deepseek_decode_logits", os.path.join(CHECKOUT, "tools",
                                               "deepseek_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # at toy widths the embedding the adapter spreads leaves the attention a
    # hundredth of the logits: the limit is set for them
    assert tool.main(["--rehearsal", "--passes", "served,slab,dense",
                      "--limit", "0.005"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5 and line["slab"]["max"] < 1e-5
    assert line["told_apart"] == ["dense"] and line["dense"]["min"] > 5e-3
    assert set(line["step_ms"]) == {"served", "slab", "dense"}
    assert {39, 40, 41, 47} <= set(line["positions"])
