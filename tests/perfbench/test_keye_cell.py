"""The Keye-sparse configuration and its cell, as new files only: the file
against its source (the catalog's config, key for key) and the issue's
arithmetic, the family's counts against the program's tree at the published
widths, the readers over what the cell brings on a run that has nothing for
them to read, the rehearsal of the cell and the builder's decode-logits tool
at a toy size."""

import copy
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
NAME = "keye-vl2-30b-l12-ep8"
CELL = NAME + ".generate-longctx-32k"
SIZES = M.config(NAME)
# every key of the catalog's `config`, as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]
NEW_METRICS = ["sparse_select_ms.gen", "sparse_select_roofline.gen",
               "sparse_attn_ms.gen", "sparse_attn_roofline.gen",
               "sparse_prefill_ms.gen", "index_kv_gb.gen",
               "selected_share.gen"]


def test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s():
    assert problems(M) == [] and config_problems(M, NAME) == []
    # every published key is in the file; all but the reduced ones unchanged
    assert {k: SIZES[k] for k in PUBLISHED if k not in REDUCED} == \
        {k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert SIZES["reduced"] == REDUCED
    assert SIZES["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (SIZES["num_hidden_layers"], SIZES["num_experts"],
            SIZES["num_local_experts"], SIZES["router_experts"],
            SIZES["expert_offset"], SIZES["vocab_size"]) == (
                12, 16, 16, 128, 0, 18992)
    # an eighth of the experts and of the vocabulary, a quarter of the depth
    assert SIZES["num_experts"] * 8 == SIZES["router_experts"]
    assert SIZES["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "eight chips" in SIZES["deployment"] and "0-15" in SIZES["deployment"]
    for point in ("qk_norm", "index_key_norm", "index_rotary",
                  "index_precision", "mrope", "chunk_sizes", "selection",
                  "vision_tower", "weights", "router"):
        assert point in SIZES["assumed"], point
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and traffic["prompt"]["values"] == [32768]
    assert (traffic["batch"], traffic["check_rows"],
            traffic["trace_batches"]) == (8, 2, 1)
    # the rehearsal's choice really chooses: 8 of a 40-token prompt
    assert SIZES["rehearsal"]["sa_config"]["topk"] == 8
    assert traffic["rehearsal"]["prompt"]["values"] == [40]
    # the cell is on out_tok_s and on every .gen metric the issue lists
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", "decode_step_ms.gen", "idle_share.gen",
            "peak_hbm_gb.gen", "mfu.gen", "moe_gmm_ms.gen",
            "held_assign_share.gen", "experts_touched_share.gen",
            "setup_compile_s.gen", "setup_compile_count.gen",
            "setup_trace_lower_s.gen", "setup_cache_miss_s.gen",
            "setup_engine_init_s.gen", "setup_unattributed_s.gen",
            *NEW_METRICS} <= listed
    assert not {"dense_decode_attn_ms.gen", "recurrent_state_gb.gen"} & listed
    for name in NEW_METRICS:
        assert M.metric(name)["workloads"] == [CELL]


def test_the_checker_names_the_faults_with_eight_cells_too(tmp_path):
    """`test_manifest.py::test_problems_are_found` plants a wrong `moves`, a
    bad unit and ONE more four-chip cell; with this PR's eighth cell a
    quarter is two, that edit is allowed, and `tests/conftest.py` holds the
    old test an expected failure until a `benchmark` PR mends it. The same
    three faults, the four-chip cells ONE PAST what the contract allows
    however many cells there are, and all three named."""
    doc = copy.deepcopy(M.doc)
    doc["per_layer"][0]["moves"] = "out_tok_s"      # serve-chat does not report it
    doc["per_layer"][1]["unit"] = "tokens per second"
    cells = doc["workloads"]
    allowed = max(1, len(cells) // 4)
    four = sum(1 for cell in cells if cell["chips"] == 4)
    one = [cell for cell in cells if cell["chips"] == 1]
    for cell in one[:allowed + 1 - four]:
        cell["chips"] = 4
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    (tmp_path / "perfbench").mkdir()
    found = "\n".join(problems(Manifest(str(path))))
    assert "does not report out_tok_s" in found
    assert "tokens per second" in found
    assert f"{allowed + 1} four-chip cells of {len(cells)}" in found
    # and at what the contract allows, no such problem
    one[0]["chips"] = 1
    path.write_text(json.dumps(doc))
    assert "four-chip cells" not in "\n".join(problems(Manifest(str(path))))


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("attention_bias", True), ("use_sliding_window", True),
                       ("mlp_only_layers", [0]), ("num_local_experts", 128),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})
    two_keys = {**SIZES["sa_config"], "indexer_num_kv_heads": 2}
    with pytest.raises(ValueError, match="one form"):
        adapter.model_config({**SIZES, "sa_config": two_keys})


def test_counts_are_the_issue_s_arithmetic():
    counts = flops.family_counts(SIZES, M)
    # attention 18.87 M, indexer 2.26 M, router 0.26 M, an expert 4.72 M
    assert round(counts._attention(SIZES) / 1e6, 2) == 18.87
    assert round(counts._indexer(SIZES) / 1e6, 2) == 2.26
    assert round(counts._router(SIZES) / 1e6, 2) == 0.26
    assert counts._expert(SIZES) == 3 * 2048 * 768
    # 1.24 B held, 2.48 GB in bf16; one of a token's 8 experts falls here on
    # average
    assert round(flops.total_params(SIZES, manifest=M) / 1e9, 2) == 1.24
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 2.48
    assert flops.matmul_params(SIZES, manifest=M) == 12 * (
        counts._attention(SIZES) + counts._indexer(SIZES)
        + counts._router(SIZES) + counts._expert(SIZES)) + 2048 * 18992
    # a token: 2,048 bytes of K and V and 128 of index key a layer
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 26112
    # a decode step at the mean context: 33.8 MB of index keys a layer,
    # 33.6 MB of chosen K and V
    assert counts.index_read_bytes(SIZES, 8, 33024) == 12 * 8 * 33024 * 128
    assert counts.selected_read_bytes(SIZES, 8, 33024) == \
        12 * 8 * 2048 * 2048
    assert counts.selected_read_bytes(SIZES, 8, 100) == 12 * 8 * 100 * 2048
    assert flops.train_flops_per_token(SIZES, 32768, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.index_topk) == \
        (16, 64, 2048)
    from deepspeed_tpu.models.keye_sparse import KeyeSparseForCausalLM
    shapes = jax.eval_shape(KeyeSparseForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    # the cell's cache: 8 rows of 33,280 slots, 6.54 GB of K and V and 0.82
    # of index keys AS HELD (a whole lane row a key, 64 values and 64
    # zeros): 7.36 GB, where the issue's arithmetic (and the family's
    # `kv_bytes_per_token`, the key's own 64) has 6.95
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    assert cfg.kv_bytes_by_kind(8, 33280) == {
        "index_kv_bytes": 12 * 8 * 33280 * 256}
    held = KeyeSparseForCausalLM(cfg).make_cache
    cache = jax.eval_shape(lambda: held(8, 33280))
    assert kv_cache_bytes(cfg, 8, 33280, jnp.bfloat16) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(
            (cache.kv.k, cache.kv.v, cache.index_keys.c)))
    assert round(kv_cache_bytes(cfg, 8, 33280, jnp.bfloat16) / 1e9, 2) == 7.36
    assert round(8 * 33280 * flops.kv_bytes_per_token(SIZES, manifest=M)
                 / 1e9, 2) == 6.95
    toy = adapter.model_config({**SIZES, **SIZES["rehearsal"]})
    assert (toy.index_topk, toy.num_experts, toy.router_experts) == (8, 4, 8)


def test_one_draw_of_the_weights_and_only_the_routers_are_spread():
    """9 s: three draws of the toy tree, each one jitted init."""
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.keye_sparse import materialize_params
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
    assert moved == {f"['layers']['layer_{i}_mlp']['gate']['wg']"
                     for i in range(2)}
    assert bool(jnp.allclose(one["layers"]["layer_1_mlp"]["gate"]["wg"],
                             adapter.ROUTER_SPREAD
                             * raw["layers"]["layer_1_mlp"]["gate"]["wg"]))


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-longctx-32k"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", NEW_METRICS)
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
    ops = [["sparse_index_select", 0.0, 3e6], ["fusion", 3e6, 4e6],
           ["sparse_index_select.1", 7e6, 5e6],
           ["sparse_attn_decode", 12e6, 20e6],
           ["sparse_attn_prefill_select.3", 32e6, 30e6],
           ["sparse_attn_prefill", 62e6, 70e6]]   # ns: 8, 20 and 100 ms
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 132e6), {"hbm_gbps": 819.0,
                                                 "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1,
                    "out_tok_s": 200.0}
    read = lambda name: M.reader(M.metric(name)["reader"])(  # noqa: E731
        ctx, **M.metric(name)["params"])
    # the context is the traffic file's: 32768 + 512 / 2
    assert read("sparse_select_roofline.gen") == pytest.approx(
        100 * 2 * 12 * 8 * 33024 * 128 / (819e9 * 8e-3))
    assert read("sparse_attn_roofline.gen") == pytest.approx(
        100 * 2 * 12 * 8 * 2048 * 2048 / (819e9 * 20e-3))
    assert read("sparse_select_ms.gen") == pytest.approx(4.0)
    assert read("sparse_attn_ms.gen") == pytest.approx(10.0)
    # both prefill kernels, a batch
    assert read("sparse_prefill_ms.gen") == pytest.approx(100.0)
    mfu = M.metric("mfu.gen")
    assert M.reader(mfu["reader"])(ctx, **mfu["params"]) == pytest.approx(
        100 * 200 * 2 * flops.matmul_params(SIZES, manifest=M) / 197e12)


def test_the_counters_and_the_gauge_are_read_from_the_hub():
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    hub = TelemetryHub(enabled=False)
    set_hub(hub)
    hub.gauge("serving_v1/index_kv_bytes", 12 * 8 * 33280 * 256)
    hub.counter("serving_v1/kv_positions_selected", 2048)
    hub.counter("serving_v1/kv_positions_live", 33024)
    ctx = ctx_without_anything()
    gb = M.metric("index_kv_gb.gen")
    assert M.reader(gb["reader"])(ctx, **gb["params"]) == pytest.approx(
        0.818, abs=1e-3)
    share = M.metric("selected_share.gen")
    assert M.reader(share["reader"])(ctx, **share["params"]) == \
        pytest.approx(6.2, abs=0.01)
    set_hub(TelemetryHub(enabled=False))


def test_the_traced_rehearsal_of_the_keye_cell_runs_on_the_cpu():
    """14 s: a process of its own (the harness holds one trace directory a
    checkout) that compiles the reference, a prefill and a decode program."""
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 51),
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
    assert {"index_kv_gb.gen", "selected_share.gen", "held_assign_share.gen",
            "experts_touched_share.gen"} <= set(got)
    assert not {"sparse_select_ms.gen", "sparse_attn_roofline.gen",
                "sparse_prefill_ms.gen"} & set(got)
    # 8 of up to 46 positions: a prefill of 40 and 5 decode steps a batch
    seen = list(range(1, 46))
    assert got["selected_share.gen"]["value"] == pytest.approx(
        100 * sum(min(n, 8) for n in seen) / sum(seen))
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/keye_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32 at toy widths, where the served path IS the
    reference and each pass without a term of the selection is not. 15 s:
    five passes of the program, each traced anew (a pass may replace the
    scores' `relu`), and the reference."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "keye_decode_logits", os.path.join(CHECKOUT, "tools",
                                           "keye_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5 < line["limit"] == tool.LIMIT
    assert line["told_apart"] == ["dense", "half", "no_index_weights",
                                  "no_relu"]
    for name in line["told_apart"]:
        assert line[name]["min"] > 1e-2
    assert line["served_safe"]["of"] == 2 * 5
    assert {39, 40, 41, 47} <= set(line["positions"])


def test_the_scanned_walk_tool_at_a_toy_size():
    """`tools/keye_scanned_walk.py --rehearsal`, 20 s in a process of its own
    (it turns the compile cache off): the walk the tool times against the
    program's is the SAME model, token for token at the toy widths, so
    what it reads on the chip is the walk's cost alone."""
    done = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "tools",
                                      "keye_scanned_walk.py"), "--rehearsal"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    unrolled, scanned = line["walks"]
    assert (unrolled["walk"], scanned["walk"]) == ("unrolled", "scanned")
    assert scanned["tokens_equal"] == 1.0
    assert len(scanned["batch_s"]) == 2 and scanned["trace_s"] > 0
