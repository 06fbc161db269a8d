"""The Trinity-Mini configuration and its cell, as new files only: the file
against its source (the catalog's config, key for key) and the issue's
arithmetic, the family's counts against a count by hand and the program's
tree at the published widths, the six new metrics over a recorded excerpt
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
NAME = "trinity-mini-l16-ep8"
CELL = NAME + ".generate-agent-8k"
SIZES = M.config(NAME)
S, F = "sliding_attention", "full_attention"
# every key of the catalog's `config`, as published
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = ["num_hidden_layers", "layer_types", "num_experts"]
RING_ROW_BYTES = 4194304    # a ring a row a layer: 2,048 slots x K and V x 4 x 128 x 2
NEW_METRICS = ["ring_decode_attn_ms.gen", "ring_decode_attn_roofline.gen",
               "full_decode_attn_roofline.gen", "band_prefill_ms.gen",
               "band_prefill_mxu.gen", "window_attend_share.gen"]
JOINED = ["decode_step_ms.gen", "decode_device_ms.gen", "prefill_share.gen",
          "idle_share.gen", "peak_hbm_gb.gen", "mfu.gen",
          "scope_unmatched_share.gen", "setup_compile_s.gen",
          "setup_compile_count.gen", "setup_trace_lower_s.gen",
          "setup_cache_miss_s.gen", "setup_engine_init_s.gen",
          "setup_unattributed_s.gen", "moe_gmm_ms.gen",
          "moe_gmm_decode_ms.gen", "moe_dispatch_ms.gen",
          "held_assign_share.gen", "experts_touched_share.gen",
          "window_kv_gb.gen", "dense_decode_attn_ms.gen"]


def test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert {k: SIZES[k] for k in PUBLISHED if k not in REDUCED} == \
        {k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert SIZES["reduced"] == REDUCED
    assert SIZES["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    # published layers 0-15 as they are: three whole periods and more, both
    # leading dense layers; an eighth of the experts; the WHOLE vocabulary
    assert SIZES["layer_types"] == PUBLISHED["layer_types"][:16]
    assert (SIZES["num_hidden_layers"], SIZES["num_dense_layers"],
            SIZES["num_experts"], SIZES["router_experts"],
            SIZES["expert_offset"], SIZES["window_layers"],
            SIZES["full_layers"]) == (16, 2, 16, 128, 0, 12, 4)
    assert SIZES["layer_types"].count(S) == SIZES["window_layers"]
    assert SIZES["layer_types"].count(F) == SIZES["full_layers"]
    assert SIZES["num_experts"] * 8 == SIZES["router_experts"]
    assert "eight chips" in SIZES["deployment"] \
        and "0-15" in SIZES["deployment"]
    for point in ("source_of_form", "embedding_scale", "qk_norm", "rotary",
                  "output_gate", "four_norms", "router_score",
                  "selection_bias", "precision", "weights", "router"):
        assert point in SIZES["assumed"], point
    assert "published code wins" in SIZES["assumed"]["source_of_form"]
    cell = M.workload(CELL)
    traffic = M.traffic(cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "generate-agent-8k"
    assert traffic["kind"] == "generate" and \
        traffic["prompt"]["values"] == [8192]
    assert (traffic["batch"], traffic["new_tokens"], traffic["check_rows"],
            traffic["trace_batches"]) == (32, 256, 4, 1)
    # the rehearsal's rings wrap: a prompt of 40 under a window of 16
    assert SIZES["rehearsal"]["sliding_window"] == 16
    assert traffic["rehearsal"]["prompt"]["values"] == [40]
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in M.metrics_for(CELL, group)}
    assert {"out_tok_s", "setup_s", *JOINED, *NEW_METRICS} <= listed
    # it does NOT join what reads a differential ring, a latent row, a
    # choice or a recurrent state
    assert not {"window_attn_roofline.gen", "window_attn_ms.gen",
                "shared_kv_gb.gen", "latent_kv_gb.gen", "selected_share.gen",
                "recurrent_state_gb.gen", "dense_slots_live_share.gen"} \
        & listed
    for name in NEW_METRICS:
        assert M.metric(name)["workloads"] == [CELL]
        assert next(m for m in M.doc["per_layer"]
                    if m["name"] == name)["workloads"] == [CELL]
        # no reader code is added, and none that reads scopes
        assert not M.metric(name)["reader"].startswith("scopes:")
    assert CELL in {w["name"] for w in M.doc["workloads"]}
    assert NAME in {c["name"] for c in M.doc["configs"]}
    assert next(c for c in M.doc["configs"] if c["name"] == NAME)[
        "source"] == SIZES["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"


def test_a_form_the_program_has_not_is_refused():
    adapter = M.module("configs", SIZES["adapter"])
    for key, other in (("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("tie_word_embeddings", True), ("n_group", 4),
                       ("topk_group", 2), ("score_func", "softmax"),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="one form"):
            adapter.model_config({**SIZES, key: other})


def test_counts_are_the_issue_s_arithmetic_and_a_count_by_hand():
    counts = flops.family_counts(SIZES, M)
    # attention 27.26 M a layer (q, o and the gate 8.39 M each; k and v 1.05
    # M each), the dense FFN 37.75 M, an expert 6.29 M, the router 0.26 M
    assert counts._attention(SIZES) == 3 * 2048 * 4096 + 2 * 2048 * 512
    assert round(counts._attention(SIZES) / 1e6, 2) == 27.26
    assert round(counts._dense_ffn(SIZES) / 1e6, 2) == 37.75
    assert round(counts._expert(SIZES) / 1e6, 2) == 6.29
    assert round(counts._router(SIZES) / 1e6, 2) == 0.26
    # 2,832.9 M held, 5.67 GB in bf16, 35% of the chip; one of a token's 8
    # experts falls here on average (8 x 16 / 128), beside the shared one
    assert round(flops.total_params(SIZES, manifest=M) / 1e6, 1) == 2832.9
    assert round(2 * flops.total_params(SIZES, manifest=M) / 1e9, 2) == 5.67
    assert 0.35 < 2 * flops.total_params(SIZES, manifest=M) / 16e9 < 0.36
    assert flops.matmul_params(SIZES, manifest=M) == \
        16 * counts._attention(SIZES) + 2 * counts._dense_ffn(SIZES) + 14 * (
            counts._router(SIZES) + 2 * counts._expert(SIZES)) \
        + 2048 * PUBLISHED["vocab_size"]
    assert round(flops.matmul_params(SIZES, manifest=M) / 1e6) == 1102
    # a token: 2,048 bytes a full layer, 8,192 over four; a ring 4.19 MB a
    # row a layer
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 8192
    assert 2048 * 2 * 4 * 128 * 2 == RING_ROW_BYTES == 4 * 1024 ** 2
    # a decode step at the mean context 8,320: 1.61 GB of rings, 2.18 GB of
    # full rows; a ring not yet full reads what it holds
    assert counts.ring_read_bytes(SIZES, 32, 8320) == 32 * 12 * RING_ROW_BYTES
    assert round(counts.ring_read_bytes(SIZES, 32, 8320) / 1e9, 2) == 1.61
    assert counts.ring_read_bytes(SIZES, 1, 100) == 12 * 100 * 2048
    assert counts.full_read_bytes(SIZES, 32, 8320) == 32 * 4 * 8320 * 2048
    assert round(counts.full_read_bytes(SIZES, 32, 8320) / 1e9, 2) == 2.18
    # a batch's prefill: the band's pairs alone, the first window's triangle
    # taken off; 92 TFLOP of window layers beside 70 of full ones
    tri = lambda n: n * (n + 1) // 2  # noqa: E731
    pairs = tri(8192) - tri(8192 - 2048)
    assert counts.band_prefill_flops(SIZES, 32, 8192) == \
        32 * 12 * pairs * 32 * 512
    assert round(counts.band_prefill_flops(SIZES, 32, 8192) / 1e12, 1) == 92.4
    assert counts.band_prefill_flops(SIZES, 1, 100) == 12 * tri(100) * 32 * 512
    full = 32 * 4 * tri(8192) * 32 * 512
    assert round((counts.band_prefill_flops(SIZES, 32, 8192) + full) / 1e12) \
        == 163
    assert flops.train_flops_per_token(SIZES, 4096, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)
    # BY HAND at the toy size (hidden 64, 4 heads of 16 on 2, FFN 96, experts
    # of 32: 4 held of 16, top 4; 4 layers, 1 dense; vocabulary 256)
    toy = {**SIZES, **SIZES["rehearsal"]}
    attention = 3 * 64 * 64 + 2 * 64 * 32
    assert counts._attention(toy) == attention == 16384
    norms = 4 * (4 * 64 + 2 * 16) + 64
    assert counts.total_params(toy) == 4 * attention + 3 * 64 * 96 + 3 * (
        64 * 16 + 16 + (4 + 1) * 3 * 64 * 32) + 2 * 64 * 256 + norms
    assert counts.matmul_params(toy) == 4 * attention + 3 * 64 * 96 + 3 * (
        64 * 16 + (4 * 4 / 16 + 1) * 3 * 64 * 32) + 64 * 256
    assert counts.kv_bytes_per_token(toy) == 2 * 1 * 2 * 16 * 2


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.n_group,
            cfg.router_bias_scale, cfg.rope_theta, cfg.window_layers,
            cfg.full_layers) == (16, 128, 1, 0.01, 10000, 12, 4)
    from deepspeed_tpu.models.afmoe import AfmoeForCausalLM
    model = AfmoeForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    toy = {**SIZES, **SIZES["rehearsal"]}
    toy_shapes = jax.eval_shape(
        AfmoeForCausalLM(adapter.model_config(toy)).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        toy_shapes["params"])) == flops.total_params(toy, manifest=M)
    # the cell's cache: 8,192 + 256 = 8,448 slots a full row; 32 rows hold
    # 2.21 GB of full rows + 1.61 GB of rings = 3.83 GB (every layer full:
    # 8.86 GB)
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    kinds = cfg.kv_bytes_by_kind(32, 8448)
    assert kinds == {"window_kv_bytes": 32 * 12 * RING_ROW_BYTES,
                     "full_kv_bytes": 32 * 8448 * 8192}
    cache = jax.eval_shape(lambda: model.make_cache(32, 8448))
    assert cache.max_len == 8448 and cache.window.max_len == 2048
    for kind, part in (("window_kv_bytes", cache.window),
                       ("full_kv_bytes", cache.kv)):
        assert sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves((part.k, part.v))) == kinds[kind]
    total = kv_cache_bytes(cfg, 32, 8448, jnp.bfloat16)
    assert total == sum(kinds.values())
    assert (round(kinds["full_kv_bytes"] / 1e9, 2),
            round(kinds["window_kv_bytes"] / 1e9, 2),
            round(total / 1e9, 2)) == (2.21, 1.61, 3.83)
    assert round(16 * 32 * 8448 * 2048 / 1e9, 2) == 8.86


def test_one_draw_of_the_weights_and_the_router_s_spread():
    """`--seed` draws the prompts: two seeds, one tree; and the adapter
    scales the routers' weights by `ROUTER_SPREAD`, the routed experts' up
    and down projections by `ROUTED_EXPERT_DAMP` (not their gate, not the
    shared expert) and NOTHING else."""
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.float32)
    from deepspeed_tpu.models.afmoe import materialize_params
    _, raw = materialize_params(cfg, rng=jax.random.PRNGKey(
        adapter.WEIGHTS_SEED), param_dtype=jnp.float32)
    _, one = adapter.materialize(cfg, 2 ** 31 + 7, jnp.float32)
    _, other = adapter.materialize(cfg, 3, jnp.float32)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: bool(jnp.all(x == y)), one, other)))
    scaled = []
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(one),
                            jax.tree_util.tree_leaves(raw)):
        name = jax.tree_util.keystr(path)
        by = adapter.ROUTER_SPREAD if name.endswith("['gate']['wg']") else \
            adapter.ROUTED_EXPERT_DAMP if name.endswith(
                ("['experts']['up']", "['experts']['down']")) else 1.0
        assert bool(jnp.all(a == b * by)), name
        scaled.append(by)
    assert sorted(b for b in scaled if b != 1.0) == [0.5] * 6 + [4.0] * 3 \
        and adapter.WEIGHTS_SEED == 60
    assert "4.0 x" in SIZES["assumed"]["router"]
    assert SIZES["assumed"]["routed_expert_damp"].startswith(
        "0.5 (afmoe_adapter.ROUTED_EXPERT_DAMP)")


def test_the_margin_simulation_is_the_one_the_file_cites():
    """`generate-agent-8k.margin_sim.py`: at the seeded spread a third of
    the rows are at a safe margin, at four times it two thirds, and 4 safe
    rows of 32 are found in all but one run in billions."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "margin_sim", M.find("traffic", "generate-agent-8k.margin_sim.py"))
    sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sim)
    import numpy as np
    sim.ROWS = 1000
    seeded = 0.02 * 2048 ** 0.5
    low = float((sim.margins(seeded, np.random.default_rng(60))
                 >= sim.SAFE).mean())
    high = float((sim.margins(4 * seeded, np.random.default_rng(60))
                  >= sim.SAFE).mean())
    assert 0.24 < low < 0.38 and 0.6 < high < 0.8
    assert sim.fewer_than(4, high, 32) < 1e-8
    assert (sim.E, sim.K, sim.HELD, sim.LAYERS) == (128, 8, 16, 14)


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-agent-8k"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric):
    """A parent commit has no such kernel and no such counter: the reader
    returns None and the line leaves the metric out; it never raises."""
    decl = M.metric(metric)
    read = M.reader(decl["reader"])
    if decl["reader"].startswith("serving:"):
        from deepspeed_tpu.telemetry import get_hub
        hub = get_hub()
        kept = {k: hub.counters.pop(k) for k in list(hub.counters)
                if k in decl["params"].values()}
        try:
            assert read(ctx_without_anything(), **decl["params"]) is None
        finally:
            hub.counters.update(kept)
        return
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
                     if k not in ("window_layers", "full_layers")}
        assert read(ctx, **decl["params"]) is None


def test_the_new_metrics_read_a_recorded_excerpt():
    """Kernel times by name, the context from the traffic file (8,192 + 256
    / 2), the bounds from the family's counts: each decode kernel's share BY
    CALL, over its own layers (twelve ring calls and four full ones a step),
    so a trace that lost a step's op events reads what a whole one reads."""
    ring = [[f"self_attn_ring_decode.{i}", i * 1e6, 0.25e6] for i in range(12)]
    full = [[f"self_attn_dense_decode.{i}", 12e6 + i * 1e6, 1e6]
            for i in range(4)]
    step = ring + full                          # ns: 3 ms of rings, 4 of full
    ops = [["while.2", 0.0, 40e6], *step,
           *[[n, 20e6 + t, d] for n, t, d in step],
           ["self_attn_flash_fwd_band.7", 40e6, 600e6],
           ["self_attn_flash_fwd.2", 640e6, 300e6],     # the full layers'
           ["self_attn_flash_fwd_band", 940e6, 400e6]]  # 1,000 ms of band
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 1340e6), {"hbm_gbps": 819.0,
                                                  "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "traced_batches": 1}
    read = lambda name: M.reader(M.metric(name)["reader"])(  # noqa: E731
        ctx, **M.metric(name)["params"])
    assert read("ring_decode_attn_ms.gen") == pytest.approx(3.0)
    assert read("dense_decode_attn_ms.gen") == pytest.approx(4.0)
    assert read("band_prefill_ms.gen") == pytest.approx(1000.0)
    counts = flops.family_counts(SIZES, M)
    ring_s = counts.ring_read_bytes(SIZES, 32, 8320) / 819e9
    full_s = counts.full_read_bytes(SIZES, 32, 8320) / 819e9
    whole = read("ring_decode_attn_roofline.gen")
    assert whole == pytest.approx(100 * ring_s / 12 / 0.25e-3)
    assert read("full_decode_attn_roofline.gen") == pytest.approx(
        100 * full_s / 4 / 1e-3)
    assert 0 < whole < 100 and 0 < read("full_decode_attn_roofline.gen") < 100
    assert read("band_prefill_mxu.gen") == pytest.approx(
        100 * counts.band_prefill_flops(SIZES, 32, 8192) / 197e12 / 1.0)
    # the second step's ops lost, its time the `while`'s own
    del ops[1 + 16:1 + 32]
    assert read("ring_decode_attn_ms.gen") == pytest.approx(1.5)
    assert read("ring_decode_attn_roofline.gen") == pytest.approx(whole)
    # the counters' share: what the program counted, summed over the run
    from deepspeed_tpu.telemetry import get_hub
    hub = get_hub()
    kept = dict(hub.counters)
    try:
        hub.counters["serving_v1/kv_positions_window"] = 12 * 2048
        hub.counters["serving_v1/kv_positions_attended"] = 12 * 2048 + 4 * 8320
        assert read("window_attend_share.gen") == pytest.approx(42.478, 1e-4)
    finally:
        hub.counters.clear()
        hub.counters.update(kept)


def test_the_kernels_are_named_as_the_metrics_search_for_them():
    from deepspeed_tpu.ops.pallas import decode_attention, flash_attention
    names = {"ring_decode_attn_ms.gen": decode_attention.RING_NAME,
             "ring_decode_attn_roofline.gen": decode_attention.RING_NAME,
             "full_decode_attn_roofline.gen": decode_attention.DENSE_NAME,
             "dense_decode_attn_ms.gen": decode_attention.DENSE_NAME,
             "band_prefill_ms.gen": flash_attention.BAND_NAME,
             "band_prefill_mxu.gen": flash_attention.BAND_NAME}
    every = {decode_attention.RING_NAME, decode_attention.DENSE_NAME,
             flash_attention.BAND_NAME, flash_attention.FWD_NAME,
             "self_attn_flash_bwd", "diff_attn_window_decode"}
    for metric, kernel in names.items():
        pattern = M.metric(metric)["params"]["pattern"]
        assert re.search(pattern, kernel + ".3"), (metric, kernel)
        # each reads its own kernel and no other
        assert not any(re.search(pattern, n) for n in every - {kernel}), metric
    # the accepted names did not move
    assert (decode_attention.DENSE_NAME, flash_attention.FWD_NAME) == (
        "self_attn_dense_decode", "self_attn_flash_fwd")


def test_the_traced_rehearsal_of_the_afmoe_cell_runs_on_the_cpu():
    """A process of its own (the harness holds one trace directory a
    checkout) that compiles the reference, a prefill and a decode program:
    the counters, the gauge of the rings, no device metric."""
    cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
           "--rehearsal", "--workload", CELL, "--seed", str(2 ** 31 + 60),
           "--seconds", "1", "--trace", "1"]
    done = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True,
                          timeout=600, env={**os.environ,
                                            "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    assert {"window_kv_gb.gen", "held_assign_share.gen",
            "experts_touched_share.gen", "window_attend_share.gen"} <= set(got)
    assert not (set(NEW_METRICS) - {"window_attend_share.gen"}) & set(got)
    # 4 rows, 3 rings of 16 slots, 2 KV heads of 16, K and V, bf16
    assert got["window_kv_gb.gen"]["value"] == pytest.approx(
        4 * 3 * 16 * 2 * 2 * 16 * 2 / 1e9)
    # decode steps at 41 .. 45 positions: three rings of 16 beside one full
    # layer of 41 .. 45
    assert got["window_attend_share.gen"]["value"] == pytest.approx(
        100 * 5 * 48 / (5 * 48 + sum(range(41, 46))))
    # experts 0-3 of 16 held: about a quarter of the assignments
    assert 10 < got["held_assign_share.gen"]["value"] < 45
    assert line["notes"]["check"]["margin_safe"] == 0.02
    assert min(line["notes"]["check"]["margins"]) >= 0.02


def test_the_decode_logits_tool_at_a_toy_size(capsys):
    """`tools/afmoe_decode_logits.py --rehearsal`: the chip comparison's
    control flow, in float32 at toy widths, where the served path IS the
    reference and a dropped gate, rotary put into the full layers and a ring
    read one slot short are not (a prefill does not read a ring, so the
    last shows in decode alone); a bf16 router moves the logits a hundred
    times the served path's rounding and flips nothing at 16 experts."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "afmoe_decode_logits", os.path.join(
            CHECKOUT, "tools", "afmoe_decode_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["served"]["max"] < 1e-5
    assert {"no_gate", "rotary_in_full", "ring_one_short"} <= set(
        line["told_apart"])
    assert line["ring_one_short"]["min"] < 1e-5 < 0.05 < \
        line["ring_one_short"]["decode_min"]
    assert line["bf16_router"]["min"] > 10 * line["served"]["max"]
    assert set(line["step_ms"]) == {"served", *tool.FAULTS}
    # a prompt of 40 under a window of 16: every judged step is past a wrap
    assert {39, 40, 41, 47} <= set(line["positions"])
    # the functions it replaced are the program's again
    from deepspeed_tpu.models import afmoe
    from deepspeed_tpu.moe import layer, sharded_moe
    from deepspeed_tpu.ops import attention
    assert afmoe._gated.__module__ == afmoe._rotated.__module__ == \
        "deepspeed_tpu.models.afmoe"
    assert layer.route_topk is sharded_moe.route_topk
    assert attention.ring_live.__module__ == "deepspeed_tpu.ops.attention"
