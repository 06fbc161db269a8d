"""The Nemotron-3-Nano configuration and its cell, as new files only: the
family's counts against the issue's arithmetic, the readers it brings on a
run that has nothing for them to read, and the reference's two routing
margins."""

import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops
from perfbench.manifest import Manifest, config_problems, problems

M = Manifest()
NAME = "nemotron3-nano-30b-l14-ep2"
CELL = NAME + ".generate-reason"
SIZES = M.config(NAME)


def test_the_manifest_may_be_sent():
    assert problems(M) == [] and config_problems(M, NAME) == []
    assert SIZES["reduced_from"]["hybrid_override_pattern"].startswith(
        SIZES["hybrid_override_pattern"])         # the FIRST 14 layers


def test_counts_are_the_cut_s_arithmetic():
    # 6 Mamba-2 x 38.7 M + 2 attention x 23.4 M + 6 x (router 0.34 M + shared
    # 19.96 M + 3 expected held experts x 9.98 M) + the 65536-row head
    assert round(flops.matmul_params(SIZES, manifest=M) / 1e6, 3) == 756.597
    assert round(flops.total_params(SIZES, manifest=M) / 1e9, 3) == 4.585
    # two attention layers of 2 KV heads x 128: 2 KB a token in bf16
    assert flops.kv_bytes_per_token(SIZES, manifest=M) == 2048
    counts = flops.family_counts(SIZES, M)
    # six float32 states of 64 x 64 x 128, read and written: 1.61 GB at 64 rows
    assert counts.ssm_update_bytes(SIZES, 64) == 2 * 4 * 6 * 64 * 64 * 64 * 128
    assert flops.train_flops_per_token(SIZES, 2048, manifest=M) > \
        6 * flops.matmul_params(SIZES, manifest=M)


def test_the_program_s_tree_has_the_counted_parameters():
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config(SIZES, remat=False, dtype=jnp.bfloat16)
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM
    shapes = jax.eval_shape(NemotronHForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == flops.total_params(SIZES, manifest=M)
    assert cfg.recurrent_state_bytes(64) == 6 * 64 * (64 * 64 * 128 * 4
                                                      + 3 * 6144 * 2)


def test_every_seed_serves_one_draw_with_the_routed_experts_at_half_range():
    """`--seed` draws the prompts: the weights are one draw, so that the
    router's load, which sets the speed, is the same on every seed; and the
    damping is the adapter's, on the tree the program seeded, exact in bf16
    (a power of two)."""
    import numpy as np
    from deepspeed_tpu.models.nemotron_h import materialize_params
    adapter = M.module("configs", SIZES["adapter"])
    cfg = adapter.model_config({**SIZES, **SIZES["rehearsal"]},
                               dtype=jnp.bfloat16)
    assert not hasattr(cfg, "expert_init_std")    # no such option in the program
    _, one = adapter.materialize(cfg, 1, jnp.bfloat16)
    _, other = adapter.materialize(cfg, 2 ** 31 + 7, jnp.bfloat16)
    _, seeded = materialize_params(
        cfg, rng=jax.random.PRNGKey(adapter.WEIGHTS_SEED),
        param_dtype=jnp.bfloat16)
    damped = 0
    for (path, a), b, raw in zip(jax.tree_util.tree_leaves_with_path(one),
                                 jax.tree_util.tree_leaves(other),
                                 jax.tree_util.tree_leaves(seeded)):
        routed = jax.tree_util.keystr(path[-2:]) in (
            "['experts']['up']", "['experts']['down']")
        damped += routed
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        np.testing.assert_array_equal(
            np.asarray(a, np.float32),
            np.asarray(raw, np.float32) * (0.5 if routed else 1.0))
    assert damped == 2 * cfg.count("E")           # the shared expert is not


def ctx_without_anything():
    return types.SimpleNamespace(
        trace=None, trace_window=None, peaks=None, counters={}, sizes=SIZES,
        traffic=M.traffic("generate-reason"), manifest=M, chips=1)


@pytest.mark.parametrize("metric", [m["name"] for m in M.metrics_for(
    CELL, "per_layer") if m["workloads"] == [CELL]])
def test_a_new_metric_reads_nothing_where_there_is_nothing(metric, monkeypatch):
    """A parent commit has no such span, counter or gauge: the reader
    returns None and the line leaves the metric out; it never raises."""
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    set_hub(TelemetryHub(enabled=False))
    decl = M.metric(metric)
    assert M.reader(decl["reader"])(ctx_without_anything(),
                                    **decl.get("params", {})) is None


def test_the_roofline_share_is_bytes_over_bandwidth_over_time():
    roofline = M.module("readers", "roofline")
    ops = [["ssm_state_update", 0.0, 2e6], ["fusion", 2e6, 5e6],
           ["ssm_state_update", 7e6, 2e6]]            # ns: 4 ms in the kernel
    ctx = ctx_without_anything()
    ctx.trace = {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}
    ctx.trace_window, ctx.peaks = (0.0, 9e6), {"hbm_gbps": 819.0,
                                               "bf16_tflops": 197.0}
    ctx.counters = {"traced_decode_steps": 2, "out_tok_s": 3000.0}
    share = roofline.hbm_share(ctx, "^ssm_state_update", "traced_decode_steps",
                               "ssm_update_bytes")
    assert share == pytest.approx(100 * 2 * 1.610612736e9 / (819e9 * 4e-3))
    assert roofline.decode_mfu(ctx, "out_tok_s") == pytest.approx(
        100 * 3000 * 2 * flops.matmul_params(SIZES, manifest=M) / 197e12)


def test_both_routing_margins_of_the_reference():
    ref = M.module("configs", SIZES["reference"])
    logits = jnp.array([[3.0, 2.0, 0.5, 0.4, -1.0]])
    s = jax.nn.sigmoid(logits)
    readme, in_logits = ref.routing_margins(s, s, 3)
    gap = s[0, 2] - s[0, 3]
    assert readme[0] == pytest.approx(float(gap / s[0, 2]))
    # over the sigmoid's slope: the gap in the router's logits (0.1 here)
    assert in_logits[0] == pytest.approx(0.1, rel=0.02)
    assert in_logits[0] > 2 * readme[0]
