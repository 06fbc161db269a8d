"""Every Pallas kernel an engine can select, compiled for a described TPU
v5e at Qwen2.5-3B shapes — no chip attached, no chip time.

Interpret mode accepts block specs the chip's compiler refuses (the int8
scale operands of `quantized_matmul` and of both int8-KV decode kernels
passed every interpret-mode test and could not lower at any real width).
The cases are `chip_smoke.kernel_cases`: what this file compiles is what
`chip_smoke.py` runs on the chip against the `jax.numpy` references.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import FULL, kernel_cases

CASES = kernel_cases(FULL)
# The attention kernels' names in the device trace's `XLA Ops` line: the HLO
# instruction of a Mosaic call is named by `pallas_call(name=...)`. The
# benchmark's kernel metrics search for these (`perfbench/metrics/`), and
# its older ones for their common prefix `self_attn`.
KERNEL_NAMES = {"flash_fwd_bwd": {"self_attn_flash_fwd", "self_attn_flash_bwd"},
                "flash_fwd": {"self_attn_flash_fwd"},
                "flash_band": {"self_attn_flash_fwd_band"},
                "decode_ring": {"self_attn_ring_decode"},
                "decode": {"self_attn_dense_decode"},
                "paged_decode": {"self_attn_paged_decode"},
                "paged_prefill": {"self_attn_paged_prefill"}}
# `self_attn_flash_bwd` Mosaic calls in a backward: ONE while the whole
# query length's dq stays in VMEM beside the dk/dv walk
# (`flash_attention.ONE_PASS_DQ_BYTES`), the two-pass form's two past it
FLASH_BWD_CALLS = {"flash_fwd_bwd_s2048": 1, "flash_fwd_bwd_s2048_d64": 1,
                   "flash_fwd_bwd_s32768": 2}
# other kernels the benchmark's metrics find by name
OTHER_NAMES = {"ssm_state_update": "ssm_state_update",
               "ssm_state_update_m1": "ssm_state_update_m1",
               "diff_attn_window_decode": "diff_attn_window_decode",
               "diff_attn_shared_decode": "diff_attn_shared_decode",
               "grouped_gemm_decode": "gmm",
               "grouped_gemm_decode_ling": "gmm",
               "grouped_gemm_decode_deepseek": "gmm",
               "grouped_gemm_decode_openpangu": "gmm",
               "held_rows": "held_combine",
               "held_rows_long": "held_combine",
               "held_combine": "held_combine",
               "kda_state_update": "kda_state_update",
               "gdn_state_update": "gdn_state_update",
               "delta_prefill_head": "delta_rule_prefill",
               "delta_prefill_channel": "delta_rule_prefill_channel",
               "mla_latent_decode": "mla_latent_decode",
               "mla_latent_decode_h128": "mla_latent_decode",
               "mla_dense_prefill": "mla_dense_prefill",
               "latent_write_dense": "latent_write_dense",
               "sparse_index_select": "sparse_index_select",
               "sparse_attn_decode": "sparse_attn_decode",
               "sparse_attn_prefill": "sparse_attn_prefill",
               "mla_sparse_decode": "mla_sparse_decode",
               "mla_sparse_decode_gathered": "mla_sparse_decode",
               "mla_sparse_prefill": "mla_sparse_prefill"}


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described (not attached) 2x2 v5e. The persistent
    compilation cache is off around these compiles: an executable for a
    described device is written but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Off the chip every kernel module answers `_interpret()` with True;
    steer them to the compiled path here, in the test."""
    from deepspeed_tpu.ops.pallas import (
        block_sparse_attention, decode_attention, delta_rule, diff_attention,
        flash_attention, grouped_gemm, held_combine, kda, mla, mla_sparse,
        paged_attention, quantized_matmul, sparse_select, ssm)
    monkeypatch.delenv("DS_TPU_PALLAS_INTERPRET", raising=False)
    for mod in (block_sparse_attention, decode_attention, delta_rule,
                diff_attention, flash_attention, grouped_gemm, held_combine,
                kda, mla, mla_sparse, paged_attention, quantized_matmul,
                sparse_select, ssm):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(case, v5e_chip, compiled_kernels):
    shapes = jax.eval_shape(case.make, jax.random.PRNGKey(0))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e_chip),
        shapes)
    text = jax.jit(case.fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    want = next((names for prefix, names in KERNEL_NAMES.items()
                 if case.name.startswith(prefix + "_")), None)
    if want:
        # bare here; `transpose_jvp_self_attn_flash_bwd__` where grad wraps
        # the kernel directly (inside a model's scopes it is bare again)
        got = [re.search(r"self_attn(_[a-z]+)+", m).group(0)
               for m in re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                                   text)]
        assert set(got) == want
        if "self_attn_flash_bwd" in want:
            assert got.count("self_attn_flash_bwd") == FLASH_BWD_CALLS[
                case.name]
    other = OTHER_NAMES.get(case.name)
    if other:
        # `ssm_update_ms.gen` and `moe_gmm_ms.gen` search for these
        calls = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
        assert any(re.match(rf"{other}(\.\d+)*$", c) for c in calls), calls


@pytest.mark.parametrize("sliding", [True, False], ids=["window", "full"])
def test_no_transpose_stands_round_a_prefill_attention(
        sliding, v5e_chip, compiled_kernels, monkeypatch):
    """One Trinity attention layer as generate-agent-8k's prefill calls it
    (`afmoe.GatedAttention`: two rows of 8,192, 32 heads on 4 of 128; four
    projections, float32 head norms, rotary in a window layer, the flash
    kernel, the float32 gate, `o_proj`), compiled for the described chip.
    The kernel takes (B, S, H x D) operands, so its result feeds the gate's
    fusion as it lies: no copy of q's size stands after it, and before a
    window layer's none in float32 (PR 63; until then two float32 copies
    of 268 MB a call, the result's and q's). What is left before it, XLA's
    re-layout of q after the rotary (window, bf16) or at the head norm
    (full, float32), is another mechanism's to take."""
    from deepspeed_tpu.models.afmoe import AfmoeConfig, GatedAttention
    from deepspeed_tpu.ops import attention as dispatch
    monkeypatch.setattr(dispatch, "_use_pallas", lambda: True)
    cfg = AfmoeConfig()
    rows, length = 2, 8192
    layer = GatedAttention(cfg, sliding)
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=v5e_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128, cfg.hidden_size), cfg.dtype))))
    x = jax.ShapeDtypeStruct((rows, length, cfg.hidden_size), cfg.dtype,
                             sharding=v5e_chip)
    text = jax.jit(lambda p, x: layer.apply(p, x)[0]).lower(
        params, x).compile().as_text()
    entry = text[text.index("\nENTRY "):]  # in the schedule's order
    kernel = re.search(r"\n[^\n]*custom_call_target=\"tpu_custom_call\"",
                       entry)
    assert kernel and ("flash_fwd_band" in kernel.group(0)) == sliding
    q_size = rows * length * cfg.num_attention_heads * cfg.head_dim

    def q_sized_copies(hlo):
        found = re.findall(r"= (\w+)\[([\d,]+)\]\S* copy\(", hlo)
        return [dtype for dtype, dims in found
                if math.prod(map(int, dims.split(","))) == q_size]

    assert q_sized_copies(entry[kernel.end():]) == []
    if sliding:
        assert "f32" not in q_sized_copies(entry[:kernel.start()])


def test_a_serving_chunks_delta_rule_is_one_kernel(v5e_chip, compiled_kernels,
                                                   monkeypatch):
    """One Gated DeltaNet layer as generate-longctx-linear's prefill calls
    it (`qwen3_next.GatedDeltaNet` with the stacked state: one row's chunk of
    2,048 positions, 16 key heads serving 32 value heads of 128), compiled
    for the described chip: the delta rule is ONE `delta_rule_prefill` call,
    no `while` (the plain form's scan over blocks) is left in the program,
    and no float32 copy of an operand's size (q, k repeated and v as blocks,
    `o` back: the plain form's `moveaxis`) stands on either side of it."""
    from deepspeed_tpu.inference.kv_cache import RecurrentState
    from deepspeed_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig
    from deepspeed_tpu.ops import attention as dispatch
    monkeypatch.setattr(dispatch, "_use_pallas", lambda: True)
    cfg = Qwen3NextConfig()
    rows, length = 8, 2048
    layer = GatedDeltaNet(cfg)
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=v5e_chip)
    nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    params, state = jax.tree_util.tree_map(on_chip, jax.eval_shape(lambda: (
        layer.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 128, cfg.hidden_size), cfg.dtype)),
        RecurrentState.create(3, rows, (nv, dk, dv),
                              cfg.linear_conv_kernel_dim, cfg.conv_dim,
                              cfg.dtype))))
    x = jax.ShapeDtypeStruct((1, length, cfg.hidden_size), cfg.dtype,
                             sharding=v5e_chip)
    text = jax.jit(lambda p, x, state: layer.apply(
        p, x, state, 1, 3, mutable=["counters"])[0]).lower(
            params, x, state).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(calls) == 1 and calls[0].startswith("delta_rule_prefill")
    assert " while(" not in text
    sizes = {length * nv * dv, length * cfg.linear_num_key_heads * dk}
    copies = [dims for dtype, dims in re.findall(
        r"= (\w+)\[([\d,]+)\]\S* copy\(", text[text.index("\nENTRY "):])
        if dtype == "f32" and math.prod(map(int, dims.split(","))) in sizes]
    assert copies == []
