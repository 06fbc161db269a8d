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
               "held_rows": "held_combine",
               "held_rows_long": "held_combine",
               "held_combine": "held_combine",
               "kda_state_update": "kda_state_update",
               "gdn_state_update": "gdn_state_update",
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
        block_sparse_attention, decode_attention, diff_attention,
        flash_attention, grouped_gemm, held_combine, kda, mla, mla_sparse,
        paged_attention, quantized_matmul, sparse_select, ssm)
    monkeypatch.delenv("DS_TPU_PALLAS_INTERPRET", raising=False)
    for mod in (block_sparse_attention, decode_attention, diff_attention,
                flash_attention, grouped_gemm, held_combine, kda, mla,
                mla_sparse, paged_attention, quantized_matmul, sparse_select,
                ssm):
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
