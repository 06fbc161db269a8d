"""Golden tests: Pallas flash attention vs XLA reference.

Mirrors the reference's kernel-test pattern (tests/unit/ops/transformer/
inference: CUDA op vs pure-torch reference at tolerance). On CPU the kernels
run in the Pallas interpreter.
"""

import os

os.environ.setdefault("DS_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas import flash_attention as flash_module
from deepspeed_tpu.ops.pallas.flash_attention import _interpret, flash_attention

# On real TPU hardware, fp32 MXU inputs round to bf16 by default, so the
# kernel and the XLA reference accumulate differently — widen tolerances
# there (interpret mode on CPU is exact fp32).
FWD_TOL = 2e-3 if _interpret() else 2e-2
BWD_TOL = 5e-3 if _interpret() else 1e-1


def _rand_qkv(b=2, sq=256, sk=256, h=4, hkv=None, d=64, dtype=jnp.float32, seed=0):
    hkv = hkv or h
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    return q, k, v


@pytest.fixture(params=["one_pass", "two_pass"])
def bwd_form(request, monkeypatch):
    """Both forms of the backward: ONE kernel with the whole query length's
    dq resident in VMEM (every shape here fits the budget), and the two-pass
    form that `_bwd` keeps for lengths past it (the budget set to 0)."""
    if request.param == "two_pass":
        monkeypatch.setattr(flash_module, "ONE_PASS_DQ_BYTES", 0)
    return request.param


def _grads(attn, q, k, v, **kw):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, **kw) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _bwd_calls(q, k, v, **kw):
    """`self_attn_flash_bwd` kernels in the traced backward."""
    text = str(jax.make_jaxpr(
        lambda *a: _grads(flash_attention, *a, **kw))(q, k, v))
    return text.count("name=self_attn_flash_bwd")


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=FWD_TOL, atol=FWD_TOL)


def test_forward_gqa():
    q, k, v = _rand_qkv(h=8, hkv=2)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=FWD_TOL, atol=FWD_TOL)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal, bwd_form):
    q, k, v = _rand_qkv(b=1, sq=128, sk=128, h=2, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=BWD_TOL, atol=BWD_TOL, err_msg=f"d{name}")


def test_backward_gqa(bwd_form):
    q, k, v = _rand_qkv(b=1, sq=128, sk=128, h=4, hkv=2, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=BWD_TOL, atol=BWD_TOL, err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk", [(64, 256), (128, 384)])
def test_causal_decode_shapes(sq, sk, bwd_form):
    """sq != sk causal (decode with a longer KV): bottom-right alignment,
    matching reference_attention's (sk - sq) offset."""
    q, k, v = _rand_qkv(sq=sq, sk=sk)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=FWD_TOL, atol=FWD_TOL)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=BWD_TOL, atol=BWD_TOL, err_msg=f"d{name}")


@pytest.mark.parametrize("causal,sq,sk,hkv,blk_q,blk_k", [
    (True, 384, 384, 1, 128, 128),    # triangular walk, n = 3
    (False, 256, 384, 2, 128, 128),   # rectangular, sq != sk
    (True, 128, 384, 2, 64, 128),     # rectangular causal, unequal blocks
    (True, 384, 256, 2, 128, 128),    # sk < sq: query rows with no key
])
def test_one_pass_is_two_pass_bit_for_bit(causal, sq, sk, hkv, blk_q, blk_k,
                                          monkeypatch):
    """The one-pass backward changes how often the score tile is computed,
    not what is summed in which order: at float32 its dq, dk and dv ARE the
    two kernels'. One `self_attn_flash_bwd` call where there were two."""
    q, k, v = _rand_qkv(b=2, sq=sq, sk=sk, h=2, hkv=hkv, d=64, seed=3)
    kw = dict(causal=causal, block_q=blk_q, block_k=blk_k)
    one = _grads(flash_attention, q, k, v, **kw)
    assert _bwd_calls(q, k, v, **kw) == 1
    monkeypatch.setattr(flash_module, "ONE_PASS_DQ_BYTES", 0)
    two = _grads(flash_attention, q, k, v, **kw)
    assert _bwd_calls(q, k, v, **kw) == 2
    for a, b, name in zip(one, two, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"d{name}")


def test_backward_over_three_blocks(bwd_form):
    """n = 4 blocks a side: the walk's first-step zeroing and last-step
    write of the resident dq with interior pairs between them, a query
    block's dq summed over up to four kv blocks; GQA 4 on 2."""
    q, k, v = _rand_qkv(b=2, sq=512, sk=512, h=4, hkv=2, d=64, seed=5)
    kw = dict(causal=True, block_q=128, block_k=128)
    g_flash = _grads(flash_attention, q, k, v, **kw)
    g_ref = _grads(reference_attention, q, k, v, causal=True)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=BWD_TOL, atol=BWD_TOL, err_msg=f"d{name}")


def test_the_budget_counts_the_padded_lanes(monkeypatch):
    """VMEM pads a (sq, 64) float32 buffer to 128 lanes: the budget is
    asked in those bytes, and the form follows it with no argument."""
    q, k, v = _rand_qkv(b=1, sq=256, sk=256, h=2, d=64)
    monkeypatch.setattr(flash_module, "ONE_PASS_DQ_BYTES", 256 * 128 * 4)
    assert _bwd_calls(q, k, v) == 1
    monkeypatch.setattr(flash_module, "ONE_PASS_DQ_BYTES", 256 * 128 * 4 - 1)
    assert _bwd_calls(q, k, v) == 2


def test_two_pass_says_so_once_a_shape(monkeypatch, tmp_path):
    """Past the budget the two-pass form runs: ONE warning a (sq, d) and a
    `flash_bwd_two_pass` event every trace, so a long-sequence user can see
    which form their step took; the one-pass form says nothing."""
    import json

    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    from deepspeed_tpu.utils.logging import WARNED_ONCE
    q, k, v = _rand_qkv(b=1, sq=128, sk=128, h=2, d=64)
    path = tmp_path / "events.jsonl"
    hub = set_hub(TelemetryHub(enabled=True, jsonl_path=str(path)))
    try:
        _bwd_calls(q, k, v)
        monkeypatch.setattr(flash_module, "ONE_PASS_DQ_BYTES", 0)
        WARNED_ONCE.discard(("flash_bwd_two_pass", 128, 64))
        _bwd_calls(q, k, v)
        _bwd_calls(q, k, v)
        assert ("flash_bwd_two_pass", 128, 64) in WARNED_ONCE
        hub.flush()
    finally:
        set_hub(TelemetryHub(enabled=False))
    events = [json.loads(line) for line in open(path)]
    said = [e for e in events if e["kind"] == "flash_bwd_two_pass"]
    assert [(e["sq"], e["d"]) for e in said] == [(128, 64)] * 2


def test_bf16_forward():
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
