"""Mesh-partitionable serving kernels (ops/pallas/sharded.py + the
sharded_* wrappers in grouped_gemm.py / quantized_matmul.py): parity vs
the single-device kernels on the virtual 8-device CPU mesh (Pallas
interpret mode), the supported-matrix predicates, and the no-silent-
fallback contract (kernel_fallback WARN + telemetry event)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import sharded
from deepspeed_tpu.ops.pallas.sharded import (
    decode_heads_shardable, kernel_fallback, mesh_fingerprint,
    nontrivial_axes, serving_mesh, sharded_decode_attention,
    sharded_paged_decode_attention, sharded_paged_prefill_attention)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import MeshTopology


def _tp_mesh(tp=2):
    groups.reset_topology()
    topo = groups.initialize(MeshTopology(tp=tp, devices=jax.devices()[:tp]))
    return topo.mesh


def _ep_mesh(ep=4):
    groups.reset_topology()
    topo = groups.initialize(MeshTopology(ep=ep, devices=jax.devices()[:ep]))
    return topo.mesh


def _mixed_mesh():
    # ep=4 over all 8 devices → nontrivial {expert: 4, data: 2}
    groups.reset_topology()
    topo = groups.initialize(MeshTopology(ep=4, devices=jax.devices()))
    return topo.mesh


# --------------------------------------------------- support predicates

def test_nontrivial_axes_and_fingerprint():
    assert nontrivial_axes(_tp_mesh()) == {"model": 2}
    assert mesh_fingerprint(_tp_mesh()) == "model2"
    assert nontrivial_axes(_mixed_mesh()) == {"expert": 4, "data": 2}
    # canonical MESH_AXES order, not alphabetical-by-accident
    assert mesh_fingerprint(_mixed_mesh()) == "data2_expert4"
    groups.reset_topology()
    topo = groups.initialize(MeshTopology(devices=jax.devices()[:1]))
    assert nontrivial_axes(topo.mesh) == {}
    # single-device fingerprint is EMPTY — existing program names must not move
    assert mesh_fingerprint(topo.mesh) == ""


def test_serving_mesh_gating(monkeypatch):
    groups.reset_topology()
    assert serving_mesh("model") == (None, 1)  # no topology
    mesh = _tp_mesh()
    got, tp = serving_mesh("model")
    assert got is mesh and tp == 2
    assert serving_mesh("expert") == (None, 1)  # wrong axis
    _mixed_mesh()
    assert serving_mesh("expert") == (None, 1)  # second nontrivial axis
    _tp_mesh()
    monkeypatch.setenv("DS_TPU_DISABLE_SHARDED_KERNELS", "1")
    assert serving_mesh("model") == (None, 1)  # kill switch


def test_decode_heads_shardable():
    assert decode_heads_shardable(8, 4, 2)
    assert not decode_heads_shardable(8, 4, 1)   # single device: bare kernel
    assert not decode_heads_shardable(8, 3, 2)   # KV heads don't divide
    assert not decode_heads_shardable(7, 7, 2)   # heads don't divide


def test_tp_shard_flavor():
    from deepspeed_tpu.ops.pallas.quantized_matmul import tp_shard_flavor
    # per-row groups (e = 64 <= n): both flavors legal; prefer honored
    assert tp_shard_flavor(256, 256, 1024, 2, prefer="n") == "n"
    assert tp_shard_flavor(256, 256, 1024, 2, prefer="k") == "k"
    # block spans rows (e = 512 > n = 64): only the K-sharded flavor
    assert tp_shard_flavor(256, 64, 32, 2, prefer="n") == "k"
    # nothing divides → None (callers fall back, loudly)
    assert tp_shard_flavor(256, 256, 1024, 3) is None


def test_kernel_fallback_warns_once_emits_always(tmp_path):
    import json
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    sharded._WARNED.clear()
    hub = set_hub(TelemetryHub(enabled=True,
                               jsonl_path=str(tmp_path / "t.jsonl")))
    try:
        kernel_fallback("demo_kernel", "reason A")
        kernel_fallback("demo_kernel", "reason A")
        hub.flush()
    finally:
        set_hub(TelemetryHub(enabled=False))
    events = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    falls = [e for e in events if e["kind"] == "kernel_fallback"]
    assert len(falls) == 2
    assert falls[0]["kernel"] == "demo_kernel"
    assert falls[0]["reason"] == "reason A"
    assert ("demo_kernel", "reason A") in sharded._WARNED


# -------------------------------------------------------- kernel parity

def _close(a, b, tol=1e-5):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = max(np.abs(b).max(), 1e-6)
    np.testing.assert_array_less(np.abs(a - b).max(), tol * scale)


def test_sharded_quantized_matmul_parity_both_flavors():
    from deepspeed_tpu.ops.pallas.quantized_matmul import (
        quantized_matmul, sharded_quantized_matmul)
    from deepspeed_tpu.ops.quantization import quantize_int8_blockwise
    mesh = _tp_mesh()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 256)), jnp.float32)
    q, sc = quantize_int8_blockwise(
        jnp.asarray(rng.standard_normal((256, 256)), jnp.float32), block=64)
    ref = quantized_matmul(x, q, sc)
    for flavor in ("n", "k"):
        out = sharded_quantized_matmul(x, q, sc, mesh, flavor=flavor)
        assert out.shape == ref.shape
        _close(out, ref)


def test_sharded_quantized_matmul_block_spans_rows():
    # (256, 64) weight with 512-wide scale blocks: per-row grouping is
    # impossible, only the K-sharded flavor applies — auto must pick it
    from deepspeed_tpu.ops.pallas.quantized_matmul import (
        quantized_matmul, sharded_quantized_matmul)
    from deepspeed_tpu.ops.quantization import quantize_int8_blockwise
    mesh = _tp_mesh()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    q, sc = quantize_int8_blockwise(
        jnp.asarray(rng.standard_normal((256, 64)), jnp.float32), block=512)
    _close(sharded_quantized_matmul(x, q, sc, mesh),
           quantized_matmul(x, q, sc))


def test_sharded_grouped_gemm_parity():
    from deepspeed_tpu.ops.pallas.grouped_gemm import (
        grouped_gemm, sharded_grouped_gemm)
    mesh = _ep_mesh()
    rng = np.random.default_rng(2)
    # 8 experts over ep=4, irregular sizes including an EMPTY expert
    sizes = jnp.asarray([7, 0, 13, 5, 9, 11, 3, 16], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((8, 128, 128)), jnp.float32)
    _close(sharded_grouped_gemm(lhs, rhs, sizes, mesh),
           grouped_gemm(lhs, rhs, sizes))


def test_sharded_grouped_gemm_rejects_indivisible_experts():
    from deepspeed_tpu.ops.pallas.grouped_gemm import sharded_grouped_gemm
    mesh = _ep_mesh()
    rng = np.random.default_rng(3)
    sizes = jnp.asarray([4, 4, 4, 4, 4, 4], jnp.int32)  # 6 experts, ep=4
    lhs = jnp.asarray(rng.standard_normal((24, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((6, 128, 128)), jnp.float32)
    with pytest.raises(ValueError):
        sharded_grouped_gemm(lhs, rhs, sizes, mesh)


@pytest.mark.slow
def test_sharded_decode_attention_parity():
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    mesh = _tp_mesh()
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 1, 8, 64)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((2, 128, 4, 64)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((2, 128, 4, 64)), jnp.float32)
    lengths = jnp.asarray([65, 128], jnp.int32)
    _close(sharded_decode_attention(q, kc, vc, lengths, mesh, block_k=128),
           decode_attention(q, kc, vc, lengths, block_k=128), tol=1e-4)


@pytest.mark.slow
def test_sharded_paged_decode_parity_plain_and_staged():
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
    mesh = _tp_mesh()
    rng = np.random.default_rng(5)
    b, hkv, nb, bs, d, h, t = 2, 4, 8, 16, 64, 8, 4
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, nb, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: b * t].reshape(b, t), jnp.int32)
    lengths = jnp.asarray([33, 64], jnp.int32)
    _close(sharded_paged_decode_attention(q, kp, vp, tables, lengths, mesh),
           paged_decode_attention(q, kp, vp, tables, lengths), tol=1e-4)
    kn = jnp.asarray(rng.standard_normal((b, hkv, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, hkv, d)), jnp.float32)
    _close(sharded_paged_decode_attention(q, kp, vp, tables, lengths, mesh,
                                          k_new=kn, v_new=vn),
           paged_decode_attention(q, kp, vp, tables, lengths,
                                  k_new=kn, v_new=vn), tol=1e-4)


@pytest.mark.slow
def test_sharded_paged_prefill_parity():
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_prefill_attention)
    mesh = _tp_mesh()
    rng = np.random.default_rng(6)
    b, hkv, nb, bs, d, h, t, s = 2, 4, 8, 16, 64, 8, 4, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, nb, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: b * t].reshape(b, t), jnp.int32)
    starts = jnp.asarray([17, 40], jnp.int32)
    _close(sharded_paged_prefill_attention(q, kp, vp, tables, starts, mesh),
           paged_prefill_attention(q, kp, vp, tables, starts), tol=1e-4)


@pytest.mark.parametrize("kind", ["decode", "prefill", "write",
                                  "write_int8"])
def test_sharded_paged_kernels_on_the_stacked_pool(kind):
    """The operand the v2 programs hand over on a tp mesh: the whole
    stacked pool, KV heads over 'model', and the layer to use replicated."""
    from deepspeed_tpu.inference.kv_cache import quantize_kv_tokens
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_kv_write, paged_prefill_attention)
    from deepspeed_tpu.ops.pallas.sharded import sharded_paged_kv_write
    mesh = _tp_mesh()
    rng = np.random.default_rng(8)
    nl, b, hkv, nb, bs, d, h, t, s = 3, 2, 4, 8, 16, 64, 8, 4, 8
    layer = jnp.int32(1)
    kp = jnp.asarray(rng.standard_normal((nl, hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nl, hkv, nb, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: b * t].reshape(b, t), jnp.int32)
    cursor = jnp.asarray([17, 40], jnp.int32)
    if kind == "decode":
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        got = sharded_paged_decode_attention(q, kp, vp, tables, cursor, mesh,
                                             layer=layer)
        want = paged_decode_attention(q, kp[1], vp[1], tables, cursor)
    elif kind == "prefill":
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        got = sharded_paged_prefill_attention(q, kp, vp, tables, cursor, mesh,
                                              layer=layer)
        want = paged_prefill_attention(q, kp[1], vp[1], tables, cursor)
    else:
        new = jnp.asarray(rng.standard_normal((2, 1, b, s, hkv, d)),
                          jnp.float32)
        args, extra = (kp, vp, new[0], new[1]), {}
        if kind == "write_int8":
            (kq, ks), (vq, vs) = quantize_kv_tokens(kp), quantize_kv_tokens(vp)
            (kn, kns), (vn, vns) = (quantize_kv_tokens(new[0]),
                                    quantize_kv_tokens(new[1]))
            args = (kq, vq, kn, vn)
            extra = dict(k_scales=ks, v_scales=vs, k_new_scales=kns,
                         v_new_scales=vns)
        got = sharded_paged_kv_write(*args, tables, cursor, layer, mesh,
                                     **extra)
        want = paged_kv_write(*args, tables, cursor, layer, **extra)
        got, want = [x for x in got if x is not None], \
            [x for x in want if x is not None]
        assert len(got) == (4 if extra else 2)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert not np.array_equal(np.asarray(got[0]), np.asarray(args[0]))
        return
    _close(got, want, tol=1e-4)


@pytest.mark.parametrize("kind", ["decode", "decode_staged", "prefill"])
def test_sharded_paged_kernels_skip_a_parked_row(kind):
    """The wrappers hand lengths / starts through as they come, so a row
    parked at capacity (`T * BS`; docs/kv_cache.md) is skipped on every
    shard: with pool block 0 NaN, which an unowned table entry (-1) reads,
    the parked row comes back finite and the live row as it does alone."""
    mesh = _tp_mesh()
    rng = np.random.default_rng(31)
    nl, hkv, nb, bs, d, h, t, s = 2, 4, 8, 16, 64, 8, 3, 8
    layer, cap = jnp.int32(1), t * bs
    kp = jnp.asarray(rng.standard_normal((nl, hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nl, hkv, nb, bs, d)), jnp.float32)
    kp, vp = kp.at[:, :, 0].set(jnp.nan), vp.at[:, :, 0].set(jnp.nan)
    tables = jnp.asarray([[-1] * t, list(1 + rng.permutation(nb - 1)[:t])],
                         jnp.int32)
    if kind == "prefill":
        q = jnp.asarray(rng.standard_normal((2, s, h, d)), jnp.float32)
        starts = jnp.asarray([cap, cap - s], jnp.int32)

        def run(rows):
            return sharded_paged_prefill_attention(
                q[rows], kp, vp, tables[rows], starts[rows], mesh,
                layer=layer)
    else:
        q = jnp.asarray(rng.standard_normal((2, 1, h, d)), jnp.float32)
        lengths = jnp.asarray([cap + 1, cap], jnp.int32)   # cursor + 1
        new = jnp.asarray(rng.standard_normal((2, 2, hkv, d)), jnp.float32)

        def run(rows):
            kw = dict(k_new=new[0, rows], v_new=new[1, rows]) \
                if kind == "decode_staged" else {}
            return sharded_paged_decode_attention(
                q[rows], kp, vp, tables[rows], lengths[rows], mesh,
                layer=layer, **kw)
    got = np.asarray(run(np.arange(2)))
    np.testing.assert_array_equal(got[1:], np.asarray(run(np.arange(1, 2))))
    assert np.isfinite(got).all() and np.abs(got[1]).min() > 0
    if kind != "decode_staged":
        np.testing.assert_array_equal(got[0], 0.0)


# ------------------------------------------- cached_attention dispatch

def _prefix_mask(index, m, s=1):
    pos = index[:, None] + jnp.arange(s)[None, :]
    return jnp.arange(m)[None, None, :] <= pos[:, :, None]


@pytest.mark.slow
def test_cached_attention_tp_mesh_routes_sharded(monkeypatch):
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops.attention import cached_attention, \
        reference_attention
    _tp_mesh()
    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 1, 8, 64)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((2, 128, 4, 64)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((2, 128, 4, 64)), jnp.float32)
    index = jnp.asarray([64, 127], jnp.int32)
    mask = _prefix_mask(index, 128)
    out = cached_attention(q, kc, vc, index, mask, impl="decode_pallas")
    ref = reference_attention(q, kc, vc, causal=False, segment_mask=mask)
    _close(out, ref, tol=1e-3)


def test_cached_attention_unsupported_mesh_falls_back(monkeypatch):
    # forced decode_pallas on a mixed mesh: NO raise, XLA path + fallback
    # event — a bare pallas_call would make GSPMD gather the whole cache
    import json
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops.attention import cached_attention, \
        reference_attention
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.hub import set_hub
    _mixed_mesh()
    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    sharded._WARNED.clear()
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((2, 1, 8, 32)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((2, 16, 4, 32)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((2, 16, 4, 32)), jnp.float32)
    index = jnp.asarray([4, 15], jnp.int32)
    mask = _prefix_mask(index, 16)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        hub = set_hub(TelemetryHub(enabled=True,
                                   jsonl_path=os.path.join(td, "t.jsonl")))
        try:
            out = cached_attention(q, kc, vc, index, mask,
                                   impl="decode_pallas")
            hub.flush()
            events = [json.loads(l)
                      for l in open(os.path.join(td, "t.jsonl"))]
        finally:
            set_hub(TelemetryHub(enabled=False))
    falls = [e for e in events if e["kind"] == "kernel_fallback"]
    assert falls and falls[0]["kernel"] == "decode_attention"
    _close(out, reference_attention(q, kc, vc, causal=False,
                                    segment_mask=mask))


# ------------------------------------------- training flash attention
#
# The chip's compiler refuses a bare Mosaic call in a partitioned program,
# so on a multi-device training mesh `attention()` must wrap the flash
# kernel in a shard_map (batch over the data axes, heads over 'model').


def _train_mesh(dp, tp):
    groups.reset_topology()
    topo = groups.initialize(
        MeshTopology(dp=dp, tp=tp, devices=jax.devices()[:dp * tp]))
    return topo.mesh


def _flash_inputs(b=4, s=128, h=4, hkv=2, d=32, seed=11):
    rng = np.random.default_rng(seed)
    mk = lambda hh: jnp.asarray(rng.standard_normal((b, s, hh, d)),
                                jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


@pytest.mark.parametrize("dp,tp,spec", [
    (1, 1, None),                                      # bare kernel
    (2, 2, ("data", None, "model", None)),
    (4, 1, ("data", None, None, None)),
    (1, 2, (None, None, "model", None)),
])
def test_flash_shard_specs(dp, tp, spec):
    from deepspeed_tpu.ops.pallas.sharded import flash_shard_specs
    mesh = _train_mesh(dp, tp)
    got_mesh, got = flash_shard_specs(4, 4, 2)
    if spec is None:
        assert (got_mesh, got) == (None, None)
    else:
        assert got_mesh is mesh
        assert got == jax.sharding.PartitionSpec(*spec)


def test_attention_rides_sharded_flash_on_train_mesh(monkeypatch):
    """Forward and gradients through `attention(impl='auto')` on a dp2 x
    tp2 mesh match the XLA reference, and the kernel sits in a shard_map."""
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops.attention import attention, reference_attention
    mesh = _train_mesh(2, 2)
    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _flash_inputs()
    loss = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v, causal=True) ** 2))
    with mesh:
        jaxpr = jax.make_jaxpr(attention)(q, k, v)
        out = jax.jit(attention)(q, k, v)
        grads = jax.jit(jax.grad(loss(attention), argnums=(0, 1, 2)))(q, k, v)
    assert "shard_map" in str(jaxpr)
    _close(out, reference_attention(q, k, v, causal=True), tol=2e-3)
    ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, ref):
        _close(g, r, tol=5e-3)


def test_attention_flash_fallback_is_announced(monkeypatch):
    # 3 KV heads over model=2: no head sharding — XLA path, said out loud
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops.attention import attention, reference_attention
    _train_mesh(2, 2)
    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    sharded._WARNED.clear()
    q, k, v = _flash_inputs(h=6, hkv=3)
    out = attention(q, k, v)
    assert any(isinstance(key, tuple) and key[0] == "flash_attention"
               for key in sharded._WARNED)
    _close(out, reference_attention(q, k, v, causal=True))


# --------------------------------------------------------- MoE EP route

def test_gmm_mesh_predicate():
    from deepspeed_tpu.moe.layer import _gmm_mesh
    mesh = _ep_mesh()
    got, ep = _gmm_mesh(8)
    assert got is mesh and ep == 4
    assert _gmm_mesh(6) == (None, 0)       # experts don't divide
    _mixed_mesh()
    assert _gmm_mesh(8) == (None, 0)       # second nontrivial axis
    groups.reset_topology()
    groups.initialize(MeshTopology(devices=jax.devices()[:1]))
    assert _gmm_mesh(8) == (None, 1)       # trivial: bare single-shard gmm


@pytest.mark.slow
def test_experts_grouped_path_ep_mesh_parity():
    from deepspeed_tpu.moe.layer import Experts
    rng = np.random.default_rng(9)
    sizes = jnp.asarray([7, 0, 13, 5, 9, 11, 3, 16], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    exp = Experts(8, 32, 64, jnp.float32)
    variables = exp.init(jax.random.PRNGKey(0), rows, sizes)
    groups.reset_topology()
    groups.initialize(MeshTopology(devices=jax.devices()[:1]))
    ref = exp.apply(variables, rows, sizes)
    _ep_mesh()
    out = exp.apply(variables, rows, sizes)
    _close(out, ref, tol=1e-4)
