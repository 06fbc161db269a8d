"""Nemotron-H (Mamba-2 / experts / attention by a pattern string) against the
benchmark's plain float32 reference, at a small size on the CPU.

Tolerances. Program and reference both compute in float32 here, in another
order (the chunked scan against the positional recurrence, sorted expert rows
against a dense sum): logits of magnitude 0.5 agree to about 1e-6, and
`TOL` = 2e-5 relative to the largest logit leaves room for that and none for
a dropped term: each mutation below moves the logits by 1e-3 or more, fifty
times the tolerance, and has to.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                             materialize_params, ssd_chunked)
from perfbench.manifest import Manifest

TOL = 2e-5
REF = Manifest().module("configs", "nemotron_h_reference")
CFG = NemotronHConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=6,
    hybrid_override_pattern="ME*MEE", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=4,
    router_experts=8, expert_offset=2, num_experts_per_tok=3,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    dtype=jnp.float32, dispatch_impl="gmm")
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if f.name != "dtype"}


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.fixture(scope="module")
def seeded():
    model, params = materialize_params(CFG, jax.random.PRNGKey(3))
    # random init barely uses the recurrence (its output is a hundredth of
    # the skip term's): slow the decay and raise dt, so that the state
    # carries hundreds of positions and a fault in it shows in the logits
    for i, kind in enumerate(CFG.hybrid_override_pattern):
        if kind == "M":
            layer = params["layers"][f"layer_{i}"]
            layer["A_log"] = jnp.full_like(layer["A_log"], -4.0)
            layer["dt_bias"] = jnp.full_like(layer["dt_bias"], 1.0)
            layer["D"] = jnp.zeros_like(layer["D"])
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 29), 0, CFG.vocab_size)
    return model, params, ids


def reference_logits(params, ids, sizes=SIZES):
    h = REF.hidden_states(params, ids, sizes)
    with jax.default_matmul_precision("highest"):
        return h @ params["lm_head"]


def served(model, params, ids, prompt, state_bits=None):
    """Logits of every position: a prefill of `prompt` positions, then one
    decode step a position through the model's own cache."""
    cache = model.make_cache(ids.shape[0], 64, dtype=jnp.float32)
    out, cache = model.apply({"params": params}, ids[:, :prompt], cache=cache)
    outs = [out]
    for t in range(prompt, ids.shape[1]):
        if state_bits is not None:   # what a lower-precision state would keep
            cache = cache.replace(state=cache.state.replace(
                ssm=jax.lax.reduce_precision(cache.state.ssm, 8, state_bits)))
        out, cache = model.apply({"params": params}, ids[:, t:t + 1],
                                 cache=cache)
        outs.append(out)
    assert int(cache.index[0]) == ids.shape[1]
    return jnp.concatenate(outs, axis=1)


def test_plain_forward_matches_the_reference(seeded):
    model, params, ids = seeded
    assert rel(model.apply({"params": params}, ids),
               reference_logits(params, ids)) < TOL


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_prefill_then_eight_decode_steps_match_the_full_forward(seeded, impl):
    """Logits, not tokens: a 21-token prefill (no multiple of the block of
    8), then 8 steps on the stored state, convolution tail and K/V."""
    _, params, ids = seeded
    model = nemotron_h.NemotronHForCausalLM(
        dataclasses.replace(CFG, dispatch_impl=impl))
    assert rel(served(model, params, ids, 21),
               reference_logits(params, ids)) < TOL


def test_a_prefill_walked_a_few_rows_at_a_time_is_the_same(seeded, monkeypatch):
    model, params, ids = seeded
    whole = served(model, params, ids, 21)
    monkeypatch.setattr(nemotron_h, "PREFILL_TOKENS", 2 * 21)   # 2 rows of 4
    assert rel(served(model, params, ids, 21), whole) < 1e-6
    # and a prefill continued from a cache that already holds 8 positions
    cache = model.make_cache(4, 64, dtype=jnp.float32)
    _, cache = model.apply({"params": params}, ids[:, :8], cache=cache)
    out, _ = model.apply({"params": params}, ids[:, 8:21], cache=cache)
    assert rel(out, whole[:, 8:21]) < TOL


def test_chunked_scan_matches_the_positional_recurrence():
    """`ssd_chunked` (blocks of 8, state carried between them, from a state
    that is not zero) against one `lax.scan` over positions, at a length
    that is no multiple of the block."""
    b, s, h, p, g, n = 2, 21, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm, cm = (jax.random.normal(k, (b, s, g, n)) for k in ks[3:5])
    h0 = jax.random.normal(ks[5], (b, h, p, n))

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        b_t, c_t = (jnp.repeat(v, h // g, axis=1) for v in (b_t, c_t))
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)
    last, want = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    y, state = ssd_chunked(x, dt, a, bm, cm, h0, 8)
    assert rel(y, jnp.moveaxis(want, 0, 1)) < 1e-5 and rel(state, last) < 1e-5


def _without_bias(params):
    out = jax.tree_util.tree_map(lambda t: t, params)
    for name, layer in out["layers"].items():
        if "gate" in layer:
            layer["gate"] = {**layer["gate"],
                             "bias": jnp.zeros_like(layer["gate"]["bias"])}
    return out


def test_a_program_that_drops_a_term_fails(seeded, monkeypatch):
    """Each by at least 50 x `TOL`, against the reference as it stands."""
    model, params, ids = seeded
    want = reference_logits(params, ids)
    # the selection bias (the program runs with a zero one)
    assert rel(served(model, _without_bias(params), ids, 21), want) > 50 * TOL
    # the routed scaling factor of 2.5
    flat = nemotron_h.NemotronHForCausalLM(
        dataclasses.replace(CFG, routed_scaling_factor=1.0))
    assert rel(served(flat, params, ids, 21), want) > 50 * TOL
    # rotary embedding in attention where the family applies none
    rotary = nemotron_h.NemotronHForCausalLM(
        dataclasses.replace(CFG, attention_rotary=True))
    assert rel(served(rotary, params, ids, 21), want) > 50 * TOL
    assert rel(served(rotary, params, ids, 21), reference_logits(
        params, ids, {**SIZES, "attention_rotary": True})) < TOL
    # the norm before the gate (the reference computed in the other order)

    def norm_then_gate(y, z, w, groups, eps):
        yg = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        return yg.reshape(y.shape) * w * jax.nn.silu(z)
    monkeypatch.setattr(REF, "_gated_group_norm", norm_then_gate)
    assert rel(served(model, params, ids, 21),
               reference_logits(params, ids)) > 50 * TOL


def test_a_bfloat16_state_fails(seeded):
    """The state kept to bf16's 8 bits between steps (float32 arithmetic
    inside a step, as a kernel would do it) over a 5-token prefill and 24
    steps: over `TOL` by a factor of 50. With 16 bits it passes."""
    model, params, ids = seeded
    want = reference_logits(params, ids)
    assert rel(served(model, params, ids, 5, state_bits=7), want) > 50 * TOL
    assert rel(served(model, params, ids, 5, state_bits=16), want) < TOL


def test_the_cache_holds_each_kind_of_layer_its_own():
    model = nemotron_h.NemotronHForCausalLM(CFG)
    cache = model.make_cache(3, 128, dtype=jnp.bfloat16)
    assert cache.kv.k.stack.shape == (1, 3, 2, 128, 16)  # ONE attention layer
    assert cache.state.ssm.shape == (2, 3, 4, 8, 16)   # two Mamba layers
    assert cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (2, 3, 3, 4 * 8 + 2 * 2 * 16)
    assert CFG.num_kv_layers == 1
    assert CFG.recurrent_state_bytes(3, jnp.bfloat16) == \
        cache.state.ssm.nbytes + cache.state.conv.nbytes


def test_the_pattern_is_checked():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(CFG, hybrid_override_pattern="ME-MEE")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(CFG, num_hidden_layers=5)
