"""Nemotron-H (Mamba-2 / experts / attention by a pattern string) against the
benchmark's plain float32 reference, at a small size on the CPU.

Tolerances. Program and reference both compute in float32 here, in another
order (the chunked scan against the positional recurrence, sorted expert rows
against a dense sum): logits of magnitude 0.5 agree to about 1e-6, and
`TOL` = 2e-5 relative to the largest logit leaves room for that and none for
a dropped term: each mutation below moves the logits by 1e-3 or more, fifty
times the tolerance, and has to.

The plain forward and the prefill-then-decode walk are the questions all
three hybrid families are asked: their bodies are `hybrid_families.py`'s.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.nemotron_h import ssd_chunked
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (NEMOTRON_CFG as CFG,
                                               NEMOTRON_TOL as TOL,
                                               compile_apply, family, rel,
                                               walk, walked)


@pytest.fixture(scope="module")
def seeded():
    return family("nemotron_h")


def served(fam, prompt, model=None, params=None, **kw):
    """Logits of every position: a prefill of `prompt` positions, then one
    decode step a position through the model's own cache; of the family as
    it stands, or of another `model` or other `params` on its prompts."""
    return walk(model or fam.model, fam.params if params is None else params,
                fam.ids, prompt, fam.cache_len, **kw)[0]


def test_plain_forward_matches_the_reference():
    hybrid_families.the_plain_forward_is_the_reference_s("nemotron_h")


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_prefill_then_eight_decode_steps_match_the_full_forward(impl):
    """Logits, not tokens: a 21-token prefill (no multiple of the block of
    8), then 8 steps on the stored state, convolution tail and K/V."""
    hybrid_families.prefill_then_decode_is_the_reference_s("nemotron_h", 21,
                                                           impl)


def test_a_prefill_walked_a_few_rows_at_a_time_is_the_same(seeded,
                                                           monkeypatch):
    fam, whole = seeded, walked("nemotron_h", 21)[0]
    monkeypatch.setattr(nemotron_h, "PREFILL_TOKENS", 2 * 21)   # 2 rows of 4
    by_rows = compile_apply()
    assert rel(served(fam, 21, apply=by_rows), whole) < 1e-6
    # and a prefill continued from a cache that already holds 8 positions
    cache = fam.model.make_cache(4, 64, dtype=jnp.float32)
    _, cache = by_rows(fam.model, fam.params, fam.ids[:, :8], cache)
    out, _ = by_rows(fam.model, fam.params, fam.ids[:, 8:21], cache)
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
    fam, want = seeded, seeded.want
    # the selection bias (the program runs with a zero one)
    assert rel(served(fam, 21, params=_without_bias(fam.params)),
               want) > 50 * TOL
    # the routed scaling factor of 2.5
    flat = nemotron_h.NemotronHForCausalLM(
        dataclasses.replace(CFG, routed_scaling_factor=1.0))
    assert rel(served(fam, 21, model=flat), want) > 50 * TOL
    # rotary embedding in attention where the family applies none
    rotary = nemotron_h.NemotronHForCausalLM(
        dataclasses.replace(CFG, attention_rotary=True))
    turned = served(fam, 21, model=rotary)
    assert rel(turned, want) > 50 * TOL
    assert rel(turned, fam.reference_logits(
        fam.params, fam.ids, {**fam.sizes, "attention_rotary": True})) < TOL
    # the norm before the gate (the reference computed in the other order)

    def norm_then_gate(y, z, w, groups, eps):
        yg = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        return yg.reshape(y.shape) * w * jax.nn.silu(z)
    monkeypatch.setattr(fam.reference, "_gated_group_norm", norm_then_gate)
    assert rel(walked("nemotron_h", 21)[0],
               fam.reference_logits(fam.params, fam.ids)) > 50 * TOL


def test_a_bfloat16_state_fails(seeded):
    """The state kept to bf16's 8 bits between steps (float32 arithmetic
    inside a step, as a kernel would do it) over a 5-token prefill and 24
    steps: over `TOL` by a factor of 50. With 16 bits it passes."""
    fam = seeded
    assert rel(served(fam, 5, state_bits=7), fam.want) > 50 * TOL
    assert rel(served(fam, 5, state_bits=16), fam.want) < TOL


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
