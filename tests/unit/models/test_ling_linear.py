"""Ling-linear (KDA 5 : 1 MLA over group-limited experts) against the float32
reference (`perfbench/configs/ling_linear_reference.py`), at a small size on
seeded weights, LOGITS not tokens: the plain forward, the loss, and a prefill
and then decoding through the caches (the latent rows, the matrix states):
the questions all three hybrid families are asked, whose bodies are
`hybrid_families.py`'s; and this family's own: the chunked KDA form against
the recurrence; the absorbed MLA form against the expanded one; the four EP4
shares against the uncut layer; and that a program which dropped a term of
the mathematics would not pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import ling_linear
from deepspeed_tpu.models.hybrid import delta_chunked as kda_chunked
from deepspeed_tpu.models.ling_linear import LingLinearConfig
from deepspeed_tpu.ops.pallas.kda import kda_step
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (LING_CFG as CFG,
                                               LING_SIZES as SIZES,
                                               LING_TOL as TOL,
                                               compile_apply, family, walk)

ROWS = 3


@pytest.fixture(scope="module")
def served():
    fam = family("ling_linear")
    return fam.model, fam.params, fam.ids, fam.want


def test_layer_kinds_of_the_published_depth_and_of_the_cut():
    cfg = LingLinearConfig()
    assert cfg.kinds == "KKKKKA" * 7 and cfg.num_kv_layers == 7
    assert cfg.kda_state_shape == (32, 128, 128) and cfg.conv_dim == 12288
    assert (cfg.latent_width, cfg.qk_head_dim) == (576, 192)
    # one period, the two leading dense layers counted once: published
    # layers 0, 2-6: the MLA layer is the fifth, not the sixth
    assert CFG.kinds == "KKKKAK"
    with pytest.raises(ValueError, match="published_layers"):
        LingLinearConfig(**{**SIZES, "published_layers": (0, 1, 2)})
    with pytest.raises(ValueError, match="whole groups"):
        LingLinearConfig(**{**SIZES, "num_experts": 6})


def test_the_plain_forward_is_the_reference_s():
    hybrid_families.the_plain_forward_is_the_reference_s("ling_linear")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("ling_linear")


# a prompt shorter than the convolution, one that is no multiple of the KDA
# chunk (32), one that is
@pytest.mark.parametrize("prompt", [2, 23, 32])
def test_prefill_then_decode_through_the_caches(prompt):
    hybrid_families.prefill_then_decode_is_the_reference_s("ling_linear",
                                                           prompt)


def test_a_prefill_a_few_rows_at_a_time_is_the_same(served, monkeypatch):
    model, params, ids, want = served
    ids = jnp.concatenate([ids, ids[:1]])           # 4 rows, groups of 2
    monkeypatch.setattr(ling_linear, "PREFILL_TOKENS", 2 * 23)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :23], model.make_cache(4, 128, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:3, 0]), want[:, 22],
                               atol=TOL)
    sums = {name: sum(int(jnp.sum(v)) for path, v in
                      jax.tree_util.tree_leaves_with_path(counted["counters"])
                      if path[-1].key == name)
            for name in model.program_counters}
    # five expert layers, two groups of rows: every token takes 4 experts
    assert sums["assignments"] == 5 * 4 * 23 * 4
    assert 0 < sums["held_assignments"] < sums["assignments"]
    assert sums["experts_held"] == 5 * 2 * 8
    assert 0 < sums["experts_touched"] <= sums["experts_held"]
    logits, _ = compile_apply()(model, params, ids[:, 23:24], cache)
    np.testing.assert_allclose(np.asarray(logits[:3, 0]), want[:, 23],
                               atol=TOL)


DROPPED = {
    "the group limit": (dict(n_group=1, topk_group=1), None),
    "the selection bias": ({}, "['gate']['bias']"),
    "the 2.5 scale": (dict(routed_scaling_factor=1.0), None),
    "the head-wise gate": ({}, "['g_proj']['kernel']"),
    "the decay gate's bias": ({}, "['dt_bias']"),
}


@pytest.mark.parametrize("term", list(DROPPED))
def test_a_program_without_a_term_would_not_pass(served, term):
    """The reference WITHOUT the term (a size changed, or a weight zeroed)
    lies further from the program than the tolerance the program is held
    to: the comparison above would refuse a program that dropped it."""
    _, params, ids, want = served
    sizes, zero = DROPPED[term]
    without = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zero and jax.tree_util.keystr(
            path).endswith(zero) else x, params)
    other = family("ling_linear").reference_logits(without, ids,
                                                   {**SIZES, **sizes})
    assert np.abs(other - want).max() > 20 * TOL


def test_the_state_is_kept_in_float32_between_tokens(served):
    """The matrix state IS the layer (no skip path beside it): rounded to
    bfloat16 between steps the walk through the caches misses the reference
    by a thousand times the tolerance (read: 2e-3 against 6e-7)."""
    model, params, ids, want = served
    rounded, _ = walk(model, params, ids, 5, 128, state_bits=7)
    assert np.abs(rounded - want[:, 4:]).max() > 100 * TOL


# ---------------------------------------------------------------- the forms


def kda_sequential(q, k, v, g, beta, s0):
    """The recurrence a position at a time: operands as `kda_chunked`'s."""
    def step(s, t):
        o, s = kda_step(s, *t)
        return s, o

    s_last, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s_last


def _kda_operands(key, b, s, h, d, per_step):
    ks = jax.random.split(key, 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, d))),
            jax.random.normal(ks[2], (b, s, h, d)),
            per_step * jax.random.uniform(ks[3], (b, s, h, d)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))


@pytest.mark.parametrize("per_step,s", [(-0.05, 70), (-5.0, 70), (-5.0, 32),
                                        (-0.5, 5)],
                         ids=["gentle", "at_the_bound", "one_block", "short"])
def test_chunked_kda_is_the_recurrence(per_step, s):
    """Exact, at decays from the seeded model's (about e^-0.05 a step) to
    the gate's bound (e^-5 a step EVERY step: a block's cumulative decay is
    e^-160, which float32 cannot hold and the middle-anchored form never
    forms), over whole and part blocks, from a non-zero state."""
    ops = _kda_operands(jax.random.PRNGKey(3), 2, s, 3, 16, per_step)
    s0 = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 16, 16))
    o, last = kda_chunked(*ops, s0)
    o_ref, last_ref = kda_sequential(*ops, s0)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(last)))
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(last, last_ref, atol=2e-5, rtol=2e-5)


def test_absorbed_mla_is_the_expanded_form(served):
    """One MLA layer alone: a prefill (expanded: every head's key and value
    formed) and then decode steps (absorbed: the query through the key half
    of the up-projection, scores over the 40 cached values a token) against
    the expanded causal pass over the whole sequence."""
    _, params, _, _ = served
    mixer = ling_linear.MLAMixer(CFG)
    p = params["layers"]["layer_4"]
    x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, 20, 64))
    want, _ = mixer.apply({"params": p}, x)
    from deepspeed_tpu.inference.kv_cache import LatentCache
    latent = LatentCache.create(1, ROWS, 32, CFG.latent_width, jnp.float32)
    got, latent = mixer.apply({"params": p}, x[:, :9], latent, 0)
    np.testing.assert_allclose(got, want[:, :9], atol=1e-6)
    step = jax.jit(lambda x_t, latent: mixer.apply({"params": p}, x_t,
                                                   latent, 0))
    for t in range(9, 20):
        latent = latent.replace(index=jnp.full((ROWS,), t, jnp.int32))
        got, row = step(x[:, t:t + 1], latent)
        np.testing.assert_allclose(got[:, 0], want[:, t], atol=1e-6)
        assert row.shape == (ROWS, 40)
        latent = latent.land(row[None])


def test_the_four_shares_add_up_to_the_uncut_layer(served):
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 16 experts, against the four EP4 shares of it (4
    experts each, whole groups), the routed parts summed and the shared
    expert, which every chip computes alike, counted ONCE."""
    from deepspeed_tpu.moe.layer import MoE
    kw = dict(hidden_size=64, num_experts=16, k=4, intermediate_size=32,
              drop_tokens=False, dtype=jnp.float32, activation="silu",
              dispatch_impl="ragged", score_fn="sigmoid", selection_bias=True,
              bias_init=jax.nn.initializers.normal(0.01),
              routed_scaling_factor=2.5, n_group=4, topk_group=2)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=16,
                shared_intermediate_size=32)
    import flax.linen as nn
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
    want = whole.apply({"params": params}, x, train=False)

    def share(chip, with_shared):
        """Chip `chip`'s layer: experts 4 chip .. 4 chip + 3 of the same
        weights, with the shared expert or without."""
        part = {"gate": params["gate"], "experts": jax.tree_util.tree_map(
            lambda t: t[4 * chip:4 * chip + 4], params["experts"])}
        if with_shared:
            part["shared_expert"] = params["shared_expert"]
        return MoE(**kw, held_offset=4 * chip, held_experts=4,
                   shared_intermediate_size=32 if with_shared else None
                   ).apply({"params": part}, x, train=False)

    routed = sum(share(chip, False) for chip in range(4))
    shared_once = share(0, True) - share(0, False)
    np.testing.assert_allclose(routed + shared_once, want, atol=1e-5)
    # and the reference's layer, given the whole, says the same
    sizes = {**SIZES, "num_experts": 16, "router_experts": 16}
    ref_out, _ = family("ling_linear").reference._experts(x, params, sizes)
    np.testing.assert_allclose(ref_out, want, atol=1e-5)


def test_the_cache_by_kind(served):
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    # no K or V at all: ONE latent array of 40 values a token for the one MLA
    # layer, five matrix states (4 heads of 16 x 16, float32) and their
    # convolution tails (q, k and v side by side)
    assert cache.kv is None and cache.window is None
    assert cache.latent.c.stack.shape == (1, 2, 1, 128, 40)
    assert cache.state.ssm.shape == (5, 2, 4, 16, 16)
    assert cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (5, 2, 3, 192)
    assert cache.max_len == 128 and cache.index.shape == (2,)
    assert cache.latent.c.stack.nbytes == \
        CFG.kv_bytes_by_kind(2, 128, jnp.bfloat16)["latent_kv_bytes"]
    assert cache.state.ssm.nbytes + cache.state.conv.nbytes == \
        CFG.recurrent_state_bytes(2, jnp.bfloat16)
    part = cache.rows(1, 1)
    assert part.latent.c.stack.shape == (1, 1, 1, 128, 40)
    assert cache.with_rows(part, 0).latent.c.stack.shape == (1, 2, 1, 128, 40)
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)
