"""openPangu-Ultra-MoE (sandwich-normed layers, DENSE latent attention with a
compressed query, ungrouped sigmoid experts with no selection bias) against
the float32 reference (`perfbench/configs/openpangu_reference.py`), at a
small size on seeded weights, LOGITS not tokens: the plain forward, the loss,
and a prefill and then decoding through the latent cache (no K, V or index
keys): the questions all six hybrid families are asked, whose bodies are
`hybrid_families.py`'s; and this family's own: a prefill in chunks attends
the ROW; each of the four norms; the sixteen EP16 shares against the uncut
layer; the counts against the tree and the cache; what DeepSeek-sparse and
this family share is one module; and that a program with one of the named
faults would not pass.

TOLERANCE (`hybrid_families.OPENPANGU_TOL`, 5e-6 absolute on logits of
magnitude 0.5): program and reference both compute in float32 here, in
another order (absorbed against expanded products at decode, sorted expert
rows against a dense sum, a chunked walk against whole rows): the largest
difference read is 4e-7. A bf16 tree moves the logits by 2e-3, four hundred
times the tolerance (`test_a_program_with_a_fault_would_not_pass`)."""

import dataclasses
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import (deepseek_sparse, hybrid, keye_sparse,
                                  latent, openpangu)
from deepspeed_tpu.models.openpangu import OpenPanguConfig
from perfbench.manifest import Manifest
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (OPENPANGU_SIZES as SIZES,
                                               OPENPANGU_TOL as TOL,
                                               compile_apply, family)


PUBLISHED_VOCAB = 153600       # rows of the embedding and of the head


@pytest.fixture(scope="module")
def served():
    fam = family("openpangu")
    return fam.model, fam.params, fam.ids, fam.want


def test_the_published_sizes_and_the_cache(served):
    cfg = OpenPanguConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.latent_width, cfg.qk_head_dim, cfg.v_head_dim,
            cfg.num_hidden_layers, cfg.vocab_size) == \
        (7680, 128, 1536, 576, 192, 128, 61, PUBLISHED_VOCAB)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_group,
            cfg.router_bias_scale, cfg.routed_scaling_factor) == \
        (256, 8, 1, None, 2.5)
    # the router's form is the family's, not a field a caller sets
    assert not {"n_group", "topk_group", "router_bias_scale"} & {
        f.name for f in dataclasses.fields(cfg)}
    with pytest.raises(TypeError):
        OpenPanguConfig(n_group=8)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)   # no YaRN
    assert (cfg.rope_theta, cfg.rms_norm_eps) == (25.6e6, 1e-5)
    # a token: 576 values of latent row a layer and nothing beside it
    from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                       kv_cache_bytes)
    assert kv_bytes_by_kind(cfg, 1, 1, jnp.bfloat16) == {
        "latent_kv_bytes": 61 * 576 * 2}
    assert kv_cache_bytes(cfg, 1, 1, jnp.bfloat16) == 61 * 576 * 2
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    assert cache.kv is None and cache.state is None and cache.window is None \
        and cache.index_keys is None
    assert cache.latent.c.stack.shape == (3, 2, 1, 128, 40)
    assert cache.max_len == 128 and cache.index.shape == (2,)
    kinds = model.cfg.kv_bytes_by_kind(2, 128, jnp.bfloat16)
    assert cache.latent.c.stack.nbytes == kinds["latent_kv_bytes"]
    counts = Manifest().module("configs", "openpangu_counts")
    assert sum(kinds.values()) == 2 * 128 * counts.kv_bytes_per_token(SIZES)
    # the cell's length is GIVEN whole blocks of 2,560 slots, as DeepSeek's
    assert (cfg.cache_slots(24832), cfg.cache_slots(128)) == (25600, 128)
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)


def test_the_counts_are_the_tree_s(served):
    counts = Manifest().module("configs", "openpangu_counts")
    assert counts.total_params(SIZES) == sum(
        x.size for x in jax.tree_util.tree_leaves(served[1]))
    # four norms a layer and no selection bias in the tree
    layers = served[1]["layers"]
    assert {k for k in layers if k.startswith("layer_1_")} == {
        "layer_1_norm", "layer_1_post_attn_norm", "layer_1_mlp_norm",
        "layer_1_post_mlp_norm", "layer_1_mlp"}
    assert set(layers["layer_1_mlp"]["gate"]) == {"wg"}


def test_what_the_two_compressed_query_families_share_exists_once():
    """Query compression, the latent's norm and rope key, absorption, the
    chunk's write and the cache's slots are `models/latent.py`'s; the walk
    (`prefill_walk`), the shell and the held-experts layer
    `models/hybrid.py`'s: both families reach them through the two modules
    and import no sibling. The chunk a prefill is cut by is each family's
    OWN constant, at the value all three learned or dense walks share."""
    for module in (deepseek_sparse, openpangu):
        assert module.latent is latent and module.hybrid is hybrid
        assert not {"prefill_walk", "_experts", "DenseFFN", "_embedded",
                    "_write_chunk"} & set(vars(module))
        assert module.PREFILL_CHUNK == keye_sparse.PREFILL_CHUNK == 2048
    assert OpenPanguConfig.cache_slots is latent.cache_slots
    assert deepseek_sparse.DeepseekSparseConfig.cache_slots \
        is latent.cache_slots


def test_the_plain_forward_is_the_reference_s():
    """The file's first use of the family pays its one build (seeded
    weights, the reference's op-by-op float32 forward)."""
    hybrid_families.the_plain_forward_is_the_reference_s("openpangu")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("openpangu")


def test_prefill_then_decode_through_the_latent_cache():
    """A prompt of one chunk under 128 tokens (the plain form of the
    prefill), then seventeen decode steps whose absorbed read covers every
    cached row. ONE prompt length: no choice's threshold to stand on either
    side of, as the two learned-choice families' tests have."""
    hybrid_families.prefill_then_decode_is_the_reference_s("openpangu", 23)


# 24 is three whole chunks of 8; 23 is PRIME: its last chunk is drawn back
# over position 15, which is computed and written a second time
@pytest.mark.parametrize("prompt", [24, 23], ids=["whole", "prime"])
def test_a_prefill_in_chunks_attends_the_row(served, monkeypatch, prompt):
    """A row's prompt in chunks of 8 queries, each against the row's slab as
    the chunks before it left it (a chunk that attended itself alone would
    miss every earlier position); then a decode step over what they wrote."""
    model, params, ids, want = served
    monkeypatch.setattr(openpangu, "PREFILL_CHUNK", 8)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :prompt],
        model.make_cache(3, 64, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt - 1],
                               atol=TOL)
    for kind in (cache, cache.latent):
        assert np.array_equal(np.asarray(kind.index), [prompt] * 3)
    sums = {name: sum(int(jnp.sum(v)) for path, v in
                      jax.tree_util.tree_leaves_with_path(counted["counters"])
                      if path[-1].key == name)
            for name in model.program_counters}
    assert sums["assignments"] == 2 * 3 * 24 * 4        # two expert layers
    assert 0 < sums["held_assignments"] < sums["assignments"]
    assert sums["experts_held"] == 2 * 3 * 3 * 4        # three chunks a row
    assert 0 < sums["experts_touched"] <= sums["experts_held"]
    logits, _ = compile_apply()(model, params, ids[:, prompt:prompt + 1],
                                cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt],
                               atol=TOL)


class _NoNorm(nn.Module):
    """What stands where a norm was dropped: nothing."""
    eps: float = 1e-5
    dtype: object = jnp.float32

    def __call__(self, x):
        return x


@pytest.mark.parametrize("kind", ["norm", "post_attn_norm", "mlp_norm",
                                  "post_mlp_norm"])
def test_each_of_the_four_norms_changes_the_output_when_dropped(
        served, monkeypatch, kind):
    """The PROGRAM with one of a layer's four norms left out (every layer's),
    on the same tree, against the reference's logits: further than twenty
    times the tolerance the program is held to."""
    model, params, ids, want = served
    real = openpangu.RMSNorm
    monkeypatch.setattr(
        openpangu, "RMSNorm", lambda eps, dtype, name=None: (
            _NoNorm if re.fullmatch(rf"layer_\d+_{kind}", name or "")
            else real)(eps, dtype, name=name))
    got = compile_apply()(type(model)(model.cfg), params, ids)
    assert not np.all(np.abs(np.asarray(got) - want) <= 20 * TOL)


def _bf16(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


# name -> (the file's keys changed, the tree changed, (a function of the
# reference, what replaces it given the real one)): the reference WITH the
# fault
FAULTS = {
    "a bf16 reference": ({}, _bf16, None),
    "no post norms": ({}, None, ("_post_norm", lambda real: lambda x, w, eps: x)),
    "no rope key in the scores": ({}, None, (
        "_key_rope", lambda real: lambda x, cos, sin: jnp.zeros_like(x))),
    "an unrotated rope key": ({}, None, (
        "_key_rope", lambda real: lambda x, cos, sin: x)),
    "another theta": ({"rope_theta": 10000.0}, None, None),
    "weights not over their sum": ({"norm_topk_prob": False}, None, None),
    "no routed scaling": ({"routed_scaling_factor": 1.0}, None, None),
    "another share of the experts": ({"expert_offset": 2}, None, None),
    "eps of 1e-6": ({"rms_norm_eps": 1e-6}, None, None),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_program_with_a_fault_would_not_pass(served, fault, monkeypatch):
    """The reference WITH the fault lies further from the program than
    twenty times the tolerance the program is held to: the comparisons above
    would refuse a program that had it. The first is the bf16 tree: float32
    where float32 is stated is what the tolerance holds."""
    _, params, ids, want = served
    fam = family("openpangu")
    sizes, tree, patch = FAULTS[fault]
    if patch:
        name, replacement = patch
        monkeypatch.setattr(fam.reference, name,
                            replacement(getattr(fam.reference, name)))
    other = fam.reference_logits(tree(params) if tree else params, ids,
                                 {**SIZES, **sizes})
    assert not np.all(np.abs(other - want) <= 20 * TOL)         # NaN is far


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 32 experts against the sixteen EP16 shares of it (2
    experts each), their routed parts summed, and the shared expert counted
    once. What every chip computes alike (the router over all 32 scores, the
    4 best of all at once, the taken weights' sum) is inside each share
    once. No groups, no selection bias: `MoE`'s defaults."""
    from deepspeed_tpu.moe.layer import MoE
    kw = dict(hidden_size=64, num_experts=32, k=4, intermediate_size=32,
              norm_topk_prob=True, drop_tokens=False, dtype=jnp.float32,
              activation="silu", dispatch_impl="ragged", score_fn="sigmoid",
              routed_scaling_factor=2.5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=32,
                shared_intermediate_size=32)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
    assert set(params["gate"]) == {"wg"}                # no bias to choose by
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0     # a decided router
    want = whole.apply({"params": params}, x, train=False)

    def share(chip, shared):
        part = {"gate": params["gate"], "experts": jax.tree_util.tree_map(
            lambda t: t[2 * chip:2 * chip + 2], params["experts"])}
        if shared:
            part["shared_expert"] = params["shared_expert"]
        return MoE(**kw, held_offset=2 * chip, held_experts=2,
                   shared_intermediate_size=32 if shared else None).apply(
            {"params": part}, x, train=False)

    routed = [share(chip, shared=False) for chip in range(16)]
    once = share(0, shared=True) - routed[0]            # the shared expert
    np.testing.assert_allclose(sum(routed) + once, want, atol=2e-5)
    assert sum(float(jnp.abs(r).max()) > 0 for r in routed) > 8
    # and the reference's layer, given the whole, says the same
    sizes = {**SIZES, "n_routed_experts": 32, "router_experts": 32,
             "expert_offset": 0, "num_experts_per_tok": 4}
    ref = family("openpangu").reference
    with jax.default_matmul_precision("highest"):
        ref_out, margin = ref._experts(x.reshape(24, 64), params, sizes)
    np.testing.assert_allclose(ref_out.reshape(want.shape), want, atol=2e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    # a share's margin is taken over its held experts alone: never smaller
    _, cut = ref._experts(x.reshape(24, 64), {
        **params, "experts": jax.tree_util.tree_map(lambda t: t[:2],
                                                    params["experts"])},
        {**sizes, "n_routed_experts": 2})
    assert bool(jnp.all(cut >= margin - 1e-6))
