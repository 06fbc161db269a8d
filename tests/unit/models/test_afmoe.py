"""Arcee Trinity (`models/afmoe.py`: window layers with rotary and full
layers without, three to one; head norms; a sigmoid gate on the attention's
output; four norms a layer; sigmoid experts chosen by a biased score)
against the float32 reference (`perfbench/configs/afmoe_reference.py`), at a
small size on seeded weights, LOGITS not tokens: the plain forward, the loss,
and a prefill and then decoding through rings AND full-length rows: the
questions all seven hybrid families are asked, whose bodies are
`hybrid_families.py`'s; and this family's own: a prefill a few rows at a
time; each line the configuration file lists under `assumed`, dropped; the
eight EP8 shares against the uncut layer; the counts against the tree and
the cache; what it shares with other families exists once.

TOLERANCE (`hybrid_families.AFMOE_TOL`, 5e-6 absolute on logits of magnitude
0.7): program and reference both compute in float32 here, in another order
(a ring's slots against a row's positions, a staged token against a written
one, sorted expert rows against a dense sum, rows in groups against whole
batches): the largest difference read is 5e-7. A bf16 tree moves the logits
by 2e-3 (`test_a_program_with_a_fault_would_not_pass`). The window is 8 and
a row 40 positions, so every ring WRAPS during the prefill (20 > 8) and
again while decoding."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.models import afmoe, hybrid, ling_linear, phi4flash
from deepspeed_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig
from perfbench.manifest import Manifest
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (AFMOE_SIZES as SIZES,
                                               AFMOE_TOL as TOL,
                                               compile_apply, family)


PUBLISHED_VOCAB = 200192       # rows of the embedding and of the head


@pytest.fixture(scope="module")
def served():
    fam = family("afmoe")
    return fam.model, fam.params, fam.ids, fam.want


def test_the_published_sizes_and_the_cache(served):
    cfg = AfmoeConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_hidden_layers, cfg.vocab_size, cfg.sliding_window) == (
                2048, 32, 4, 128, 6144, 1024, 32, PUBLISHED_VOCAB, 2048)
    # three window layers to one full, the full one every fourth
    assert cfg.layer_types == ((SLIDING,) * 3 + (FULL,)) * 8
    assert (cfg.window_layers, cfg.full_layers) == (24, 8)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_dense_layers,
            cfg.n_group, cfg.router_bias_scale, cfg.routed_scaling_factor,
            cfg.norm_topk_prob) == (128, 8, 2, 1, 0.01, 2.826, True)
    assert cfg.embed_scale == pytest.approx(2048 ** 0.5)
    # the router's form is the family's, not a field a caller sets
    assert not {"n_group", "topk_group", "router_bias_scale"} & {
        f.name for f in dataclasses.fields(cfg)}
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_hidden_layers=3, layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_hidden_layers=1, layer_types=("chunked_attention",))
    # a token: K and V of the full layers alone; a ring is 2,048 slots a row
    from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                       kv_cache_bytes)
    token = 2 * 4 * 128 * 2
    assert kv_bytes_by_kind(cfg, 1, 1, jnp.bfloat16) == {
        "window_kv_bytes": 24 * 2048 * token, "full_kv_bytes": 8 * token}
    assert kv_cache_bytes(cfg, 3, 4096, jnp.bfloat16) == 3 * token * (
        24 * 2048 + 8 * 4096)
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    assert cache.state is None and cache.latent is None \
        and cache.index_keys is None
    assert cache.kv.k.stack.shape == (2, 2, 2, 128, 16) and not cache.kv.ring
    assert cache.window.k.stack.shape == (3, 2, 2, 8, 16) and cache.window.ring
    assert cache.max_len == 128 and cache.index.shape == (2,)
    kinds = model.cfg.kv_bytes_by_kind(2, 128, jnp.bfloat16)
    for kind, held in (("window_kv_bytes", cache.window),
                       ("full_kv_bytes", cache.kv)):
        assert held.k.stack.nbytes + held.v.stack.nbytes == kinds[kind]
    counts = Manifest().module("configs", "afmoe_counts")
    assert kinds["full_kv_bytes"] == 2 * 128 * counts.kv_bytes_per_token(SIZES)
    # a view of a ring says so, and a full layer's does not
    assert cache.window.layer_views(0, staged=True)[0].ring
    assert not cache.kv.layer_views(0, staged=True)[0].ring
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)


def test_the_counts_are_the_tree_s(served):
    counts = Manifest().module("configs", "afmoe_counts")
    assert counts.total_params(SIZES) == sum(
        x.size for x in jax.tree_util.tree_leaves(served[1]))
    layers = served[1]["layers"]
    assert {k for k in layers if k.startswith("layer_1_")} == {
        "layer_1_norm", "layer_1_post_attn_norm", "layer_1_mlp_norm",
        "layer_1_post_mlp_norm", "layer_1_mlp"}
    assert set(layers["layer_1"]) == {"q_proj", "k_proj", "v_proj",
                                      "gate_proj", "o_proj", "q_norm",
                                      "k_norm"}
    assert layers["layer_1"]["q_norm"]["weight"].shape == (16,)
    assert set(layers["layer_1_mlp"]["gate"]) == {"wg", "bias"}
    assert set(layers["layer_0_mlp"]) == {"gate_proj", "up_proj", "down_proj"}


def test_what_the_family_shares_exists_once():
    """The held-experts layer, the dense FFN and the shell are
    `models/hybrid.py`'s (the family reaches them through the module, and
    imports no sibling), the four-norm layer's norm `llama.RMSNorm` under the
    family's own name (a tool replaces it there), and a prefill's write into a
    ring is `kv_cache.write_prefill_rows`, which Phi-4-mini-flash's stacks
    go through too: nothing is copied."""
    from deepspeed_tpu.models.llama import RMSNorm
    assert afmoe.hybrid is hybrid and ling_linear.hybrid is hybrid
    for module in (afmoe, ling_linear):
        assert not {"_experts", "DenseFFN", "_RowGroups", "_embedded"} \
            & set(vars(module))
    assert afmoe.RMSNorm is RMSNorm
    assert not hasattr(phi4flash, "_write_prefill")
    assert not hasattr(afmoe, "write_prefill_rows")
    # positions 0 .. 10 into a ring of 4: the last four, p in slot p mod 4
    stack = jnp.zeros((2, 1, 1, 4, 1))
    new = jnp.arange(11.0).reshape(1, 11, 1, 1)
    ring = kv_cache.write_prefill_rows(stack, 1, new, ring=True)
    assert ring[1, 0, 0, :, 0].tolist() == [8.0, 9.0, 10.0, 7.0]
    assert not bool(jnp.any(ring[0]))
    flat = kv_cache.write_prefill_rows(jnp.zeros((1, 1, 1, 16, 1)), 0, new,
                                       ring=False)
    assert flat[0, 0, 0, :11, 0].tolist() == list(range(11))


def test_the_plain_forward_is_the_reference_s():
    """The file's first use of the family pays its one build (seeded
    weights, the reference's op-by-op float32 forward)."""
    hybrid_families.the_plain_forward_is_the_reference_s("afmoe")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("afmoe")


@pytest.mark.parametrize("prompt", [20, 5], ids=["past_the_window",
                                                 "inside_the_window"])
def test_prefill_then_decode_through_rings_and_full_rows(prompt):
    """A prompt of 20 under a window of 8: the rings keep its last 8
    positions, rolled into their slots, and wrap again in the 20 decode
    steps; a prompt of 5 leaves the rings part full, and decoding fills
    them exactly, then wraps them."""
    hybrid_families.prefill_then_decode_is_the_reference_s("afmoe", prompt)


def test_a_prefill_walks_the_batch_a_row_at_a_time(served, monkeypatch):
    """`PREFILL_TOKENS` under two rows' tokens: the scan over row groups,
    each group's rows cut out of both kinds' stacks and written back; the
    expert layers' counters come out of the scan, the attention's count
    nothing in a prefill and every kind's positions in a decode step."""
    model, params, ids, want = served
    monkeypatch.setattr(afmoe, "PREFILL_TOKENS", 30)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :20], model.make_cache(3, 64, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 19],
                               atol=TOL)
    for kind in (cache, cache.kv, cache.window):
        assert np.array_equal(np.asarray(kind.index), [20] * 3)

    def sums(counted):
        return {name: sum(
            int(jnp.sum(v)) for path, v in
            jax.tree_util.tree_leaves_with_path(counted["counters"])
            if path[-1].key == name) for name in model.program_counters}
    got = sums(counted)
    assert got["assignments"] == 4 * 3 * 20 * 4         # four expert layers
    assert 0 < got["held_assignments"] < got["assignments"]
    assert got["kv_positions_attended"] == got["kv_positions_window"] == 0
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, 20:21], cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 20],
                               atol=TOL)
    got = sums(counted)
    # 21 positions a row: three rings of 8 slots, two full layers of 21
    assert got["kv_positions_window"] == 3 * 3 * 8
    assert got["kv_positions_attended"] == 3 * (3 * 8 + 2 * 21)


def _bf16(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


def _no_bias(params):
    """The tree with every selection bias at 0."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
            path).endswith("['gate']['bias']") else x, params)


# name -> (the file's keys changed, the tree changed, (a function of the
# reference, what replaces it given the real one)): the reference WITH the
# fault. Each line of the configuration file's `assumed` is here, dropped.
FAULTS = {
    "a bf16 reference": ({}, _bf16, None),
    "rotary in the full layers too": ({}, None, (
        "_positioned", lambda real: lambda x, cos, sin, sliding:
        real(x, cos, sin, True))),
    "no rotary in the window layers": ({}, None, (
        "_positioned", lambda real: lambda x, cos, sin, sliding: x)),
    "another theta": ({"rope_theta": 1e6}, None, None),
    "no gate on the attention's output": ({}, None, (
        "_gate", lambda real: lambda o, g: o)),
    "no head norms": ({}, None, ("_head_norm", lambda real:
                                 lambda x, w, eps: x)),
    "no post norms": ({}, None, ("_post_norm", lambda real:
                                 lambda x, w, eps: x)),
    "an unscaled embedding": ({"mup_enabled": False}, None, None),
    "no bias in the choice": ({}, _no_bias, None),
    "the bias in the weights too": ({}, None, (
        "_weighed", lambda real: lambda scores, choice: choice)),
    "no route scale": ({"route_scale": 1.0}, None, None),
    "weights not over their sum": ({"route_norm": False}, None, None),
    "a window one position longer": ({"sliding_window": 9}, None, None),
    "every layer full": ({"layer_types": [FULL] * 5}, None, None),
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
    fam = family("afmoe")
    sizes, tree, patch = FAULTS[fault]
    if patch:
        name, replacement = patch
        monkeypatch.setattr(fam.reference, name,
                            replacement(getattr(fam.reference, name)))
    # ONE row: the op-by-op float32 reference is 3 s a row, sixteen times
    other = fam.reference_logits(tree(params) if tree else params, ids[:1],
                                 {**SIZES, **sizes})
    assert not np.all(np.abs(other - want[:1]) <= 20 * TOL)     # NaN is far


@pytest.mark.parametrize("name,broken", [
    ("_gated", lambda o, g, dtype: o.astype(dtype)),
    ("_rotated", lambda cfg, q, k, positions, sliding: (q, k))],
    ids=["no_gate", "no_rotary"])
def test_the_program_without_a_step_is_not_the_reference(served, monkeypatch,
                                                         name, broken):
    """And the other way round: the PROGRAM with its gate or its window
    layers' rotary taken out, on the same tree, is further from the
    reference's logits than twenty times the tolerance."""
    model, params, ids, want = served
    monkeypatch.setattr(afmoe, name, broken)
    got = compile_apply()(type(model)(model.cfg), params, ids)
    assert not np.all(np.abs(np.asarray(got) - want) <= 20 * TOL)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 32 experts against the eight EP8 shares of it (4
    experts each), their routed parts summed, and the shared expert counted
    once. What every chip computes alike (the router over all 32 scores, the
    selection bias in the choice of the 4 best, the taken weights' sum) is
    inside each share once."""
    from deepspeed_tpu.moe.layer import MoE
    kw = dict(hidden_size=64, num_experts=32, k=4, intermediate_size=32,
              norm_topk_prob=True, drop_tokens=False, dtype=jnp.float32,
              activation="silu", dispatch_impl="ragged", score_fn="sigmoid",
              selection_bias=True, bias_init=nn.initializers.normal(0.1),
              routed_scaling_factor=2.826)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=32,
                shared_intermediate_size=32)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
    assert set(params["gate"]) == {"wg", "bias"}
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0     # a decided router
    want = whole.apply({"params": params}, x, train=False)

    def share(chip, shared):
        part = {"gate": params["gate"], "experts": jax.tree_util.tree_map(
            lambda t: t[4 * chip:4 * chip + 4], params["experts"])}
        if shared:
            part["shared_expert"] = params["shared_expert"]
        return MoE(**kw, held_offset=4 * chip, held_experts=4,
                   shared_intermediate_size=32 if shared else None).apply(
            {"params": part}, x, train=False)

    routed = [share(chip, shared=False) for chip in range(8)]
    once = share(0, shared=True) - routed[0]            # the shared expert
    np.testing.assert_allclose(sum(routed) + once, want, atol=2e-5)
    assert sum(float(jnp.abs(r).max()) > 0 for r in routed) > 4
    # and the reference's layer, given the whole, says the same
    sizes = {**SIZES, "num_experts": 32, "router_experts": 32,
             "expert_offset": 0, "num_experts_per_tok": 4}
    ref = family("afmoe").reference
    with jax.default_matmul_precision("highest"):
        ref_out, margin = ref._experts(x.reshape(24, 64), params, sizes)
    np.testing.assert_allclose(ref_out.reshape(want.shape), want, atol=2e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    # the bias decides here: without it the same tokens take other experts
    flat, _ = ref._experts(x.reshape(24, 64), {**params, "gate": {
        **params["gate"], "bias": jnp.zeros(32)}}, sizes)
    assert float(jnp.abs(flat - ref_out).max()) > 1e-3
    # a share's margin is taken over its held experts alone: never smaller
    _, cut = ref._experts(x.reshape(24, 64), {
        **params, "experts": jax.tree_util.tree_map(lambda t: t[:4],
                                                    params["experts"])},
        {**sizes, "num_experts": 4})
    assert bool(jnp.all(cut >= margin - 1e-6))
