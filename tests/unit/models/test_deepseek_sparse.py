"""DeepSeek-sparse (latent attention under a learned choice of 2,048 cached
rows, query compression that feeds attention and indexer, YaRN, over
group-limited sigmoid experts) against the float32 reference
(`perfbench/configs/deepseek_sparse_reference.py`), at a small size on seeded
weights, LOGITS not tokens: the plain forward, the loss, and a prefill and
then decoding through the caches (the latent rows and the index keys beside
them, no K or V): the questions all five hybrid families are asked, whose
bodies are `hybrid_families.py`'s; and this family's own: a prefill in
chunks chooses over the ROW; the sixteen EP16 shares, which cut every group
in halves, against the uncut layer; the counts against the tree and the
cache; and that a program with one of the named faults would not pass."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import deepseek_sparse
from deepspeed_tpu.models.deepseek_sparse import DeepseekSparseConfig
from perfbench.manifest import Manifest
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (DEEPSEEK_SIZES as SIZES,
                                               DEEPSEEK_TOL as TOL,
                                               compile_apply, family)

TOPK = SIZES["index_topk"]


@pytest.fixture(scope="module")
def served():
    fam = family("deepseek_sparse")
    return fam.model, fam.params, fam.ids, fam.want


def test_the_published_sizes_and_the_cache_by_kind(served):
    cfg = DeepseekSparseConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.latent_width, cfg.qk_head_dim, cfg.v_head_dim) == \
        (7168, 128, 1536, 576, 192, 128)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == \
        (64, 128, 2048)
    # 192^-0.5 x (0.1 ln 40 + 1)^2
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2,
                                              rel=1e-4)
    # a token: 576 values of latent row and 128 of index key a layer, BOTH
    # kinds counted for one engine, and their sum what is held
    from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                       kv_cache_bytes)
    assert kv_bytes_by_kind(cfg, 1, 1, jnp.bfloat16) == {
        "latent_kv_bytes": 61 * 576 * 2, "index_kv_bytes": 61 * 128 * 2}
    assert kv_cache_bytes(cfg, 1, 1, jnp.bfloat16) == 61 * 704 * 2
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    assert cache.kv is None and cache.state is None and cache.window is None
    assert cache.latent.c.stack.shape == (3, 2, 1, 128, 40)
    assert cache.index_keys.c.stack.shape == (3, 2, 1, 128, 16)
    assert cache.max_len == 128 and cache.index.shape == (2,)
    kinds = model.cfg.kv_bytes_by_kind(2, 128, jnp.bfloat16)
    assert cache.latent.c.stack.nbytes == kinds["latent_kv_bytes"]
    assert cache.index_keys.c.stack.nbytes == kinds["index_kv_bytes"]
    counts = Manifest().module("configs", "deepseek_sparse_counts")
    assert sum(kinds.values()) == 2 * 128 * counts.kv_bytes_per_token(SIZES)
    # every cursor of both kinds moves together
    moved = cache.advance_row(1, 5)
    assert [list(np.asarray(k.index)) for k in (
        moved, moved.latent, moved.index_keys)] == [[0, 5]] * 3
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)


def test_the_counts_are_the_tree_s(served):
    """`total_params` at the small size equals the program's tree, leaf for
    leaf summed; at the cell's size the issue's hand-written numbers are
    `tests/perfbench/test_deepseek_cell.py`'s."""
    counts = Manifest().module("configs", "deepseek_sparse_counts")
    assert counts.total_params(SIZES) == sum(
        x.size for x in jax.tree_util.tree_leaves(served[1]))


def test_the_plain_forward_is_the_reference_s():
    """The file's first use of the family pays its one build (seeded
    weights, the reference's op-by-op float32 forward)."""
    hybrid_families.the_plain_forward_is_the_reference_s("deepseek_sparse")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("deepseek_sparse")


# prompts below, at and above `index_topk` (8): every position is kept, the
# first position with a choice is a decode step's, the prefill itself
# chooses; every generated position's LOGITS are compared, and from 8 on the
# choice drops rows
@pytest.mark.parametrize("prompt", [5, 8, 23])
def test_prefill_then_decode_through_the_caches(prompt):
    hybrid_families.prefill_then_decode_is_the_reference_s("deepseek_sparse",
                                                           prompt)


# 24 is three whole chunks of 8; 23 is PRIME: its last chunk is drawn back
# over position 15, which is computed, written and counted a second time
@pytest.mark.parametrize("prompt,walked", [
    (24, list(range(1, 25))),
    (23, list(range(1, 17)) + list(range(16, 24)))], ids=["whole", "prime"])
def test_a_prefill_in_chunks_chooses_over_the_row(served, monkeypatch, prompt,
                                                  walked):
    """A row's prompt in chunks of 8 queries (`index_topk` is 8 too: a
    choice made over the chunk alone would keep every position of it and
    none before), each against the row's slabs as the chunks before it left
    them; the counters summed over chunks."""
    model, params, ids, want = served
    monkeypatch.setattr(deepseek_sparse, "PREFILL_CHUNK", 8)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :prompt],
        model.make_cache(3, 64, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt - 1],
                               atol=TOL)
    for kind in (cache, cache.latent, cache.index_keys):
        assert np.array_equal(np.asarray(kind.index), [prompt] * 3)
    sums = {name: sum(int(jnp.sum(v)) for path, v in
                      jax.tree_util.tree_leaves_with_path(counted["counters"])
                      if path[-1].key == name)
            for name in model.program_counters}
    layers, rows = 3, 3
    assert sums["kv_positions_live"] == layers * rows * sum(walked)
    assert sums["kv_positions_selected"] == layers * rows * sum(
        min(t, TOPK) for t in walked)
    assert sums["assignments"] == 2 * rows * 24 * 4      # two expert layers
    assert 0 < sums["held_assignments"] < sums["assignments"]
    assert sums["experts_held"] == 2 * rows * 3 * 4      # three chunks a row
    logits, _ = compile_apply()(model, params, ids[:, prompt:prompt + 1],
                                cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt],
                               atol=TOL)


def _bf16(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


def _no_selection_bias(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
            path).endswith("['gate']['bias']") else x, params)


def _plain_freq(cfg):
    dr = cfg["qk_rope_head_dim"]
    return cfg["rope_theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)


# name -> (the file's keys changed, the tree changed, (a function of the
# reference, what replaces it given the real one)): the reference WITH the
# fault
FAULTS = {
    "a bf16 reference": ({}, _bf16, None),
    "no selection bias": ({}, _no_selection_bias, None),
    "no mscale": ({}, None, ("yarn", lambda real: lambda cfg: (
        real(cfg)[:2] + (1.0,)))),
    "unscaled YaRN frequencies": ({}, None, ("yarn", lambda real: lambda cfg: (
        (_plain_freq(cfg),) + real(cfg)[1:]))),
    "no YaRN at all": ({"rope_scaling": None}, None, None),
    "a choice over the chunk alone": ({}, None, (
        "_candidates", lambda real: lambda t, s: (
            jnp.arange(s)[None, :] >= (t // 8 * 8)[:, None]))),
    "an indexer fed from u": ({}, None, (
        "_index_input", lambda real: lambda cq, x: x[..., :cq.shape[-1]])),
    "no rotary in the indexer": ({}, None, (
        "_index_rope", lambda real: lambda x, cos, sin: x)),
    "the selection": ({"index_topk": 64}, None, None),
    "half the selection": ({"index_topk": TOPK // 2}, None, None),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_program_with_a_fault_would_not_pass(served, fault, monkeypatch):
    """The reference WITH the fault lies further from the program than
    twenty times the tolerance the program is held to, at some generated
    position past `index_topk`: the comparisons above would refuse a program
    that had it."""
    _, params, ids, want = served
    fam = family("deepseek_sparse")
    sizes, tree, patch = FAULTS[fault]
    if patch:
        name, replacement = patch
        monkeypatch.setattr(fam.reference, name,
                            replacement(getattr(fam.reference, name)))
    other = fam.reference_logits(tree(params) if tree else params, ids,
                                 {**SIZES, **sizes})
    assert not np.all(np.abs(other - want)[:, TOPK:] <= 20 * TOL)  # NaN is far


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 32 experts against the sixteen EP16 shares of it (2
    experts each: eight groups of 4 cut in HALVES, as 16 of 256 cut a group
    of 32), their routed parts summed, and the shared expert counted once.
    What every chip computes alike (the router over all 32 scores and all 8
    groups, the selection bias, the taken weights' sum) is inside each share
    once."""
    from deepspeed_tpu.moe.layer import MoE
    kw = dict(hidden_size=64, num_experts=32, k=4, intermediate_size=32,
              norm_topk_prob=True, drop_tokens=False, dtype=jnp.float32,
              activation="silu", dispatch_impl="ragged", score_fn="sigmoid",
              selection_bias=True, bias_init=nn.initializers.normal(0.1),
              routed_scaling_factor=2.5, n_group=8, topk_group=4)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=32,
                shared_intermediate_size=32)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
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
             "expert_offset": 0, "num_experts_per_tok": 4, "n_group": 8,
             "topk_group": 4}
    ref = family("deepseek_sparse").reference
    with jax.default_matmul_precision("highest"):
        ref_out, margin = ref._experts(x.reshape(24, 64), params, sizes)
    np.testing.assert_allclose(ref_out.reshape(want.shape), want, atol=2e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    # a share that cuts a group: its margin is taken over the held half alone
    _, cut = ref._experts(x.reshape(24, 64), {
        **params, "experts": jax.tree_util.tree_map(lambda t: t[:2],
                                                    params["experts"])},
        {**sizes, "n_routed_experts": 2})
    assert bool(jnp.all(cut >= margin - 1e-6))
