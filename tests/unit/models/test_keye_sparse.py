"""Keye-sparse (GQA under a learned choice of 2,048 cached tokens, over
softmax-routed experts) against the float32 reference
(`perfbench/configs/keye_sparse_reference.py`), at a small size on seeded
weights, LOGITS not tokens: the plain forward, the loss, and a prefill and
then decoding through the caches (K and V, and the index keys beside them):
the questions all four hybrid families are asked, whose bodies are
`hybrid_families.py`'s; and this family's own: the chosen SETS against
`jax.lax.top_k`'s; a prefill in chunks; the eight EP8 shares against the
uncut layer; and that a program which dropped a term of the mathematics
would not pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid, keye_sparse
from deepspeed_tpu.models.keye_sparse import KeyeSparseConfig
from deepspeed_tpu.ops.pallas import sparse_select as ss
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (KEYE_SIZES as SIZES,
                                               KEYE_TOL as TOL,
                                               compile_apply, family)

TOPK = SIZES["sa_config"]["topk"]


@pytest.fixture(scope="module")
def served():
    fam = family("keye_sparse")
    return fam.model, fam.params, fam.ids, fam.want


def test_the_published_sizes_and_the_cut():
    cfg = KeyeSparseConfig()
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 4, 128)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim,
            cfg.index_topk) == (16, 64, 2048)
    # a token's index key is held a whole lane row, 256 bytes a layer (its
    # own 64 values are 128) beside 2,048 of K and V
    assert cfg.kv_bytes_by_kind(1, 1)["index_kv_bytes"] == 48 * 128 * 2
    from deepspeed_tpu.inference.capacity_scan import kv_cache_bytes
    assert kv_cache_bytes(cfg, 1, 1, jnp.bfloat16) == 48 * (2048 + 256)


def test_the_plain_forward_is_the_reference_s():
    """13 s: the file's first use of the family pays its one build (seeded
    weights, the reference's op-by-op float32 forward)."""
    hybrid_families.the_plain_forward_is_the_reference_s("keye_sparse")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("keye_sparse")


# prompts below, at and above `topk` (8): every position is kept, the first
# position with a choice is a decode step's, the prefill itself chooses
@pytest.mark.parametrize("prompt", [5, 8, 23])
def test_prefill_then_decode_through_the_caches(prompt):
    hybrid_families.prefill_then_decode_is_the_reference_s("keye_sparse",
                                                           prompt)


@pytest.mark.parametrize("s", [5, 127, 128, 300, 2048, 2053, 32768, 40009])
def test_every_length_is_walked_in_whole_tiles(s):
    """A prime length over the chunk, the reviewer's 2,053, is two chunks of
    2,048, the second drawn back over 2,043 positions; nothing is a chunk of
    one query (that shape is a decode step's)."""
    size, starts = hybrid.prefill_chunks(s, keye_sparse.PREFILL_CHUNK)
    assert size == s if s < 128 else (size % 128 == 0 and size <= 2048)
    assert starts[0] == 0 and starts[-1] + size == s
    assert all(0 < b - a <= size for a, b in zip(starts, starts[1:]))
    assert len(starts) == -(-s // size)
    if s == 2053:
        assert (size, starts) == (2048, [0, 5])


# 24 is three whole chunks of 8; 23 is PRIME: its last chunk is drawn back
# over position 15, which is computed, written and counted a second time
@pytest.mark.parametrize("prompt,walked", [
    (24, list(range(1, 25))),
    (23, list(range(1, 17)) + list(range(16, 24)))], ids=["whole", "prime"])
def test_a_prefill_in_chunks_is_the_same(served, monkeypatch, prompt, walked):
    """A row's prompt in chunks of 8 queries, each against the row's slabs
    as the chunks before it left them; the counters summed over chunks."""
    model, params, ids, want = served
    monkeypatch.setattr(keye_sparse, "PREFILL_CHUNK", 8)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :prompt],
        model.make_cache(3, 64, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt - 1],
                               atol=TOL)
    assert np.array_equal(np.asarray(cache.index), [prompt] * 3)
    assert np.array_equal(np.asarray(cache.index_keys.index), [prompt] * 3)
    sums = {name: sum(int(jnp.sum(v)) for path, v in
                      jax.tree_util.tree_leaves_with_path(counted["counters"])
                      if path[-1].key == name)
            for name in model.program_counters}
    layers, rows = 3, 3
    assert sums["kv_positions_live"] == layers * rows * sum(walked)
    assert sums["kv_positions_selected"] == layers * rows * sum(
        min(t, TOPK) for t in walked)
    assert sums["assignments"] == layers * rows * 24 * 3
    assert 0 < sums["held_assignments"] < sums["assignments"]
    assert sums["experts_held"] == layers * rows * 3 * 4     # three chunks a row
    logits, _ = compile_apply()(model, params, ids[:, prompt:prompt + 1],
                                cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, prompt],
                               atol=TOL)


def test_the_chosen_sets_are_top_k_s(served):
    """One layer's own index operands from the seeded tree, float32: the set
    each query keeps, through the plain form and through the prefill and
    decode kernels (interpreted), is `jax.lax.top_k`'s on the same scores,
    ties included (a duplicated key ties two positions exactly)."""
    _, params, _, _ = served
    key = jax.random.PRNGKey(9)
    s, hi, di = 40, 4, 8
    q_i = jax.random.normal(key, (s, hi, di))
    k_i = jax.random.normal(jax.random.fold_in(key, 1), (s, di))
    k_i = k_i.at[7].set(k_i[3]).at[30].set(k_i[3])        # exact ties
    w = jax.random.normal(jax.random.fold_in(key, 2), (s, hi))
    scores = np.asarray(ss.index_scores(q_i, w, k_i))
    live = np.tril(np.ones((s, s), bool))
    want = np.zeros((s, s), bool)
    for t in range(s):
        _, at = jax.lax.top_k(jnp.where(live[t], scores[t], -jnp.inf),
                              min(TOPK, s))
        want[t, np.asarray(at)[:min(TOPK, t + 1)]] = True
    np.testing.assert_array_equal(
        np.asarray(ss.chosen(jnp.asarray(scores), jnp.asarray(live), TOPK)),
        want)
    assert want[35, 3] == want[35, 7] or want[35, 3]     # the lower one first
    # the decode kernel's choice at every position, the step's key staged
    stack = jnp.zeros((1, s, 1, 64, di)).at[0, :, 0, :s].set(k_i[None])
    lengths = jnp.arange(1, s + 1, dtype=jnp.int32)
    bias, count = jax.jit(lambda *a: ss.sparse_index_select(
        *a[:3], 0, a[3], TOPK, a[4]))(q_i, w, stack, lengths, k_i)
    np.testing.assert_array_equal(np.asarray(bias)[:, :s] == 0.0, want)
    np.testing.assert_array_equal(np.asarray(count), want.sum(-1))


DROPPED = {
    "the selection": (dict(topk=64), None),
    "half the selection": (dict(topk=TOPK // 2), None),
    "the relu": ({}, ("_index_scores", lambda q_i, k_i, w: jnp.einsum(
        "bqh,bqhs->bqs", w, jnp.einsum("bqhd,bsd->bqhs", q_i, k_i)))),
    "the heads' weights": ({}, ("_index_scores", lambda q_i, k_i, w: jnp.sum(
        jax.nn.relu(jnp.einsum("bqhd,bsd->bqhs", q_i, k_i)), axis=2))),
    "a causal choice": ({}, ("_candidates", lambda t, s: jnp.ones(
        (t.shape[0], s), bool))),
    "the q and k norms": ({}, ("_head_norm", lambda x, w, eps: x)),
}


@pytest.mark.parametrize("term", list(DROPPED))
def test_a_program_without_a_term_would_not_pass(served, term, monkeypatch):
    """The reference WITHOUT the term (the indexer's size changed, or one of
    its functions replaced) lies further from the program than the tolerance
    the program is held to: the comparison above would refuse a program that
    dropped it."""
    _, params, ids, want = served
    fam = family("keye_sparse")
    sa, patch = DROPPED[term]
    if patch:
        monkeypatch.setattr(fam.reference, *patch)
    other = fam.reference_logits(
        params, ids, {**SIZES, "sa_config": {**SIZES["sa_config"], **sa}})
    assert not np.all(np.abs(other - want) <= 20 * TOL)     # a NaN is far too


def test_the_eight_shares_add_up_to_the_uncut_layer(served):
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 16 experts against the eight EP8 shares of it (2
    experts each), summed. What every chip computes alike (the router over
    all 16 scores, the taken weights' sum) is inside each share once; there
    is no shared expert to count once."""
    from deepspeed_tpu.moe.layer import MoE
    import flax.linen as nn
    kw = dict(hidden_size=64, num_experts=16, k=4, intermediate_size=32,
              norm_topk_prob=True, drop_tokens=False, dtype=jnp.float32,
              activation="silu", dispatch_impl="ragged", score_fn="softmax")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=16)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0     # a decided router
    want = whole.apply({"params": params}, x, train=False)

    def share(chip):
        part = {"gate": params["gate"], "experts": jax.tree_util.tree_map(
            lambda t: t[2 * chip:2 * chip + 2], params["experts"])}
        return MoE(**kw, held_offset=2 * chip, held_experts=2).apply(
            {"params": part}, x, train=False)

    np.testing.assert_allclose(sum(share(chip) for chip in range(8)), want,
                               atol=1e-5)
    assert float(jnp.abs(share(0)).max()) > 0
    # and the reference's layer, given the whole, says the same
    sizes = {**SIZES, "num_experts": 16, "router_experts": 16,
             "expert_offset": 0, "num_experts_per_tok": 4}
    ref_out, margin = family("keye_sparse").reference._experts(x, params,
                                                               sizes)
    np.testing.assert_allclose(ref_out, want, atol=1e-5)
    assert margin.shape == (2, 12) and bool(jnp.all(margin >= 0))


def test_the_cache_by_kind(served):
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    # K and V of every layer at full length and BESIDE them one 8-value index
    # key a token a layer, stored a whole lane row; no state, no ring, no
    # latent rows
    assert cache.state is None and cache.window is None and cache.latent is None
    assert cache.kv.k.stack.shape == (3, 2, 2, 128, 16)
    assert cache.index_keys.c.stack.shape == (3, 2, 1, 128, 128)
    assert cache.max_len == 128 and cache.index.shape == (2,)
    cfg = model.cfg
    # counted as held
    assert cache.index_keys.c.stack.nbytes == cfg.kv_bytes_by_kind(
        2, 128, jnp.bfloat16)["index_kv_bytes"]
    assert (KeyeSparseConfig().index_key_lanes, cfg.index_key_lanes) == \
        (128, 128)
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)
    with pytest.raises(ValueError, match="a prefill of"):
        model.apply({"params": served[1]}, served[2][:, :30],
                    cache=model.make_cache(3, 16, dtype=jnp.float32))
