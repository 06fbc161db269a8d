"""Routing limited by groups (`sharded_moe.limit_to_groups`, `route_topk`)
against a plain loop over tokens, and `n_group` 1 against the routing that
was there before the choice existed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import _gating_core, route_scores, route_topk

E, K = 32, 4


def plain_loop(logits, bias, n_group, topk_group, k, scale):
    """(weights, expert ids) a token at a time, as the published routing is
    described: sigmoid scores; the choice on score + bias; a group's score
    the sum of its best two; the best groups stay; the best k experts inside
    them; weights the unbiased scores over their sum times the scale."""
    weights, ids = [], []
    for row in np.asarray(logits, np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        c = s + np.asarray(bias, np.float64)
        size = len(c) // n_group
        group_score = [np.sort(c[g * size:(g + 1) * size])[-2:].sum()
                       for g in range(n_group)]
        stays = np.argsort(group_score)[::-1][:topk_group]
        allowed = [e for e in range(len(c)) if e // size in stays]
        taken = sorted(allowed, key=lambda e: -c[e])[:k]
        w = s[taken] / s[taken].sum() * scale
        ids.append(taken)
        weights.append(w)
    return np.array(weights), np.array(ids)


@pytest.mark.parametrize("n_group,topk_group", [(1, 1), (8, 4), (8, 1)],
                         ids=["no_groups", "four_of_eight", "one_of_eight"])
def test_route_topk_is_the_plain_loop(n_group, topk_group):
    kl, kb = jax.random.split(jax.random.PRNGKey(n_group + topk_group))
    logits = jax.random.normal(kl, (64, E))
    bias = 0.3 * jax.random.normal(kb, (E,))
    w, idx = route_topk(logits, K, "sigmoid", bias, True, 2.5, n_group,
                        topk_group)
    want_w, want_idx = plain_loop(logits, bias, n_group, topk_group, K, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    if n_group == 1:
        # bit for bit the routing before the choice existed: the same call
        # without the two arguments, and the scores untouched
        w0, idx0 = route_topk(logits, K, "sigmoid", bias, True, 2.5)
        assert np.array_equal(np.asarray(w), np.asarray(w0))
        assert np.array_equal(np.asarray(idx), np.asarray(idx0))
        scores, chosen = route_scores(logits, "sigmoid", bias, 1, 1)
        assert np.array_equal(np.asarray(chosen),
                              np.asarray(scores + bias.astype(jnp.float32)))
    else:
        # the limit binds: without it some token takes another expert
        _, free = route_topk(logits, K, "sigmoid", bias, True, 2.5)
        assert not np.array_equal(np.asarray(idx), np.asarray(free))


def test_the_capacity_gate_takes_the_same_choice():
    logits = jax.random.normal(jax.random.PRNGKey(2), (40, E))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    _, idx = route_topk(logits, K, "sigmoid", bias, True, 2.5, 8, 4)
    core = _gating_core(logits, K, 1.0, 4, False, None, None, True, "sigmoid",
                        bias, 2.5, 8, 4)
    assert np.array_equal(np.asarray(core[2]), np.asarray(idx))


def test_groups_that_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="groups"):
        route_topk(jnp.zeros((2, 30)), 2, "sigmoid", None, True, 1.0, 8, 4)
