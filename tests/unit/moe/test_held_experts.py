"""An expert layer that is TOLD which experts it holds (`MoE.held_experts`):
sigmoid scores, a selection bias in the choice only, relu² experts with no
gate, a shared expert, and the share of a deployment's experts.

Tolerances: float32 throughout; the grouped GEMM, the expert buffer and the
dense sum below add the same products in another order: 1e-5 of the largest
output. A dropped term moves the output by a tenth of it or more.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import route_topk

D, E, K, F, FS = 32, 8, 3, 16, 24
TOL = 1e-5


def layer(offset=0, held=E, impl="gmm", shared=FS, **kw):
    return MoE(hidden_size=D, num_experts=E, k=K, intermediate_size=F,
               drop_tokens=False, dtype=jnp.float32, activation="relu2",
               dispatch_impl=impl, score_fn="sigmoid", selection_bias=True,
               bias_init=nn.initializers.normal(0.3),
               routed_scaling_factor=2.5, held_offset=offset,
               held_experts=held, shared_intermediate_size=shared, **kw)


@pytest.fixture(scope="module")
def whole():
    """The uncut layer's parameters and some tokens."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, D))
    params = nn.meta.unbox(layer().init(jax.random.PRNGKey(1), x,
                                        train=False)["params"])
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0   # logits of spread 1
    return params, x


def share_of(params, lo, count):
    """What the chip that holds experts lo .. lo+count-1 has of the tree."""
    out = dict(params)
    out["experts"] = {k: v[lo:lo + count] for k, v in params["experts"].items()}
    return out


def plain(params, x, lo=0, count=E, bias=True, scale=2.5):
    """The layer as the issue writes it, a dense sum over the held experts."""
    u = x.reshape(-1, D)
    s = jax.nn.sigmoid(u @ params["gate"]["wg"])
    c = s + (params["gate"]["bias"] if bias else 0.0)
    _, idx = jax.lax.top_k(c, K)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    out = jnp.zeros_like(u)
    for e in range(lo, lo + count):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        out += w_e[:, None] * (jnp.square(jax.nn.relu(
            u @ params["experts"]["up"][e])) @ params["experts"]["down"][e])
    sh = params["shared_expert"]
    out += jnp.square(jax.nn.relu(u @ sh["up"][0])) @ sh["down"][0]
    return out.reshape(x.shape)


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_the_uncut_layer_is_the_published_mathematics(whole, impl):
    params, x = whole
    got = layer(impl=impl).apply({"params": params}, x, train=False)
    assert rel(got, plain(params, x)) < TOL
    # and each term is there: without it the result is another
    assert rel(got, plain(params, x, bias=False)) > 0.05
    assert rel(got, plain(params, x, scale=1.0)) > 0.05


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_the_shares_add_up(whole, impl):
    """Two chips hold experts 0-3 and 4-7. Their parts of the result, the
    shared expert counted ONCE, are the uncut layer's; nothing stands in for
    the peer: a half's own part is that of its experts alone."""
    params, x = whole
    uncut = layer(impl=impl).apply({"params": params}, x, train=False)
    halves = [layer(lo, E // 2, impl).apply(
        {"params": share_of(params, lo, E // 2)}, x, train=False)
        for lo in (0, E // 2)]
    shared_once = plain(params, x, 0, 0)
    assert rel(halves[0] + halves[1] - shared_once, uncut) < TOL
    for lo, half in zip((0, E // 2), halves):
        assert rel(half, plain(params, x, lo, E // 2)) < TOL
    assert rel(halves[0], uncut) > 0.05          # a share is not the whole


def test_absent_assignments_are_dropped_before_dispatch_and_counted(whole):
    params, x = whole
    half = layer(0, E // 2)
    _, sown = half.apply({"params": share_of(params, 0, E // 2)}, x,
                         train=False, mutable=["counters"])
    _, idx = route_topk(x.reshape(-1, D) @ params["gate"]["wg"], K, "sigmoid",
                        params["gate"]["bias"])
    assert int(sown["counters"]["assignments"]) == x.shape[0] * x.shape[1] * K
    assert int(sown["counters"]["held_assignments"]) == int((idx < E // 2).sum())
    assert 0 < int(sown["counters"]["held_assignments"]) < idx.size


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_padding_rows_are_not_routed(whole, impl):
    """A row marked invalid has no assignment: it reaches no expert, is not
    counted, and gets the shared expert's output alone; the rows beside it
    are what they were."""
    params, x = whole
    valid = jnp.ones(x.shape[:2], bool).at[1, 4:].set(False)
    got, sown = layer(impl=impl).apply({"params": params}, x, train=False,
                                       valid=valid, mutable=["counters"])
    want = plain(params, x)
    assert rel(got[0], want[0]) < TOL and rel(got[1, :4], want[1, :4]) < TOL
    assert rel(got[1, 4:], plain(params, x, 0, 0)[1, 4:]) < TOL
    assert int(sown["counters"]["assignments"]) == int(valid.sum()) * K
    assert int(sown["counters"]["held_assignments"]) == int(valid.sum()) * K


def test_the_selection_bias_moves_the_choice_and_never_the_weights():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.array([0.0, 0.0, 0.0, 5.0])
    w, idx = route_topk(logits, 2, "sigmoid", bias, norm_topk_prob=False)
    assert sorted(idx[0].tolist()) == [0, 3]            # chosen by s + bias
    s = jax.nn.sigmoid(logits[0])
    assert jnp.allclose(jnp.sort(w[0]), jnp.sort(s[jnp.array([0, 3])]))
    w, idx = route_topk(logits, 2, "sigmoid", None, True, 2.5)
    assert sorted(idx[0].tolist()) == [0, 1]
    assert jnp.allclose(w.sum(), 2.5)


def test_softmax_routing_is_what_it_was():
    """`score_fn='softmax'` with no bias is the old gate bit for bit: the
    choice by the logits, the weights the softmax's, renormalised."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (7, E))
    w, idx = route_topk(logits, 2)
    p = jax.nn.softmax(logits, -1)
    _, want_idx = jax.lax.top_k(logits, 2)
    want = jnp.take_along_axis(p, want_idx, -1)
    assert jnp.array_equal(idx, want_idx)
    assert jnp.array_equal(w, want / want.sum(-1, keepdims=True))
