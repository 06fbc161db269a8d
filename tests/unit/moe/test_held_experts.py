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


# ------------------------------------------- rows sized by the chip's share
# `held_dispatch_gmm` under a bound on the sorted rows (`held_row_bound`):
# the narrow body, the full-width body it falls back to, the expert buffer
# (`held_dispatch_ragged`) and a float32 dense sum, on the same inputs.
NT, NK, NE, TILE = 288, 4, 16, 16          # 1,152 assignments: past the rule's 1,024


def _narrow_inputs(routing, count, padded):
    """(x, weights (T, k), expert ids (T, k), valid, the held experts' FFNs)."""
    ks = jax.random.split(jax.random.PRNGKey(59), 5)
    x = jax.random.normal(ks[0], (NT, D))
    gate, idx = jax.lax.top_k(jax.nn.sigmoid(
        jax.random.normal(ks[1], (NT, NE))), NK)
    if routing == "one_expert":        # every token's first: held expert 0
        idx = jnp.concatenate([jnp.zeros_like(idx[:, :1]),
                               1 + idx[:, 1:] % (NE - 1)], axis=1)
    elif routing == "absent":          # none on a held one
        idx = count + idx % (NE - count)
    valid = None
    if padded:
        valid = jnp.arange(NT) % 5 != 3
    ws = {"up": jax.random.normal(ks[2], (count, D, F)) * 0.3,
          "down": jax.random.normal(ks[3], (count, F, D)) * 0.3}
    return x, gate, idx.astype(jnp.int32), valid, ws


def _grouped(ws):
    from deepspeed_tpu.ops.pallas.grouped_gemm import grouped_gemm

    def fn(rows, sizes):
        h = grouped_gemm(rows, ws["up"], sizes, tiling=(TILE, D, F))
        return grouped_gemm(jnp.square(jax.nn.relu(h)), ws["down"], sizes,
                            tiling=(TILE, F, D))
    return fn


def _buffered(ws):
    def fn(buf):                       # (count, T, D), the batched form
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", buf, ws["up"])))
        return jnp.einsum("ecf,efd->ecd", h, ws["down"])
    return fn


def _dense_sum(x, gate, idx, valid, ws, count):
    out = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        if valid is not None:
            w_e = jnp.where(valid, w_e, 0.0)
        out += w_e[:, None] * (jnp.square(jax.nn.relu(x @ ws["up"][e]))
                               @ ws["down"][e])
    return out


# (id, held of 16, routing, padding rows, the bound: "rule" or an offset from
#  the held rows' own number, the full-width body runs)
NARROW = [
    ("share_1_16", 1, "drawn", False, "rule", False),
    ("share_1_8", 2, "drawn", False, "rule", False),
    ("share_1_4", 4, "drawn", False, "rule", False),    # the rule: no bound
    ("share_1_4_bounded", 4, "drawn", False, 40, False),
    ("share_1_2", 8, "drawn", False, "rule", False),    # the rule: no bound
    ("padding_rows", 2, "drawn", True, "rule", False),
    ("held_at_the_bound", 2, "drawn", False, 0, False),
    ("held_one_past_the_bound", 2, "drawn", False, -1, True),
    ("padding_one_past", 2, "drawn", True, -1, True),
    ("no_held_row", 2, "absent", False, "rule", False),
    ("all_on_one_held_expert", 1, "one_expert", False, "rule", True),
]


@pytest.mark.parametrize("case", NARROW, ids=[c[0] for c in NARROW])
def test_a_held_layer_moves_the_rows_it_holds(case):
    """Whatever the bound, the result is the full-width body's and the
    buffer path's within float32 rounding of the dense sum (the narrow body
    adds a token's terms by held expert, in pairs); past the bound the
    full-width body runs, bit for bit, and is counted; nothing is dropped."""
    from deepspeed_tpu.moe import sharded_moe as sm
    _, count, routing, padded, at, runs_wide = case
    x, gate, idx, valid, ws = _narrow_inputs(routing, count, padded)
    held = (idx < count) if valid is None else (idx < count) & valid[:, None]
    n = int(held.sum())
    bound = sm.held_row_bound(NT * NK, count, NE, TILE) if at == "rule" \
        else n + at
    if at == "rule" and count <= NE // 8:
        assert bound == 2 * NT * NK * count // NE and bound % TILE == 0
    elif at == "rule":
        assert bound == NT * NK                         # today's program
    run = jax.jit(sm.held_dispatch_gmm, static_argnames=(
        "offset", "count", "grouped_fn", "bound"))
    got, n_got, wide = run(x, gate, idx, 0, count, _grouped(ws), valid, bound)
    full, n_full, never = run(x, gate, idx, 0, count, _grouped(ws), valid,
                              None)
    buf, n_buf = sm.held_dispatch_ragged(x, gate, idx, 0, count,
                                         _buffered(ws), valid)
    want = _dense_sum(x, gate, idx, valid, ws, count)
    assert int(n_got) == int(n_full) == int(n_buf) == n
    assert (int(wide), int(never)) == (int(runs_wide), 0)
    assert runs_wide == (n > bound)
    scale = max(float(jnp.abs(want).max()), 1e-6)
    for other in (full, buf, want):
        assert float(jnp.abs(got - other).max()) / scale < TOL
    if runs_wide:
        assert jnp.array_equal(got, full)
    if routing == "absent":
        assert n == 0 and not jnp.any(got)
    if routing == "one_expert":
        assert n == NT > bound                          # no assignment lost


@pytest.mark.parametrize("skewed", [False, True], ids=["seeded", "skewed"])
def test_the_layer_sizes_its_rows_by_its_share_and_counts_the_wide_calls(
        skewed):
    """A layer that holds 1 of 16 experts, 1,152 assignments a call: the
    bound comes from the shapes (no option: 256 rows, a row tile), the
    result is the expert buffer's, and a router that sends every token to
    the held expert takes the full-width body and says so
    (`held_wide_calls`)."""
    def moe(impl):
        return MoE(hidden_size=D, num_experts=NE, k=NK, intermediate_size=F,
                   drop_tokens=False, dtype=jnp.float32, activation="silu",
                   dispatch_impl=impl, score_fn="sigmoid",
                   selection_bias=True, held_offset=4, held_experts=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, NT // 2, D))
    params = nn.meta.unbox(moe("gmm").init(jax.random.PRNGKey(4), x,
                                           train=False)["params"])
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0
    if skewed:
        params["gate"]["bias"] = params["gate"]["bias"].at[4].set(9.0)
    got, sown = moe("gmm").apply({"params": params}, x, train=False,
                                 mutable=["counters"])
    want, other = moe("ragged").apply({"params": params}, x, train=False,
                                      mutable=["counters"])
    assert rel(got, want) < TOL
    counted = sown["counters"]
    assert int(counted["held_wide_calls"]) == int(skewed)
    assert int(other["counters"]["held_wide_calls"]) == 0
    assert int(counted["held_assignments"]) == \
        int(other["counters"]["held_assignments"])
    if skewed:
        assert int(counted["held_assignments"]) == NT   # one of a token's 4


# ---------------------------------- no gather or scatter round the GEMMs
ROUTERS = [
    ("softmax", "softmax", False, 1, 1),
    ("sigmoid", "sigmoid", False, 1, 1),         # `top_k`'s own values
    ("sigmoid_select_bias", "sigmoid", True, 1, 1),
    ("softmax_select_bias", "softmax", True, 1, 1),
    ("sigmoid_groups", "sigmoid", False, 4, 2),
    ("sigmoid_select_bias_groups", "sigmoid", True, 4, 2),
]


@pytest.mark.parametrize("router", ROUTERS, ids=[r[0] for r in ROUTERS])
@pytest.mark.parametrize("norm", [True, False], ids=["normed", "raw"])
def test_the_chosen_weights_are_the_gathered_scores_bit_for_bit(router, norm):
    """`route_topk` picks the chosen experts' scores with no gather (a select
    and a sum of one term, or the top-k's own values where it was taken of
    the scores): the same bits as `take_along_axis` of the scores, for every
    score function, with a selection bias and with groups."""
    from deepspeed_tpu.moe.sharded_moe import route_scores
    _, score_fn, biased, n_group, topk_group = router
    ks = jax.random.split(jax.random.PRNGKey(61), 2)
    logits = jax.random.normal(ks[0], (37, 16)) * 2.0
    bias = jax.random.normal(ks[1], (16,)) * 0.3 if biased else None
    got, idx = route_topk(logits, 3, score_fn, bias, norm, 2.5, n_group,
                          topk_group)
    scores, chosen_by = route_scores(logits, score_fn, bias, n_group,
                                     topk_group)
    _, want_idx = jax.lax.top_k(chosen_by, 3)
    want = jnp.take_along_axis(scores, want_idx, axis=-1)
    if norm:
        want = want / jnp.maximum(want.sum(-1, keepdims=True), 1e-20)
    assert jnp.array_equal(idx, want_idx)
    assert jnp.array_equal(got, want * 2.5)
    assert float(got.min()) > 0.0


@pytest.mark.parametrize("count,padded,routing", [
    (1, False, "drawn"), (2, True, "drawn"), (4, False, "drawn"),
    (8, True, "drawn"), (2, False, "absent"), (1, False, "one_expert")])
def test_the_counts_are_bincounts_without_a_scatter(count, padded, routing):
    """`held_group_sizes` (a compare and a sum) is `bincount` of the held
    experts' local ids, and the layer's `experts_touched` the number of its
    non-empty bins, padding rows and absent assignments in no bin."""
    from deepspeed_tpu.moe import sharded_moe as sm
    x, gate, idx, valid, ws = _narrow_inputs(routing, count, padded)
    held, local = sm.held_assignments(idx, 0, count, valid)
    want = jnp.bincount(local.reshape(-1), length=count + 1)[:count]
    got = jax.jit(sm.held_group_sizes, static_argnums=1)(local, count)
    assert got.dtype == jnp.int32 and jnp.array_equal(got, want)
    assert int(got.sum()) == int(held.sum())
    _, n_held, _ = sm.held_dispatch_gmm(x, gate, idx, 0, count, _grouped(ws),
                                        valid)
    assert int(n_held) == int(want.sum())

    moe = MoE(hidden_size=D, num_experts=NE, k=NK, intermediate_size=F,
              drop_tokens=False, dtype=jnp.float32, activation="relu2",
              dispatch_impl="gmm", score_fn="sigmoid", held_offset=0,
              held_experts=count)
    xs = x.reshape(2, NT // 2, D)
    params = nn.meta.unbox(moe.init(jax.random.PRNGKey(4), xs,
                                    train=False)["params"])
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0
    ok = None if valid is None else valid.reshape(2, NT // 2)
    _, sown = moe.apply({"params": params}, xs, train=False, valid=ok,
                        mutable=["counters"])
    _, chosen = route_topk(x @ params["gate"]["wg"], NK, "sigmoid")
    mine = sm.held_assignments(chosen, 0, count, valid)[1]
    bins = jnp.bincount(mine.reshape(-1), length=count + 1)[:count]
    assert int(sown["counters"]["experts_touched"]) == int((bins > 0).sum())
    assert int(sown["counters"]["held_assignments"]) == int(bins.sum())
    # 1,152 assignments over 16 experts: a row tile of 256, K tiles kept, and
    # no grid step that finds its weights resident
    assert int(sown["counters"]["weight_tile_revisits"]) == 0


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_a_decode_sized_call_counts_the_row_tiles_a_group_straddles(impl):
    """15 tokens x top 4 over 16 experts, 8 held: a row tile of 16 and about
    30 held rows, so a group reaches a second row tile. The layer's
    `weight_tile_revisits` is the count `held_group_sizes` gives by the rule
    (`grouped_gemm.weight_tile_revisits`), and 0 through the buffer path,
    which runs no grouped GEMM."""
    import numpy as np
    from deepspeed_tpu.moe import sharded_moe as sm
    t, count = 15, 8
    assert sm.held_row_tile(t * NK, NE) == 16
    moe = MoE(hidden_size=D, num_experts=NE, k=NK, intermediate_size=F,
              drop_tokens=False, dtype=jnp.float32, activation="relu2",
              dispatch_impl=impl, score_fn="sigmoid", held_offset=0,
              held_experts=count)
    x = jax.random.normal(jax.random.PRNGKey(66), (t, 1, D))
    params = nn.meta.unbox(moe.init(jax.random.PRNGKey(4), x,
                                    train=False)["params"])
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0
    _, sown = moe.apply({"params": params}, x, train=False,
                        mutable=["counters"])
    _, chosen = route_topk(x[:, 0] @ params["gate"]["wg"], NK, "sigmoid")
    sizes = np.bincount(np.asarray(sm.held_assignments(chosen, 0, count)[1])
                        .reshape(-1), minlength=count + 1)[:count]
    ends = np.cumsum(sizes)
    want = sum(int((e - 1) // 16 - (e - s) // 16)
               for e, s in zip(ends, sizes) if s)
    assert want > 0
    assert int(sown["counters"]["weight_tile_revisits"]) == \
        (want if impl == "gmm" else 0)
