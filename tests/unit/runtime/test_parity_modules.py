"""Tests for API-parity modules: DeepSpeedTransformerLayer, checkpoint
engines, Domino layer."""

import jax
import jax.numpy as jnp
import numpy as np


def test_transformer_layer_runs_and_trains():
    from deepspeed_tpu.ops.transformer import (
        DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)
    cfg = DeepSpeedTransformerConfig(hidden_size=64, heads=4,
                                     num_hidden_layers=1, pre_layer_norm=True)
    layer = DeepSpeedTransformerLayer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    out = layer.apply({"params": params}, x)
    assert out.shape == x.shape
    g = jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x) ** 2))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))
    # post-LN variant too
    cfg2 = DeepSpeedTransformerConfig(hidden_size=64, heads=4,
                                      pre_layer_norm=False, return_tuple=True)
    layer2 = DeepSpeedTransformerLayer(cfg2)
    p2 = layer2.init(jax.random.PRNGKey(2), x)["params"]
    assert layer2.apply({"params": p2}, x)[0].shape == x.shape


def test_checkpoint_engines_roundtrip(tmp_path):
    from deepspeed_tpu.runtime.checkpoint_engine import (
        AsyncCheckpointEngine, TorchCheckpointEngine)
    tree = {"w": jnp.arange(8.0), "nested": {"b": jnp.ones((3, 3))}}
    eng = TorchCheckpointEngine()
    eng.save(tree, str(tmp_path / "sync"))
    back = eng.load(str(tmp_path / "sync"))
    np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(8.0))

    a = AsyncCheckpointEngine()
    a.save(tree, str(tmp_path / "async"))
    assert a.commit("tag")
    back = a.load(str(tmp_path / "async"))
    np.testing.assert_array_equal(np.asarray(back["nested"]["b"]), np.ones((3, 3)))


def _as_mlp(fn):
    """`fn` under the layer's convention for its FFN: alone over the rows,
    with an array to give back over a pair of half-batches."""
    return lambda x, *held: (fn(x), *held) if held else fn(x)


def _dp2_tp2_layout():
    """dp2 x tp2 on four of the virtual devices, and the layout a layer's
    exchanges get there (runtime/domino/transformer.py)."""
    from deepspeed_tpu.runtime.domino import exchange_layout
    from deepspeed_tpu.utils import groups
    groups.reset_topology()
    groups.initialize(groups.MeshTopology(dp=2, tp=2,
                                          devices=jax.devices()[:4]))
    return exchange_layout(4, 2)


def test_domino_layer_matches_unsplit():
    """The interleaved walk over a PAIR of half-batches is the plain walk
    over the rows; `split_rows` cuts every device's own rows in two and
    `merge_rows` puts them back in their order."""
    from deepspeed_tpu.runtime.domino import (DominoTransformerLayer,
                                              merge_rows, split_rows)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    w_a = jax.random.normal(k1, (32, 32)) * 0.1
    w_m = jax.random.normal(k2, (32, 32)) * 0.1
    attn = lambda x: jnp.tanh(x @ w_a)
    mlp = lambda x: jnp.tanh(x @ w_m)
    layer = DominoTransformerLayer(attn, _as_mlp(mlp))
    x = jax.random.normal(k3, (4, 8, 32))
    h = x + attn(x)
    ref = h + mlp(h)
    np.testing.assert_allclose(np.asarray(layer(x)), np.asarray(ref),
                               rtol=1e-6)
    layout = _dp2_tp2_layout()
    halves = split_rows(x, layout)
    # dp2 holds rows (0, 1) and (2, 3): a half is one row of each device
    np.testing.assert_array_equal(np.asarray(halves[0]), np.asarray(x[::2]))
    np.testing.assert_array_equal(np.asarray(merge_rows(halves, layout)),
                                  np.asarray(x))
    np.testing.assert_allclose(
        np.asarray(merge_rows(layer(halves), layout)), np.asarray(ref),
        rtol=1e-6)


def test_llama_derived_half_batches_exact():
    """On dp2 x tp2 the block walks its rows as two half-batches with its
    reductions exchanged, decided by the mesh and the shapes alone (no
    config field), and is numerically EXACT vs the single-device block:
    batch rows are independent through the layer. What the 2x2 of v5e
    read of it is in runtime/domino/transformer.py."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    groups.reset_topology()
    cfg = llama.llama_config("llama-tiny", dtype=jnp.float32)
    model, params = llama.materialize_params(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 16)),
                      jnp.int32)
    ref = model.apply({"params": params}, ids)
    assert _dp2_tp2_layout() is not None
    assert llama._exchange_layout(cfg, ids.shape[0]) is not None
    got = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_domino_overlap_shape():
    """The interleave must create the dependency break AND name the
    reduction as an exchange: with a row-parallel product inside attn/mlp
    on dp2 x tp2, the pair walk traces FOUR `ppermute`s over `model`, each
    of one half-batch's partial product, and no `psum` (the form until PR
    53 traced four psums, which XLA all-reduces synchronously); the plain
    walk over the rows traces two whole-batch ones. The exchanges ARE what
    the chip's scheduler starts early (the schedule is the compiler's, not
    asserted)."""
    from jax.core import jaxprs_in_params
    from deepspeed_tpu.runtime.domino import (DominoTransformerLayer,
                                              merge_rows, row_parallel,
                                              split_rows)
    layout = _dp2_tp2_layout()
    B, S, D = 4, 8, 16
    w1 = jnp.ones((D, D), jnp.float32) * 0.01
    w2 = jnp.ones((D, D), jnp.float32) * 0.01
    dims = (((2,), (0,)), ((), ()))

    def layer(w1, w2):
        product = row_parallel(layout)
        return DominoTransformerLayer(
            attn_fn=lambda h: product(h, w1, dims),
            mlp_fn=_as_mlp(lambda h: product(h, w2, dims)))

    def run(x, w1, w2):
        return merge_rows(layer(w1, w2)(split_rows(x, layout)), layout)

    def exchanged_rows(fn):
        rows, psums = [], []

        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "ppermute":
                    rows.append(eqn.invars[0].aval.shape[0])
                if eqn.primitive.name in ("psum", "psum_invariant"):
                    psums.append(eqn)
                for sub in jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(fn)(x, w1, w2).jaxpr)
        assert not psums
        return rows

    x = jnp.ones((B, S, D), jnp.float32)
    # 4 half-batch exchanges (2 halves x attn+mlp), a device's one row each
    assert exchanged_rows(run) == [1, 1, 1, 1]
    assert exchanged_rows(lambda x, w1, w2: layer(w1, w2)(x)) == [2, 2]

    # numerical parity with the unsplit layer
    def unsplit(x):
        def dense(h, w):
            return (h.reshape(-1, D) @ w).reshape(h.shape)
        h = x + dense(x, w1)
        return h + dense(h, w2)
    got = jax.jit(run)(x, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(unsplit(x)),
                               rtol=1e-5, atol=1e-5)
