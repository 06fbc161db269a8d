"""comm facade tests (reference tests/unit/comm/test_dist.py): the traced
collectives must work inside shard_map manual regions, and the host-plane
surface must report correct sizes.

The `no-set-mesh` pragmas below answer a tpulint rule that outlived its
reason (ROADMAP C2 retires it; see docs/static_analysis.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.utils import groups


@pytest.fixture
def mesh():
    return Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))


def _smap(fn, mesh, in_specs, out_specs, axes):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=axes, check_vma=False)


def test_all_reduce_ops(mesh):
    x = jnp.arange(8.0).reshape(4, 2)

    for op, expect in [(comm.ReduceOp.SUM, x.sum(0)),
                       (comm.ReduceOp.AVG, x.mean(0)),
                       (comm.ReduceOp.MAX, x.max(0)),
                       (comm.ReduceOp.MIN, x.min(0))]:
        f = _smap(lambda v, op=op: comm.all_reduce(v[0], op=op, group="data"),
                  mesh, P("data"), P(), {"data"})
        with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
            out = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def test_all_gather_reduce_scatter_all_to_all(mesh):
    x = jnp.arange(16.0).reshape(4, 4)

    f = _smap(lambda v: comm.all_gather(v[0], group="data", axis=0),
              mesh, P("data"), P(), {"data"})
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        g = jax.jit(f)(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(x.reshape(-1)))

    f = _smap(lambda v: comm.reduce_scatter(v[0], group="data", scatter_dim=0),
              mesh, P("data"), P("data"), {"data"})
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        rs = jax.jit(f)(jnp.broadcast_to(x.reshape(-1), (4, 16)))
    np.testing.assert_array_equal(np.asarray(rs), 4 * np.arange(16.0))

    f = _smap(lambda v: comm.all_to_all_single(v[0], group="data",
                                               split_axis=0, concat_axis=0),
              mesh, P("data"), P("data"), {"data"})
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        a2a = jax.jit(f)(x)
    np.testing.assert_array_equal(np.asarray(a2a),
                                  np.asarray(x).T.reshape(-1))


@pytest.mark.parametrize("group,scatter_dim", [
    ("data", 0), ("data", 1), (("data", "model"), 1), ("model", 0)],
    ids=["data_dim0", "data_dim1", "data_x_model", "model"])
def test_reduce_scatter_by_exchange_is_reduce_scatter(mesh, group, scatter_dim):
    """`n - 1` permutes of the peers' slices and a local sum give
    `psum_scatter`'s result over one axis or two, along either dimension;
    bf16 partials summed in float32 are the float32 sum of the partials
    (nothing is rounded between two of them)."""
    axes = (group,) if isinstance(group, str) else group
    spec = P(axes)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 24))

    def run(fn, x, **kw):
        out_spec = P(*([None] * scatter_dim + [axes]))
        f = jax.shard_map(lambda v: fn(v[0], group=group,
                                       scatter_dim=scatter_dim, **kw),
                          mesh=mesh, in_specs=spec, out_specs=out_spec,
                          check_vma=False)
        return jax.jit(f)(x)

    n = int(np.prod([mesh.shape[a] for a in axes]))
    x = x[:n]
    np.testing.assert_allclose(
        np.asarray(run(comm.reduce_scatter_by_exchange, x)),
        np.asarray(run(comm.reduce_scatter, x)), rtol=1e-6, atol=1e-6)
    half = x.astype(jnp.bfloat16)
    out = run(comm.reduce_scatter_by_exchange, half, sum_dtype=jnp.float32)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(half.astype(jnp.float32).sum(0)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n", [2, 4])
def test_all_reduce_by_exchange_is_psum(n, dtype):
    """Over two ranks a permute each way and one local add give `psum`'s
    bits on BOTH ranks (`a + b` is `b + a`), in the partials' own dtype;
    past `EXCHANGE_MAX_RANKS` the exchange's bytes lose to the ring's and
    the function is `psum` itself, with no permute in its trace."""
    mesh = Mesh(np.asarray(jax.devices()).reshape(8 // n, n),
                ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(5), (n, 16, 24)).astype(dtype)

    def run(fn):
        return jax.shard_map(lambda v: fn(v[0], group="model")[None],
                             mesh=mesh, in_specs=P("model"),
                             out_specs=P("model"), check_vma=False)

    got = jax.jit(run(comm.all_reduce_by_exchange))(x)
    want = jax.jit(run(lambda v, group: comm.all_reduce(v, group=group)))(x)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for rank in range(1, n):    # every rank holds the same sum
        np.testing.assert_array_equal(np.asarray(got[rank], np.float32),
                                      np.asarray(got[0], np.float32))
    traced = str(jax.make_jaxpr(run(comm.all_reduce_by_exchange))(x))
    assert ("ppermute" in traced) == (n <= comm.comm.EXCHANGE_MAX_RANKS)
    assert ("psum" in traced) == (n > comm.comm.EXCHANGE_MAX_RANKS)


def test_ppermute_ring(mesh):
    f = _smap(lambda v: comm.ppermute(
        v[0], perm=[(i, (i + 1) % 4) for i in range(4)], group="data"),
        mesh, P("data"), P("data"), {"data"})
    x = jnp.arange(4.0)[:, None]
    with jax.set_mesh(mesh):  # tpulint: disable=no-set-mesh
        out = jax.jit(f)(x)
    np.testing.assert_array_equal(np.asarray(out).reshape(-1), [3, 0, 1, 2])


def test_world_size_and_groups():
    groups.reset_topology()
    groups.initialize(dp=2, sp=2, tp=2)
    assert comm.get_world_size() == 8
    assert comm.get_world_size("sequence") == 2
    assert comm.get_world_size(("data", "sequence")) == 4  # product, not len
    assert comm.get_rank() == 0
    comm.barrier()
