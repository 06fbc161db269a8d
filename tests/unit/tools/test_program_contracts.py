"""tpuverify unit tests: one violating + one clean fixture per contract,
CLI exit codes over a monkeypatched matrix, and (slow) the real
tiny-model matrices traced clean end-to-end.

The fixtures are tiny hand-built jits — each violating one reproduces the
incident class its contract encodes (undonated state, uncommitted cache
leaf, per-layer eager scatters, host callback in a traced body, rogue
shard_map, unregistered program). shard_map fixtures are make_jaxpr-only:
on the old-jaxlib sandboxes actually COMPILING manual-region programs can
SIGABRT XLA:CPU, and the contract needs only the jaxpr.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.tools.tpuverify import all_contracts, verify
from deepspeed_tpu.tools.tpuverify.core import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)
from deepspeed_tpu.tools.tpuverify.put import (
    CompiledRecord,
    EngineUnderTest,
    ProgramUnderTest,
)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _put(fn, args, **kw):
    kw.setdefault("name", "fixture")
    return ProgramUnderTest(fn=fn, args=tuple(args), **kw)


def _ids(violations):
    return sorted({v.contract for v in violations})


# ------------------------------------------------------- donation-aliasing


def test_donation_violating():
    def step(state, batch):
        return state + batch.sum()

    put = _put(jax.jit(step), [_sds((8, 8)), _sds((8,))], donate=(0,))
    out = verify([put], contracts=["donation-aliasing"])
    assert _ids(out) == ["donation-aliasing"]
    assert "not donated" in out[0].message


def test_donation_clean():
    def step(state, batch):
        return state + batch.sum()

    put = _put(jax.jit(step, donate_argnums=(0,)),
               [_sds((8, 8)), _sds((8,))], donate=(0,))
    assert verify([put], contracts=["donation-aliasing"]) == []


def test_donation_skips_non_lowerable():
    # capacity bind_key callables have no .lower — contract must skip
    put = _put(lambda s: s, [_sds((4,))], donate=(0,))
    assert verify([put], contracts=["donation-aliasing"]) == []


# --------------------------------------------------------- pinned-sharding


def _engine(pinned_trees, records=(), detector=None, **kw):
    from deepspeed_tpu.telemetry.recompile import RecompileDetector
    return EngineUnderTest(name="fixture-engine",
                           detector=detector or RecompileDetector(),
                           records=list(records),
                           pinned_trees=list(pinned_trees), **kw)


def test_pinned_sharding_violating():
    # a bare jnp array is uncommitted — exactly the leaf class that
    # silently recompiled serving programs in r4
    eng = _engine([("cache", {"k": jnp.zeros((4, 8))})])
    out = verify([eng], contracts=["pinned-sharding"])
    assert _ids(out) == ["pinned-sharding"]
    assert "uncommitted" in out[0].message


def test_pinned_sharding_clean():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    leaf = jax.device_put(jnp.zeros((4, 8)),
                          NamedSharding(mesh, PartitionSpec()))
    eng = _engine([("cache", {"k": leaf})])
    assert verify([eng], contracts=["pinned-sharding"]) == []


def test_pinned_sharding_bulk_signature_violating():
    from deepspeed_tpu.telemetry.recompile import RecompileDetector
    det = RecompileDetector()
    det.record_signatures = True
    det.observe("decode", (jnp.zeros((64, 64)),))  # bulk + uncommitted
    eng = _engine([], detector=det)
    out = verify([eng], contracts=["pinned-sharding"])
    assert out and "entered uncommitted" in out[0].message
    # small leaves (per-call ids/rng) stay under bulk_bytes: no finding
    det2 = RecompileDetector()
    det2.record_signatures = True
    det2.observe("decode", (jnp.zeros((2, 8), jnp.int32),))
    assert verify([_engine([], detector=det2)],
                  contracts=["pinned-sharding"]) == []


# --------------------------------------------------- kv-scatter-discipline

_CACHE = ((4, 2, 8, 16), "float32")  # (L, B, M, D) toy cache


def test_kv_scatter_violating():
    # the r4 incident: one eager scatter per layer instead of staging
    def decode(cache, tok):
        for layer in range(4):
            cache = cache.at[layer, :, 3].set(tok)
        return cache

    put = _put(jax.jit(decode), [_sds(_CACHE[0]), _sds((2, 16))],
               cache_shapes=frozenset({_CACHE}))
    out = verify([put], contracts=["kv-scatter-discipline"])
    assert _ids(out) == ["kv-scatter-discipline"]
    assert "4 scatters" in out[0].message


def test_kv_scatter_clean_batched():
    def decode(cache, toks):
        # ONE batched scatter landing every layer
        return cache.at[:, :, 3].set(toks)

    put = _put(jax.jit(decode), [_sds(_CACHE[0]), _sds((4, 2, 16))],
               cache_shapes=frozenset({_CACHE}))
    assert verify([put], contracts=["kv-scatter-discipline"]) == []


def test_kv_scatter_ignores_int32_tables():
    # cursors/tables are int32 — excluded from the discipline
    def bump(tables):
        for i in range(4):
            tables = tables.at[i].set(i)
        return tables

    put = _put(jax.jit(bump), [_sds((4, 8), jnp.int32)],
               cache_shapes=frozenset({((4, 8), "int32")}))
    assert verify([put], contracts=["kv-scatter-discipline"]) == []


def test_scan_body_counts_per_step():
    # per-layer writes inside ONE scan body count once per step aval
    def decode(cache, toks):
        def body(c, layer_tok):
            i, tok = layer_tok
            return c.at[i % 4, :, 3].set(tok), ()

        cache, _ = jax.lax.scan(
            body, cache, (jnp.arange(4), toks))
        return cache

    put = _put(jax.jit(decode), [_sds(_CACHE[0]), _sds((4, 2, 16))],
               cache_shapes=frozenset({_CACHE}))
    assert verify([put], contracts=["kv-scatter-discipline"]) == []


# ------------------------------------------------------- kv-pool-in-place

_POOL = ((3, 2, 4, 8, 16), "float32")  # (L, Hkv, NB, BS, D) toy pool
_POOLS = frozenset({_POOL})


def _pool_put(fn, extra=(), donate=(0,), **kw):
    return _put(jax.jit(fn, donate_argnums=donate),
                [_sds(_POOL[0]), *extra], pool_shapes=_POOLS, **kw)


def _layer_write(pool, layer, tok):
    """One token row into `[layer, :, slot 5]` of the stacked pool."""
    flat = pool.reshape(3, 2, 32, 16)
    return flat.at[layer, :, 5].set(tok).reshape(pool.shape)


def _by_index(pool, toks):
    def body(p, x):
        layer, tok = x
        return _layer_write(p, layer, tok), ()
    return jax.lax.scan(body, pool, (jnp.arange(3), toks))[0]


def _by_scanning_the_pool(pool, toks):
    def body(_, x):
        p, tok = x                       # one layer's pool, cut out
        return (), p.reshape(2, 32, 16).at[:, 5].set(tok).reshape(p.shape)
    return jax.lax.scan(body, (), (pool, toks))[1]


def _by_slicing(pool, toks):
    for layer in range(3):
        p = jax.lax.dynamic_index_in_dim(pool, layer, keepdims=False)
        p = p.reshape(2, 32, 16).at[:, 5].set(toks[layer]).reshape(p.shape)
        pool = jax.lax.dynamic_update_index_in_dim(pool, p, layer, 0)
    return pool


_TOKS = [_sds((3, 2, 16))]


def test_kv_pool_in_place_clean():
    put = _pool_put(_by_index, _TOKS)
    assert verify([put], contracts=["kv-pool-in-place"]) == []


@pytest.mark.parametrize("fn,what", [
    (_by_scanning_the_pool, "scans over"),
    (_by_slicing, "dynamic_slice of a pool"),
    (_by_slicing, "dynamic_update_slice of a pool"),
], ids=["scanned", "sliced", "written_back"])
def test_kv_pool_in_place_violating(fn, what):
    out = verify([_pool_put(fn, _TOKS)], contracts=["kv-pool-in-place"])
    assert _ids(out) == ["kv-pool-in-place"]
    assert any(what in v.message for v in out)


def test_kv_pool_in_place_wants_every_pool_aliased():
    # the pool comes back, but the program was not given its buffer
    put = _pool_put(_by_index, _TOKS, donate=())
    out = verify([put], contracts=["kv-pool-in-place"])
    assert len(out) == 1 and "not aliased" in out[0].message
    # two pools of one shape, one of them read only: one loose argument
    def one_of_two(k, v, toks):
        return _by_index(k, toks), v.sum()
    put = _put(jax.jit(one_of_two, donate_argnums=(0, 1)),
               [_sds(_POOL[0]), _sds(_POOL[0]), *_TOKS], pool_shapes=_POOLS)
    out = verify([put], contracts=["kv-pool-in-place"])
    assert len(out) == 1 and out[0].message.startswith("1 pool argument")


def test_kv_pool_in_place_needs_pool_shapes():
    # a program that names no pool (v1, train, a slot-layout engine)
    put = _put(jax.jit(_by_scanning_the_pool), [_sds(_POOL[0]), *_TOKS])
    assert verify([put], contracts=["kv-pool-in-place"]) == []


# the stacked dense cache of a v1 generate program (PR 42): held inside the
# token loop, where the parent's program cut every layer out each step

_STACK = ((3, 2, 2, 8, 16), "float32")  # (L, B, Hkv, M, D) toy stack
_STEPS = 5


def _token_loop(step):
    """A generate-shaped program: the stack made inside, a token scan that
    carries it through `step(stack, tok) -> stack`."""
    def gen(toks):
        stack = jnp.zeros(_STACK[0], jnp.float32)
        return jax.lax.scan(lambda s, t: (step(s, t), ()), stack, toks)[0]
    return _put(jax.jit(gen), [_sds((_STEPS, 3, 2, 2, 16))],
                stack_shapes=frozenset({_STACK}), token_loop=_STEPS)


def _land(stack, tok):
    """One write a step: every layer's token at slot 5 of each row."""
    return stack.at[:, jnp.arange(2), :, 5].set(jnp.moveaxis(tok, 1, 0))


def _step_by_index(stack, tok):
    def layer(h, l):   # reads the stack by index, writes nothing
        return h + jnp.take(stack, l, axis=0).sum(), ()
    h, _ = jax.lax.scan(layer, 0.0, jnp.arange(3))
    return _land(stack, tok + h)


def _step_scanning_the_stack(stack, tok):
    def layer(_, x):
        k, t = x        # one layer's cache, cut out and written back
        return (), k.at[jnp.arange(2), :, 5].set(t)
    return jax.lax.scan(layer, (), (stack, tok))[1]


def _step_slicing(stack, tok):
    k = jax.lax.dynamic_index_in_dim(stack, tok[0, 0, 0, 0].astype(jnp.int32),
                                     keepdims=False)
    return _land(stack, tok + k.sum())


def _step_relaying(stack, tok):
    k = jnp.swapaxes(jnp.take(stack, 1, axis=0), 1, 2)   # (B, M, Hkv, D)
    return _land(stack, tok + k[:, 0].sum())


def _step_through_a_kernel(aliased):
    from jax.experimental import pallas as pl

    def kernel(s_ref, o_ref):
        o_ref[...] = s_ref[...] + 1.0

    def step(stack, tok):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
            input_output_aliases={0: 0} if aliased else {},
            interpret=True)(stack)
    return step


def test_dense_stack_in_place_clean():
    for step in (_step_by_index, _step_through_a_kernel(True)):
        assert verify([_token_loop(step)], contracts=["kv-pool-in-place"]) == []


@pytest.mark.parametrize("step,what", [
    (_step_scanning_the_stack, "scans over"),
    (_step_slicing, "dynamic_slice of a cache-shaped"),
    (_step_relaying, "transpose of a cache-shaped"),
    (_step_through_a_kernel(False), "does not alias"),
], ids=["scanned", "sliced", "re-laid", "kernel_copy"])
def test_dense_stack_in_place_violating(step, what):
    out = verify([_token_loop(step)], contracts=["kv-pool-in-place"])
    assert _ids(out) == ["kv-pool-in-place"]
    assert any(what in v.message for v in out)


def test_dense_stack_in_place_is_not_vacuous():
    # a program that names a stack but has no token loop holding one
    put = _token_loop(_step_by_index)
    put.token_loop = _STEPS + 1
    out = verify([put], contracts=["kv-pool-in-place"])
    assert len(out) == 1 and "nothing to hold" in out[0].message


def test_v1_generate_keeps_its_dense_cache_in_place():
    """A v1 generate program (`jit_ds_v1_generate_b2_s8_n4`: one module a key
    since PR 56) of a tiny llama as the chip dispatches it: the
    token loop holds the decode kernel and the writer on the carried stack,
    and no finding. Budget: K and V, one stack each; one writer a step."""
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    from deepspeed_tpu.tools.tpuverify.put import build_v1_chip_dispatch_put
    put = build_v1_chip_dispatch_put()
    assert len(put.stack_shapes) == 1 and put.token_loop == 3
    assert verify([put]) == []
    (loop,) = [e for _, e in primitive_eqns(put.jaxpr(), {"scan"})
               if e.params["length"] == put.token_loop]
    names = [str(e.params.get("name") or e.params["name_and_src_info"])
             for _, e in primitive_eqns(loop.params["jaxpr"], {"pallas_call"})]
    assert sum("kv_write_dense" in n for n in names) == 1
    assert sum("self_attn_dense_decode" in n for n in names) == 1
    stack = next(iter(put.stack_shapes))[0]
    carried = [v for v in loop.invars[loop.params["num_consts"]:]
               if tuple(v.aval.shape) == stack]
    assert len(carried) == 2


def test_the_parents_v1_program_fails_kv_pool_in_place():
    """The per-layer view, which a v1 generate program held before PR 42 (a
    model with no say in its cache still gets it): scanned over in the
    token loop, and re-laid for the kernel."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.tools.tpuverify.put import build_v1_chip_dispatch_put

    class PerLayerView(LlamaForCausalLM):
        make_cache = None

    out = verify([build_v1_chip_dispatch_put(PerLayerView)],
                 contracts=["kv-pool-in-place"])
    assert any("scans over" in v.message for v in out)
    assert any("transpose of a cache-shaped" in v.message for v in out)


def test_kv_scatter_budget_counts_the_stacked_target():
    """The chunk scatter now targets the STACKED aval from inside the
    layer scan: `scatter_target_shapes` lists it (and its token-flat
    view), so the budget still counts one K and one V scatter a body, and
    a third is still a finding."""
    from deepspeed_tpu.inference.kv_cache import scatter_target_shapes
    shapes = scatter_target_shapes({"k": _sds(_POOL[0])})
    assert ((3, 2, 32, 16), "float32") in shapes

    def chunk(k, v, toks, extra):
        def body(kv, x):
            layer, tok = x
            k, v = kv
            k, v = _layer_write(k, layer, tok), _layer_write(v, layer, tok)
            if extra:
                k = _layer_write(k, layer, tok + 1)
            return (k, v), ()
        return jax.lax.scan(body, (k, v), (jnp.arange(3), toks))[0]

    for extra, want in ((False, []), (True, ["kv-scatter-discipline"])):
        put = _put(jax.jit(lambda k, v, t: chunk(k, v, t, extra)),
                   [_sds(_POOL[0]), _sds(_POOL[0]), *_TOKS],
                   cache_shapes=shapes)
        assert _ids(verify([put], contracts=["kv-scatter-discipline"])) == want


# -------------------------------------------------------- no-host-callback


def test_host_callback_violating():
    def step(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    put = _put(jax.jit(step), [_sds((4,))])
    out = verify([put], contracts=["no-host-callback"])
    assert _ids(out) == ["no-host-callback"]
    assert "host-escape" in out[0].message


def test_host_callback_pure_callback_violating():
    def step(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2, _sds((4,)), x)
        return y + 1

    put = _put(jax.jit(step), [_sds((4,))])
    assert _ids(verify([put], contracts=["no-host-callback"])) == \
        ["no-host-callback"]


def test_host_callback_clean():
    put = _put(jax.jit(lambda x: x * 2), [_sds((4,))])
    assert verify([put], contracts=["no-host-callback"]) == []


# -------------------------------------------------- manual-region-allowlist


def _shard_map_put(**kw):
    from jax.sharding import Mesh, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    if not hasattr(jax, "shard_map"):
        pytest.skip("no jax.shard_map on this jax")
    mesh = Mesh(np.array(devs[:2]), ("data",))

    def fn(x):
        return jax.shard_map(lambda v: v * 2, mesh=mesh,
                             in_specs=P("data"), out_specs=P("data"))(x)

    # make_jaxpr only — never compile manual regions on this jaxlib
    return _put(fn, [_sds((8, 4))], **kw)


def test_shard_map_violating():
    put = _shard_map_put()
    out = verify([put], contracts=["manual-region-allowlist"])
    assert _ids(out) == ["manual-region-allowlist"]


def test_shard_map_allowlisted_clean():
    put = _shard_map_put(allow_shard_map=True)
    assert verify([put], contracts=["manual-region-allowlist"]) == []


def test_plain_program_clean():
    put = _put(jax.jit(lambda x: x + 1), [_sds((4,))])
    assert verify([put], contracts=["manual-region-allowlist"]) == []


# -------------------------------------------------- registration-coverage


def test_registration_violations():
    from deepspeed_tpu.telemetry.recompile import RecompileDetector
    det = RecompileDetector()
    det.observe("v1:generate:b2", (jnp.zeros((2, 8), jnp.int32),))
    eng = _engine(
        [],
        records=[
            CompiledRecord("ok", "v1:generate:b2"),
            CompiledRecord("untracked", None),
            CompiledRecord("unobserved", "v1:generate:b4"),
        ],
        detector=det)
    out = verify([eng], contracts=["registration-coverage"])
    msgs = "\n".join(v.message for v in out)
    assert len(out) == 2
    assert "no RecompileDetector identity" in msgs
    assert "never observed" in msgs


def test_registration_clean():
    from deepspeed_tpu.telemetry.recompile import RecompileDetector
    det = RecompileDetector()
    det.observe("train:train_batch", (jnp.zeros((4,)),))
    eng = _engine(
        [],
        records=[CompiledRecord("train:train_batch", "train:train_batch")],
        detector=det)
    assert verify([eng], contracts=["registration-coverage"]) == []


def test_residency_coverage_violating():
    # an engine whose placement path skipped MemoryPlane.register — both
    # the params and (non-train) kv_cache rows are missing
    eng = _engine([], residency={"params": 0, "kv_cache": 0})
    out = verify([eng], contracts=["residency-coverage"])
    assert len(out) == 2 and _ids(out) == ["residency-coverage"]
    assert any("params" in v.message for v in out)
    assert any("kv_cache" in v.message for v in out)


def test_residency_coverage_clean_and_train_exempt_from_kv():
    eng = _engine([], residency={"params": 4096, "kv_cache": 512})
    assert verify([eng], contracts=["residency-coverage"]) == []
    train = EngineUnderTest(name="train", detector=None, records=[],
                            pinned_trees=[],
                            residency={"params": 4096, "kv_cache": 0})
    assert verify([train], contracts=["residency-coverage"]) == []


# ------------------------------------------------------- core + baseline


def test_unknown_contract_raises():
    with pytest.raises(KeyError):
        verify([], contracts=["no-such-contract"])


def test_contract_catalog_complete():
    assert sorted(all_contracts()) == [
        "donation-aliasing", "kv-pool-in-place", "kv-scatter-discipline",
        "manual-region-allowlist", "no-host-callback",
        "pinned-sharding", "registration-coverage", "residency-coverage"]
    for contract in all_contracts().values():
        assert contract.doc and contract.incident


def test_baseline_round_trip(tmp_path):
    v1 = Violation("donation-aliasing", "train:train_batch", "msg a")
    v2 = Violation("pinned-sharding", "v2", "msg b")
    path = str(tmp_path / ".tpuverify-baseline.json")
    save_baseline(path, [v1, v2])
    baseline = load_baseline(path)
    assert new_violations([v1, v2], baseline) == []
    v3 = Violation("no-host-callback", "v1", "msg c")
    assert new_violations([v1, v3], baseline) == [v3]


# --------------------------------------------------------------- the CLI


def _fake_matrix(violating):
    def build(include=("train", "v1", "v2")):
        if violating:
            def step(state, batch):
                return state + batch.sum()
            return [ProgramUnderTest(
                name="fake:step", fn=jax.jit(step),
                args=(_sds((4, 4)), _sds((4,))), donate=(0,))]
        return [ProgramUnderTest(name="fake:ok",
                                 fn=jax.jit(lambda x: x + 1),
                                 args=(_sds((4,)),))]
    return build


def test_cli_exit_codes(monkeypatch, tmp_path):
    from deepspeed_tpu.tools.tpuverify import put as put_mod
    from deepspeed_tpu.tools.tpuverify.cli import main

    monkeypatch.chdir(tmp_path)  # no repo baseline in scope
    monkeypatch.setattr(put_mod, "build_default_matrix",
                        _fake_matrix(violating=False))
    assert main(["--no-baseline"]) == 0

    monkeypatch.setattr(put_mod, "build_default_matrix",
                        _fake_matrix(violating=True))
    assert main(["--no-baseline"]) == 1
    assert main(["--select", "bogus-contract"]) == 2

    # baseline flow: grandfather the violation, then exit 0
    baseline = str(tmp_path / "bl.json")
    assert main(["--update-baseline", "--baseline", baseline]) == 0
    assert main(["--baseline", baseline]) == 0


def test_cli_list_contracts(capsys):
    from deepspeed_tpu.tools.tpuverify.cli import main
    assert main(["--list-contracts"]) == 0
    out = capsys.readouterr().out
    assert "donation-aliasing" in out and "registration-coverage" in out


def test_cli_unknown_component(monkeypatch):
    from deepspeed_tpu.tools.tpuverify.cli import main
    assert main(["--include", "nonsense"]) == 2


# ------------------------------------------------- the real matrix (slow)


@pytest.mark.slow
def test_train_matrix_clean():
    from deepspeed_tpu.tools.tpuverify.put import build_default_matrix
    assert verify(build_default_matrix(include=("train",))) == []


@pytest.mark.slow
def test_v1_matrix_clean_and_nonvacuous():
    from deepspeed_tpu.tools.tpuverify.put import build_default_matrix
    from deepspeed_tpu.tools.tpuverify.contracts import _kv_shapes
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import \
        count_cache_scatters
    puts = build_default_matrix(include=("v1",))
    assert verify(puts) == []
    progs = [p for p in puts if p.kind == "program" and p.cache_shapes]
    assert progs
    counted = sum(
        sum(count_cache_scatters(p.jaxpr(),
                                 _kv_shapes(p.cache_shapes)).values())
        for p in progs)
    assert counted > 0, "kv-scatter contract is vacuous on v1"


@pytest.mark.slow
def test_v2_matrix_clean_and_nonvacuous():
    from deepspeed_tpu.tools.tpuverify.put import build_default_matrix
    from deepspeed_tpu.tools.tpuverify.contracts import _kv_shapes
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import \
        count_cache_scatters
    puts = build_default_matrix(include=("v2",))
    assert verify(puts) == []
    progs = [p for p in puts if p.kind == "program" and p.cache_shapes]
    counted = sum(
        sum(count_cache_scatters(p.jaxpr(),
                                 _kv_shapes(p.cache_shapes)).values())
        for p in progs)
    assert counted > 0, "kv-scatter contract is vacuous on v2"
