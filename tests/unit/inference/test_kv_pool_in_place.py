"""The stacked KV pool, addressed by layer and written in place (PR 29):
the PROGRAMS over it and their STRUCTURE, on the CPU (Pallas kernels in
interpret mode). The kernels themselves (each with layer `l` equals its
per-layer call on `pool[l]`; rows that hold nothing run no step; the writer
is the scatter) are `test_kv_pool_decode_kernel.py` and
`test_kv_pool_prefill_writer_kernels.py`: one file was one worker's chain
under `--dist loadfile` (PR 50).

- PROGRAMS: `prefill`, `decode`, `chunk_batch` and `fused_batch` of a v2
  engine return the same logits and the same WHOLE cache, bit for bit, as
  the engine whose model keeps the per-layer-view scan this PR replaced
  (`PerLayerViewLlama`: the old form, built from `update_layer` on views).
- STRUCTURE: the paged cached scan scans over no pool; a dense `KVCache` in
  the per-layer view is still scanned over as `(cache.k, cache.v)` and holds
  no layer operand (the stacked view, which `generate-batch` runs since PR
  42, has its own file: `test_dense_cache_in_place.py`).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (KVCache, PagedKVCache,
                                              decode_mask, quantize_kv_tokens)
from deepspeed_tpu.models.llama import (LlamaBlock, LlamaConfig,
                                        LlamaForCausalLM, RMSNorm,
                                        materialize_params)
from deepspeed_tpu.ops.attention import rope_cos_sin

# ----------------------------------------------------------------- programs


class PerLayerViewLlama(LlamaForCausalLM):
    """`LlamaForCausalLM` with the cached scan as it was before PR 29: the
    block scan scans over `(cache.k, cache.v)`, each layer gets a
    `PagedLayer` VIEW of its own pool (`layer=None`) and `update_layer`
    writes that view. The reference the layer-indexed scan must equal."""

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, cache=None):
        if cache is None:  # the engine's shape probe: the parent's own path
            return super().__call__(input_ids, labels, positions)
        cfg = self.cfg
        embed = self.param("embed_tokens", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        h = jnp.take(embed.astype(cfg.dtype), input_ids, axis=0)
        s = input_ids.shape[1]
        index = cache.index
        pos = index[:, None] + jnp.arange(s)[None, :]
        cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta, cfg.dtype)
        mask = decode_mask(pos, cache.max_len, window=cfg.sliding_window)
        scan = nn.scan(
            LlamaBlock, variable_axes={"params": 0},
            split_rngs={"params": True}, in_axes=(nn.broadcast, 0),
            out_axes=0, length=cfg.num_hidden_layers,
            metadata_params={nn.meta.PARTITION_NAME: "layers"})
        h, (k_new, v_new) = scan(cfg, name="layers")(
            h, (cos, sin, index, mask), (cache.k, cache.v))
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(h)
        return self._lm_head(h, embed), cache.replace(
            k=k_new, v=v_new, index=index + s)


CFG = LlamaConfig(vocab_size=96, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=3, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128,
                  dtype=jnp.float32)
MAX_BATCH, MAX_SEQ, BLOCK = 4, 48, 8


@pytest.fixture(scope="module")
def params():
    return materialize_params(CFG)[1]


def _engines(params, **kw):
    """(layer-indexed engine, per-layer-view engine), caches filled alike:
    random pools, every row owning distinct blocks, one table entry
    unowned."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    pair = []
    for cls in (LlamaForCausalLM, PerLayerViewLlama):
        groups.reset_topology()
        eng = InferenceEngineV2(cls(CFG), params=params, max_batch=MAX_BATCH,
                                max_seq_len=MAX_SEQ, cache_block_size=BLOCK,
                                **kw)
        rng = np.random.default_rng(11)
        c = eng.cache
        t = c.k.tables.shape[-1]
        tables = rng.permutation(c.num_blocks)[:MAX_BATCH * t].reshape(
            MAX_BATCH, t).astype(np.int32)
        tables[2, t - 1] = -1
        c = c.with_tables(jnp.asarray(tables))

        def fill(layer):
            pool = rng.standard_normal(layer.pool.shape)
            if layer.scales is None:
                return layer.replace(pool=jnp.asarray(pool, layer.pool.dtype))
            q, sc = quantize_kv_tokens(jnp.asarray(pool, jnp.float32))
            return layer.replace(pool=q, scales=sc)
        c = c.replace(k=fill(c.k), v=fill(c.v))
        eng.cache = jax.device_put(c, eng._cache_pin)
        pair.append(eng)
    return pair


def _same(got, want):
    ga, wa = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(ga) == len(wa)
    for g, w in zip(ga, wa):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _prefill(eng, rng):
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 16)), jnp.int32)
    return eng._prefill_fn(16)(eng.params, eng.cache, ids, jnp.int32(1),
                               jnp.int32(11))


def _decode(eng, rng):
    cap = eng.cache.max_len
    eng.cache = eng.cache.replace(index=jnp.asarray(   # row 3 parked
        [5, BLOCK, 2 * BLOCK + 3, cap], jnp.int32))
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (MAX_BATCH, 1)),
                       jnp.int32)
    active = jnp.asarray([True, True, True, False])
    return eng._decode_fn()(eng.params, eng.cache, toks, active)


def _chunk_rows(eng, rng, width):
    chunk, cap = eng.split_fuse_chunk, eng.cache.max_len
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, (width, chunk)),
                      jnp.int32)
    # a cursor mid-block, a block-aligned one, a parked row (the rest)
    slots = np.full((width,), MAX_BATCH, np.int32)
    starts = np.full((width,), cap, np.int32)
    valids = np.zeros((width,), np.int32)
    slots[:2], starts[:2], valids[:2] = (0, 2), (3, BLOCK), (chunk, chunk - 1)
    return ids, jnp.asarray(slots), jnp.asarray(starts), jnp.asarray(valids)


def _chunk_batch(eng, rng):
    return eng._chunk_batch_fn()(eng.params, eng.cache,
                                 *_chunk_rows(eng, rng, MAX_BATCH))


def _fused_batch(eng, rng):
    cap = eng.cache.max_len
    eng.cache = eng.cache.replace(index=jnp.asarray(
        [cap, 7, cap, 2 * BLOCK], jnp.int32))     # rows 1 and 3 decode
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (MAX_BATCH, 1)),
                       jnp.int32)
    active = jnp.asarray([False, True, False, True])
    from deepspeed_tpu.inference.v2.engine_v2 import chunk_row_widths
    width = chunk_row_widths(eng.max_batch)[0]
    return eng._fused_batch_fn(width)(eng.params, eng.cache, toks, active,
                                      *_chunk_rows(eng, rng, width))


PROGRAMS = {"prefill": _prefill, "decode": _decode,
            "chunk_batch": _chunk_batch, "fused_batch": _fused_batch}
ENGINES = {"f32": {}, "int8": {"kv_cache_dtype": "int8"},
           "chunk_is_block": {"split_fuse_chunk": BLOCK}}


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("engine", list(ENGINES))
def test_program_equals_the_per_layer_view_scan(params, engine, program):
    """Logits and the whole returned cache, bit for bit. `chunk_is_block`
    runs both scatters of `_update_paged_layer`'s `lax.cond`: the rows'
    cursors are not all aligned in `chunk_batch` (token scatter) and the
    prefill's are (block scatter)."""
    new, old = _engines(params, **ENGINES[engine])
    got = PROGRAMS[program](new, np.random.default_rng(5))
    want = PROGRAMS[program](old, np.random.default_rng(5))
    _same(got, want)
    cache = got[0]
    assert not np.array_equal(np.asarray(cache.k.pool[0]),
                              np.asarray(cache.k.pool[1]))


@pytest.mark.parametrize("program", ["prefill", "fused_batch"])
@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_program_equals_with_the_kernels_on(params, monkeypatch, engine,
                                            program):
    """The chip's path, interpreted: both paged kernels and the Pallas
    writer (chunk rows and `apply_stage`) inside the programs, on the
    stacked pool by layer index against per-layer views."""
    import deepspeed_tpu.ops.attention as attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    new, old = _engines(params, **ENGINES[engine])
    got = PROGRAMS[program](new, np.random.default_rng(5))
    want = PROGRAMS[program](old, np.random.default_rng(5))
    _same(got, want)
    jaxpr = str(jax.make_jaxpr(
        lambda c: c.apply_stage())(new.cache))
    assert "kv_write_paged" in jaxpr and "scatter" not in jaxpr


@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_decode_program_walks_no_grid_axis_of_the_tables_length(
        params, monkeypatch, engine):
    """PR 46: the `decode` program's `self_attn_paged_decode` call, once a
    layer inside the layer scan, has a grid of the batch's rows alone: no
    axis of the block table's length T, nor of rows x T."""
    import deepspeed_tpu.ops.attention as attention
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    eng, _ = _engines(params, **ENGINES[engine])
    t = eng.cache.k.tables.shape[-1]
    assert t > 1 and t != MAX_BATCH
    toks = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    active = jnp.ones((MAX_BATCH,), bool)
    jaxpr = jax.make_jaxpr(eng._decode_fn())(eng.params, eng.cache, toks,
                                             active)
    grids = [tuple(e.params["grid_mapping"].grid)
             for _, e in primitive_eqns(jaxpr.jaxpr, {"pallas_call"})
             if "self_attn_paged_decode" in str(
                 e.params.get("name") or e.params["name_and_src_info"])]
    assert grids == [(MAX_BATCH,)]


@pytest.mark.parametrize("engine", ["f32", "int8"])
def test_put_rounds_with_shared_prefix_blocks(params, engine):
    """A served sequence of rounds (prefill, chunks beside decodes, two
    prompts sharing their first blocks, a fork) leaves the two engines
    with the same tokens' logits and the same cache."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.utils import groups
    rng = np.random.default_rng(3)
    shared = list(map(int, rng.integers(0, CFG.vocab_size, 2 * BLOCK)))
    prompts = {1: shared + [5, 6, 7], 2: shared + [9, 8],
               3: list(map(int, rng.integers(0, CFG.vocab_size, 19)))}
    runs = []
    for cls in (LlamaForCausalLM, PerLayerViewLlama):
        groups.reset_topology()
        eng = InferenceEngineV2(cls(CFG), params=params, max_batch=MAX_BATCH,
                                max_seq_len=MAX_SEQ, cache_block_size=BLOCK,
                                split_fuse_chunk=BLOCK, prefix_sharing=True,
                                **ENGINES[engine])
        outs = [eng.put([1], [np.asarray(prompts[1])])]
        # the prompt's three chunks ran in that one round, three rows of
        # the four (PR 36); nothing is pending, and an empty put is empty
        assert list(outs[0]) == [1] and eng.put([], []) == {}
        outs.append(eng.put([2, 3], [np.asarray(prompts[2]),
                                     np.asarray(prompts[3])]))
        outs.append(eng.put([1], [[4]]))      # a decode beside their chunks
        outs.append(eng.put([], []))
        eng.fork(1, 4)
        for step in range(3):
            outs.append(eng.put([1, 2, 3, 4], [[step + 1]] * 4))
        assert eng.block_manager.prefix_hits >= 1
        runs.append((outs, eng.cache))
    (got, got_cache), (want, want_cache) = runs
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for uid in g:
            np.testing.assert_array_equal(np.asarray(g[uid]),
                                          np.asarray(w[uid]))
    _same(got_cache, want_cache)


def test_int8_chunks_keep_their_scales(params):
    """A prompt served into an int8 pool leaves its tokens' scales in the
    cache (the merge of a chunk program's rows dropped them before PR 29:
    the pool held int8 values under unit scales)."""
    new, _ = _engines(params, kv_cache_dtype="int8")
    before = np.asarray(new.cache.k.scales)
    cache, _ = _chunk_batch(new, np.random.default_rng(2))
    assert (np.asarray(cache.k.scales) != before).any()


# ---------------------------------------------------------------- structure


def _scans(jaxpr):
    from deepspeed_tpu.tools.tpuverify.jaxpr_util import primitive_eqns
    return [e for _, e in primitive_eqns(jaxpr, ["scan"])]


def _scanned(eqn):
    """Shapes of a scan's scanned inputs and outputs."""
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    return ([tuple(v.aval.shape) for v in eqn.invars[skip:]],
            [tuple(v.aval.shape) for v in
             eqn.outvars[eqn.params["num_carry"]:]])


@pytest.mark.parametrize("staged,s", [(True, 1), (False, 1), (True, 4)],
                         ids=["staged_decode", "unstaged_decode", "chunk"])
def test_paged_scan_scans_over_no_pool(params, staged, s):
    model = LlamaForCausalLM(CFG)
    cache = PagedKVCache.create(CFG.num_hidden_layers, 2, 32, 2, CFG.head_dim,
                                num_blocks=6, block_size=BLOCK,
                                dtype=jnp.float32, staged=staged)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: model.apply({"params": p}, i, cache=c))(
        params, jnp.zeros((2, s), jnp.int32), cache)
    pool = tuple(cache.k.pool.shape)
    scans = [e for e in _scans(jaxpr) if e.params["length"] == pool[0]]
    assert scans
    for eqn in scans:
        xs, ys = _scanned(eqn)
        assert pool not in xs and pool not in ys
        skip = eqn.params["num_consts"]
        carry = [tuple(v.aval.shape) for v in
                 eqn.invars[skip:skip + eqn.params["num_carry"]]]
        consts = [tuple(v.aval.shape) for v in eqn.invars[:skip]]
        # a pass that can write the pools carries them; staged decode
        # cannot, and closes over them
        writes = not (staged and s == 1)
        assert (carry.count(pool), consts.count(pool)) == (
            (2, 0) if writes else (0, 2))


def test_dense_scan_is_the_parents(params):
    """The per-layer view of the dense cache (`KVCache.create`: int8 caches,
    the v2 slot layout, the other families): it still scans over
    `(cache.k, cache.v)`, and nothing of the paged protocol (a layer
    index) has entered it."""
    model = LlamaForCausalLM(CFG)
    cache = KVCache.create(CFG.num_hidden_layers, 2, 32, 2, CFG.head_dim,
                           dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: model.apply({"params": p}, i, cache=c))(
        params, jnp.zeros((2, 1), jnp.int32), cache)
    (eqn,) = [e for e in _scans(jaxpr)
              if e.params["length"] == CFG.num_hidden_layers]
    xs, ys = _scanned(eqn)
    assert xs.count(tuple(cache.k.shape)) == 2
    assert ys.count(tuple(cache.k.shape)) == 2
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    assert not [v for v in eqn.invars[skip:]
                if v.aval.shape == (CFG.num_hidden_layers,)
                and jnp.issubdtype(v.aval.dtype, jnp.integer)]
