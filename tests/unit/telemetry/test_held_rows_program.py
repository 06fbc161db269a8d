"""What a held expert layer's bound on its sorted rows
(`moe/sharded_moe.held_row_bound`) leaves in the COMPILED programs of the two
families whose prefill it was written for (DeepSeek-V3.2, openPangu-Ultra), at
the tests' sizes with 2 of 16 experts held (top 4, hidden 64): read off the
compiled text by the program map's own rows (`tools/tpucomms/hlo.py`), as
`test_program_map.py` reads the engines' programs.

- a prefill chunk of 512 tokens is 2,048 assignments, sized for 512 rows:
  under `dispatch` and `combine`, outside the full-width body that a call
  whose held rows pass the bound falls back to, NO instruction has an operand
  or a result of `T x k` rows by `D`; inside that body they are what they
  were (so the test can tell);
- a decode step (4 assignments a row) is under the rule's floor: its program
  has no `conditional` of the layer's own (the `pl.when`s of the interpreted
  grouped GEMM under `experts`, and of the interpreted way back under
  `combine`, are the kernels', and the CPU's alone).
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import deepseek_sparse, openpangu
from deepspeed_tpu.moe.sharded_moe import held_row_bound, held_row_tile
from deepspeed_tpu.tools.tpucomms import hlo
from perfbench.manifest import Manifest
from tests.unit.models.hybrid_families import DEEPSEEK_SIZES, OPENPANGU_SIZES

FAMILIES = {
    "deepseek_sparse": (deepseek_sparse.DeepseekSparseForCausalLM,
                        DEEPSEEK_SIZES),
    "openpangu": (openpangu.OpenPanguForCausalLM, OPENPANGU_SIZES)}
CHUNK = 512


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def programs(request):
    """{tokens a call: (the parsed module, its rows by instruction, each
    instruction's line of the text)} of one family's prefill chunk and
    decode step, compiled on shapes alone."""
    cls, sizes = FAMILIES[request.param]
    # 2 of 16 held, an eighth: the rule's largest share (the families'
    # own tests hold 4, which the rule leaves unbounded)
    sizes = dict(sizes, n_routed_experts=2)
    cfg = Manifest().module("configs", request.param + "_adapter"
                            ).model_config(sizes, dtype=jnp.float32,
                                           dispatch_impl="gmm")
    model = cls(cfg)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = jax.eval_shape(lambda: model.make_cache(1, CHUNK + 128,
                                                    dtype=jnp.float32))
    out = {}
    for tokens in (CHUNK, 1):
        text = jax.jit(lambda p, i, c: model.apply(
            {"params": p}, i, cache=c, mutable=["counters"])).trace(
            params, jax.ShapeDtypeStruct((1, tokens), jnp.int32), cache
        ).lower().compile().as_text()
        lines = {m.group(1): line for line in text.splitlines()
                 if (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line))}
        out[tokens] = (hlo.instruction_rows(text)[1],
                       hlo.parse_module(text).computations, lines)
    return sizes, out


def _held(rows):
    return [r for r in rows if any(n.endswith("._held")
                                   for n in hlo.scope_names(r["scope"]))]


def _own_branches(rows):
    """The layer's own `conditional`s (the interpreted kernels' lie under
    `experts` and under `combine`)."""
    return [r for r in _held(rows) if r["opcode"] == "conditional"
            and not {"experts", "combine"} & set(hlo.scope_names(r["scope"]))]


def _reached_from(comps, start):
    """Names of the instructions of `start` and of every computation it
    calls."""
    names, todo, seen = set(), [start], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for i in comps[comp]:
            names.add(i.name)
            todo += [c for _, c in i.refs]
    return names


def test_a_prefill_chunk_moves_no_row_it_does_not_hold(programs):
    sizes, out = programs
    rows, comps, lines = out[CHUNK]
    k, d = sizes["num_experts_per_tok"], sizes["hidden_size"]
    held, scored = sizes["n_routed_experts"], sizes["router_experts"]
    bound = held_row_bound(CHUNK * k, held, scored,
                           held_row_tile(CHUNK * k, scored))
    assert bound == 2 * CHUNK * k * held // scored < CHUNK * k
    full_width = re.compile(rf"\[{CHUNK * k},{d}\]")
    sized = re.compile(rf"\[{bound},{d}\]")
    # ONE branch a layer, and it is the layer's own; `lax.cond`'s false
    # branch, the full-width body, is the first computation it names
    own = _own_branches(rows)
    assert len(own) == sizes["num_hidden_layers"] \
        - sizes["first_k_dense_replace"]
    by_name = {i.name: i for comp in comps.values() for i in comp}
    in_wide = set()
    for r in own:
        in_wide |= _reached_from(comps, by_name[r["instr"]].refs[0][1])
    moved = {"narrow": [], "wide": []}
    for r in _held(rows):
        if {"dispatch", "combine"} & set(hlo.scope_names(r["scope"])):
            moved["wide" if r["instr"] in in_wide else "narrow"].append(
                lines[r["instr"]])
    assert moved["narrow"] and moved["wide"]
    assert not [l for l in moved["narrow"] if full_width.search(l)]
    assert [l for l in moved["narrow"] if sized.search(l)]
    # the body a skewed call takes is today's: all T x k rows, gathered
    assert [l for l in moved["wide"] if full_width.search(l)]


def test_a_decode_step_s_program_has_no_branch(programs):
    rows = programs[1][1][0]
    held = _held(rows)
    assert held and {"dispatch", "combine"} <= {
        n for r in held for n in hlo.scope_names(r["scope"])}
    assert not _own_branches(rows)
