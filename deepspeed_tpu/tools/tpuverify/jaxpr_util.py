"""jaxpr/lowered-program introspection helpers for tpuverify.

Everything here is static: walking eqns of a (recursively nested) jaxpr
and reading the input-output aliasing of an AOT ``.lower()``ed program.
No compiles, no dispatches — safe on any backend, including the old-jaxlib
sandboxes where actually *running* shard_map programs can SIGABRT XLA:CPU.
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Set, Tuple

try:  # jax >= 0.5 moved the core types
    from jax.extend import core as _jcore  # type: ignore
    _Jaxpr = _jcore.Jaxpr
    _ClosedJaxpr = _jcore.ClosedJaxpr
except Exception:  # pragma: no cover - version-dependent import path
    from jax import core as _jcore  # type: ignore
    _Jaxpr = _jcore.Jaxpr
    _ClosedJaxpr = _jcore.ClosedJaxpr

# Host-escape primitives: any of these inside a hot-path program means a
# device→host→device round trip per step (pure_callback / io_callback /
# jax.debug.callback lower to a callback eqn; jax.debug.print has its own
# debug_print primitive).
CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback", "debug_callback",
                            "debug_print"})

# The scatter family as it appears in decode jaxprs. dynamic_update_slice
# is included: XLA lowers cursor-indexed cache writes to either form, and
# the per-step cost class is the same.
SCATTER_PRIMS = frozenset({"scatter", "scatter-add", "scatter-mul",
                           "scatter-min", "scatter-max",
                           "dynamic_update_slice"})

SHARD_MAP_PRIMS = frozenset({"shard_map"})


def _as_jaxpr(obj):
    if isinstance(obj, _ClosedJaxpr):
        return obj.jaxpr
    if hasattr(obj, "jaxpr") and isinstance(getattr(obj, "jaxpr"), _Jaxpr):
        return obj.jaxpr
    return obj


def _sub_jaxprs(eqn) -> Iterator[Tuple[str, object]]:
    """(param-name, sub-jaxpr) pairs of one eqn — scan/while bodies, cond
    branches (each branch is its OWN body), pjit/custom_* calls."""
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            if isinstance(v, (_ClosedJaxpr, _Jaxpr)):
                tag = name if len(vals) == 1 else f"{name}[{i}]"
                yield tag, _as_jaxpr(v)


def iter_bodies(jaxpr, path: str = "") -> Iterator[Tuple[str, object]]:
    """Yield (path, jaxpr) for the top-level jaxpr and every nested body.
    A 'body' is one straight-line jaxpr: a scan body executes per step, a
    cond branch executes per taken branch — so per-body counting is what
    the one-scatter-per-step contract needs (two cond *branches* each
    scattering once is one scatter per step, not two)."""
    jaxpr = _as_jaxpr(jaxpr)
    yield path or "<top>", jaxpr
    for eqn in jaxpr.eqns:
        for tag, sub in _sub_jaxprs(eqn):
            sub_path = f"{path}/{eqn.primitive.name}:{tag}" if path \
                else f"{eqn.primitive.name}:{tag}"
            yield from iter_bodies(sub, sub_path)


def iter_eqns(jaxpr) -> Iterator[Tuple[str, object]]:
    """Flat (body-path, eqn) stream over every body."""
    for path, body in iter_bodies(jaxpr):
        for eqn in body.eqns:
            yield path, eqn


def primitive_eqns(jaxpr, names: Iterable[str]) -> List[Tuple[str, object]]:
    """Every eqn whose primitive name is in ``names``, with its body path."""
    names = frozenset(names)
    return [(path, eqn) for path, eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in names]


def count_cache_scatters(
        jaxpr, cache_shapes: Iterable[Tuple[Tuple[int, ...], str]]
) -> Dict[Tuple[str, Tuple[Tuple[int, ...], str]], int]:
    """{(body-path, (shape, dtype)): scatter count} over scatter-family
    eqns whose OUTPUT aval matches a cache buffer shape — the operational
    definition of 'a scatter into the KV cache'."""
    targets: Set[Tuple[Tuple[int, ...], str]] = set(cache_shapes)
    counts: Dict[Tuple[str, Tuple[Tuple[int, ...], str]], int] = {}
    for path, body in iter_bodies(jaxpr):
        for eqn in body.eqns:
            if eqn.primitive.name not in SCATTER_PRIMS:
                continue
            for outvar in eqn.outvars:
                aval = getattr(outvar, "aval", None)
                if aval is None:
                    continue
                sd = (tuple(aval.shape), str(aval.dtype))
                if sd in targets:
                    key = (path, sd)
                    counts[key] = counts.get(key, 0) + 1
    return counts


# --------------------------------------------------------- lowered programs


def donated_leaves(lowered, argnum: int) -> Tuple[int, int]:
    """(donated, total) array-leaf counts of positional arg ``argnum`` in
    an AOT-lowered program's args_info."""
    import jax
    info = lowered.args_info
    # args_info mirrors the call as (args, kwargs) on this jax — unwrap to
    # the positional tuple (we never lower with kwargs)
    if isinstance(info, tuple) and len(info) == 2 \
            and isinstance(info[1], dict) and not info[1]:
        info = info[0]
    leaves = jax.tree_util.tree_leaves(info[argnum])
    total = len(leaves)
    donated = sum(1 for leaf in leaves if getattr(leaf, "donated", False))
    return donated, total


def aliasing_output_count(lowered) -> int:
    """How many inputs the lowered program aliases to outputs
    (``tf.aliasing_output`` attributes in the StableHLO text) — the
    ground truth that donation actually reached the compiler, not just
    the jit spec."""
    try:
        text = lowered.as_text()
    except Exception:
        return -1  # not introspectable on this jax — treat as unknown
    return text.count("tf.aliasing_output")


def arg_aliasing(lowered) -> List[Tuple[Tuple[int, ...], str, bool]]:
    """(shape, MLIR element type, aliased to an output?) of every argument
    of the lowered program's entry function, in order, read from its
    StableHLO text (`%argN: tensor<2x8x16xf32> {tf.aliasing_output = 0 ...}`);
    empty where the text cannot be had."""
    import re
    try:
        text = lowered.as_text()
    except Exception:
        return []
    head = re.search(r"func\.func public @main\((.*?)\)\s*->", text, re.S)
    if head is None:
        return []
    out = []
    # one chunk an argument: its attributes nest braces (shardings)
    for chunk in re.split(r"%arg\d+: ", head.group(1))[1:]:
        m = re.match(r"tensor<([^>]*)>", chunk)
        if m is None:
            continue
        *dims, elt = m.group(1).split("x")
        out.append((tuple(int(d) for d in dims), elt,
                    "tf.aliasing_output" in chunk))
    return out
