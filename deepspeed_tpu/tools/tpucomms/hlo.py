"""Post-SPMD HLO text parsing: collective extraction + replica-group
decoding back to canonical mesh axes.

stdlib-only (``re``, no jax/numpy import): the parser reads text, and
its tests run on any box. Everything jax-flavored (jaxpr fallback,
topology access) lives in ``fingerprint.py``.

What the parser understands (jax 0.4.37 → current ``compiled.as_text()``):

- the five collective instruction families — ``all-gather``,
  ``all-reduce``, ``reduce-scatter``, ``collective-permute``,
  ``all-to-all`` — in both their sync and ``-start``/``-done`` async
  spellings (``-done`` lines carry no shape/group info and are skipped;
  the ``-start`` result tuple's LAST element is the destination buffer);
- both ``replica_groups`` text forms: explicit ``{{0,1},{2,3}}`` and the
  iota form ``[num_groups,group_size]<=[dims]`` with an optional
  ``T(perm)`` transpose;
- ``source_target_pairs`` on collective-permute;
- computation blocks (lines ending ``{``) and ``body=%name`` references,
  so a collective can be classified as living inside a while-loop body —
  the GAS ``lax.scan`` compiles to ONE while loop, and XLA's LICM hoists
  loop-invariant param gathers into the entry computation, which is why
  static counting must know in-body from main-line.

Replica-group decoding: partition id ``p`` maps to mesh coordinates via
row-major unraveling over the canonical axis order
``('pipe','repl','data','expert','sequence','model')`` (``model``
innermost — TP pairs are consecutive ids). A group communicates over the
axes whose coordinates vary within it; the decode is *regular* when every
group is exactly the cartesian product of those axes' sizes.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

MESH_AXES: Tuple[str, ...] = ("pipe", "repl", "data", "expert", "sequence",
                              "model")

# HLO dtype token → bytes per element (default 4 for unknown tokens —
# wrong is better than crashed in a telemetry path; s4/u4 round up to 1).
DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

WIRE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
              "collective-permute", "all-to-all")


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction lifted out of the HLO text."""
    kind: str                       # one of WIRE_KINDS
    dtype: str                      # HLO dtype token of the result buffer
    shape: Tuple[int, ...]          # result (destination) shape
    replica_groups: Tuple[Tuple[int, ...], ...]  # () for permute
    source_target_pairs: Tuple[Tuple[int, int], ...]  # permute only
    computation: str                # enclosing computation name
    in_loop: bool                   # computation is a while-loop body

    @property
    def out_bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * DTYPE_BYTES.get(self.dtype, 4)

    @property
    def group_size(self) -> int:
        if self.replica_groups:
            return len(self.replica_groups[0])
        if self.source_target_pairs:
            return 2
        return 1

    @property
    def wire_bytes(self) -> int:
        """Per-device wire bytes under fixed conventions
        (chosen so the ideal ZeRO-3 schedule sums to exactly 3×P):
        all-gather = gathered output bytes; reduce-scatter = full input
        bytes (output × group); all-reduce = 2× operand bytes (its
        reduce-scatter + all-gather decomposition); permute / all-to-all
        = operand bytes."""
        if self.kind == "all-reduce":
            return 2 * self.out_bytes
        if self.kind == "reduce-scatter":
            return self.out_bytes * self.group_size
        return self.out_bytes


# ------------------------------------------------------------------ parsing

# `%name = TYPE op(` where TYPE is a shape or a tuple of shapes.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(?P<rtype>\([^)]*\)|[a-z][a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

# `%name (args) -> result {` opens a computation (ENTRY or region).
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")

_BODY_RE = re.compile(r"body=%?([\w.\-]+)")

_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{}\s]*\}\}|\{\}|"
    r"\[\d+,\d+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")

_PAIRS_RE = re.compile(r"source_target_pairs=\{([\d,{}\s]*)\}")

_IOTA_RE = re.compile(
    r"\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _parse_result_shape(rtype: str) -> Tuple[str, Tuple[int, ...]]:
    """dtype token + dims of the result buffer. For async-start tuple
    results the LAST element is the destination (the gathered/reduced
    buffer); sync results are a single shape."""
    shapes = _SHAPE_RE.findall(rtype)
    if not shapes:
        return "f32", ()
    dtype, dims = shapes[-1]
    shape = tuple(int(d) for d in dims.split(",") if d != "")
    return dtype, shape


def _parse_explicit_groups(text: str) -> Tuple[Tuple[int, ...], ...]:
    return tuple(
        tuple(int(x) for x in grp.split(",") if x.strip() != "")
        for grp in re.findall(r"\{([\d,\s]*)\}", text) if grp.strip() != "")


def _parse_iota_groups(text: str) -> Optional[Tuple[Tuple[int, ...], ...]]:
    m = _IOTA_RE.match(text)
    if not m:
        return None
    ng, gs = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",")]
    perm = [int(p) for p in m.group(4).split(",")] if m.group(4) \
        else list(range(len(dims)))
    # flatten iota(dims) transposed by perm, C order, without numpy
    t_shape = [dims[p] for p in perm]
    flat: List[int] = []

    def rec(prefix: List[int]) -> None:
        if len(prefix) == len(t_shape):
            idx = [0] * len(dims)
            for i, p in enumerate(perm):
                idx[p] = prefix[i]
            lin = 0
            for d, x in zip(dims, idx):
                lin = lin * d + x
            flat.append(lin)
            return
        for v in range(t_shape[len(prefix)]):
            rec(prefix + [v])

    rec([])
    if len(flat) != ng * gs:
        return None
    return tuple(tuple(flat[i * gs:(i + 1) * gs]) for i in range(ng))


def parse_replica_groups(text: str) -> Tuple[Tuple[int, ...], ...]:
    """Decode either replica_groups text form into explicit id tuples.
    ``{}`` (all devices, one group) decodes to () — callers substitute
    the full device set when they know the world size."""
    text = text.strip()
    if text == "{}":
        return ()
    iota = _parse_iota_groups(text)
    if iota is not None:
        return iota
    return _parse_explicit_groups(text)


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """All collective instructions in one optimized-HLO module dump, each
    tagged with its enclosing computation and whether that computation is
    a while-loop body."""
    bodies = set(_BODY_RE.findall(hlo_text))
    ops: List[CollectiveOp] = []
    computation = ""
    for line in hlo_text.splitlines():
        comp = _COMP_RE.match(line)
        if comp:
            computation = comp.group(1)
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        dtype, shape = _parse_result_shape(m.group("rtype"))
        gm = _GROUPS_RE.search(line)
        groups = parse_replica_groups(gm.group(1)) if gm else ()
        pm = _PAIRS_RE.search(line)
        pairs: Tuple[Tuple[int, int], ...] = ()
        if pm:
            pairs = tuple(
                (int(a), int(b))
                for a, b in re.findall(r"\{(\d+),(\d+)\}", pm.group(0)))
        ops.append(CollectiveOp(
            kind=m.group("op"), dtype=dtype, shape=shape,
            replica_groups=groups, source_target_pairs=pairs,
            computation=computation, in_loop=computation in bodies))
    return ops


# ----------------------------------------------------------- axis decoding


def partition_coords(p: int, sizes: Sequence[int]) -> Tuple[int, ...]:
    """Mesh coordinates of logical partition id ``p`` under canonical
    row-major order (last axis fastest-varying)."""
    out: List[int] = []
    for s in reversed(sizes):
        out.append(p % s)
        p //= s
    return tuple(reversed(out))


def _canonical_sizes(sizes_map: Dict[str, int]) -> Tuple[int, ...]:
    return tuple(int(sizes_map.get(ax, 1)) for ax in MESH_AXES)


def groups_to_axes(groups: Sequence[Sequence[int]],
                   sizes_map: Dict[str, int]
                   ) -> Tuple[Tuple[str, ...], bool]:
    """(axes, regular) for one collective's replica groups. ``axes`` are
    the canonical mesh axes whose coordinates vary inside any group;
    ``regular`` is False when a group is not exactly the cartesian
    product of those axes (a misplanned / axis-crossing group — callers
    surface it instead of trusting the axis attribution)."""
    sizes = _canonical_sizes(sizes_map)
    n_total = 1
    for s in sizes:
        n_total *= s
    if not groups:  # replica_groups={} — every device, one group
        groups = [tuple(range(n_total))]
    varying = set()
    for g in groups:
        coords = [partition_coords(p, sizes) for p in g]
        for d in range(len(MESH_AXES)):
            if len({c[d] for c in coords}) > 1:
                varying.add(d)
    axes = tuple(MESH_AXES[d] for d in sorted(varying))
    expect = 1
    for d in varying:
        expect *= sizes[d]
    regular = all(len(set(g)) == len(g) == expect for g in groups)
    return axes, regular


def pairs_to_axes(pairs: Sequence[Tuple[int, int]],
                  sizes_map: Dict[str, int]
                  ) -> Tuple[Tuple[str, ...], bool]:
    """Axes a collective-permute moves data over: the coordinates that
    differ between any source and its target. Always 'regular' — a
    permute has no product structure to validate."""
    sizes = _canonical_sizes(sizes_map)
    varying = set()
    for s, t in pairs:
        cs, ct = partition_coords(s, sizes), partition_coords(t, sizes)
        for d in range(len(MESH_AXES)):
            if cs[d] != ct[d]:
                varying.add(d)
    return tuple(MESH_AXES[d] for d in sorted(varying)), True


def op_axes(op: CollectiveOp, sizes_map: Dict[str, int]
            ) -> Tuple[Tuple[str, ...], bool]:
    if op.kind == "collective-permute":
        return pairs_to_axes(op.source_target_pairs, sizes_map)
    return groups_to_axes(op.replica_groups, sizes_map)
