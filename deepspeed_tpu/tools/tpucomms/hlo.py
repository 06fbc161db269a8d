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
  static counting must know in-body from main-line;
- every instruction of every computation (``parse_module``): its opcode,
  its ``metadata={op_name=...}``, the computations it references
  (``calls=``, ``to_apply=``, ``body=``, ``condition=``,
  ``branch_computations=``, ``called_computations=``), a fusion's
  ``kind=`` and a custom call's target. ``instruction_rows`` turns that
  into the table ``telemetry.program_map`` keeps: one row for each
  instruction the device line of a trace can name, with the scope it was
  traced under and what a fusion HOLDS (``holds``).

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

_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")

# `%name = TYPE opcode(`: nothing in a TYPE (tuples, `{1,0:T(8,128)S(1)}`
# tilings, `/*index=5*/` comments) is a space followed by `word(`, so the
# first such word after the `=` is the opcode.
_ANY_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rtype>.*?)\s"
    r"(?P<op>[a-z][\w\-]*)\(")

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_KIND_RE = re.compile(r"\bkind=(\w+)")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_REF_RE = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations|"
    r"called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_REF_NAME_RE = re.compile(r"%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _operands_end(tail: str) -> int:
    """Index in `tail` (the text after `opcode(`) of the parenthesis that
    closes the operand list."""
    depth = 0
    for i, ch in enumerate(tail):
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                return i
            depth -= 1
    return len(tail)


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


@dataclass(frozen=True)
class Instruction:
    """One instruction of one computation of an optimised module."""
    name: str                       # `fusion.123`, no `%`
    opcode: str                     # `fusion`, `while`, `all-reduce-start`
    computation: str                # the computation it lies in
    op_name: str                    # metadata op_name, "" where it has none
    operands: Tuple[str, ...]       # the instructions it reads, in order
    refs: Tuple[Tuple[str, str], ...]   # (attribute, computation) it names
    kind: str                       # a fusion's `kind=`, else ""
    target: str                     # a custom call's target, else ""
    collective: Optional[CollectiveOp]  # decoded, for the five families

    def called(self, *attrs: str) -> Tuple[str, ...]:
        return tuple(c for a, c in self.refs if a in attrs)


@dataclass(frozen=True)
class Module:
    name: str                       # `jit_train_batch`, as the trace prints it
    entry: str                      # the ENTRY computation
    computations: Dict[str, Tuple[Instruction, ...]]


def _collective_of(line: str, kind: str, rtype: str, computation: str,
                   in_loop: bool) -> CollectiveOp:
    dtype, shape = _parse_result_shape(rtype)
    gm = _GROUPS_RE.search(line)
    groups = parse_replica_groups(gm.group(1)) if gm else ()
    pm = _PAIRS_RE.search(line)
    pairs: Tuple[Tuple[int, int], ...] = ()
    if pm:
        pairs = tuple(
            (int(a), int(b))
            for a, b in re.findall(r"\{(\d+),(\d+)\}", pm.group(0)))
    return CollectiveOp(
        kind=kind, dtype=dtype, shape=shape, replica_groups=groups,
        source_target_pairs=pairs, computation=computation, in_loop=in_loop)


def parse_module(hlo_text: str) -> Module:
    """Every instruction of every computation of one optimised-HLO module
    dump (`compiled.as_text()`), in the text's order."""
    bodies = set(_BODY_RE.findall(hlo_text))
    name = entry = computation = ""
    comps: Dict[str, List[Instruction]] = {}
    for line in hlo_text.splitlines():
        if not name:
            mod = _MODULE_RE.match(line)
            if mod:
                name = mod.group(1)
                continue
        comp = _COMP_RE.match(line)
        if comp:
            computation = comp.group(1)
            comps.setdefault(computation, [])
            if line.lstrip().startswith("ENTRY"):
                entry = computation
            continue
        m = _ANY_INSTR_RE.match(line)
        if m is None:
            continue
        op, tail = m.group("op"), line[m.end():]
        end = _operands_end(tail)
        operands, tail = tuple(_OPERAND_RE.findall(tail[:end])), tail[end:]
        base = op[:-6] if op.endswith("-start") else op
        coll = _collective_of(line, base, m.group("rtype"), computation,
                              computation in bodies) \
            if base in WIRE_KINDS else None
        refs = tuple((attr, ref) for attr, val in _REF_RE.findall(tail)
                     for ref in _REF_NAME_RE.findall(val))
        meta, kind, target = (_OP_NAME_RE.search(tail), _KIND_RE.search(tail),
                              _TARGET_RE.search(tail))
        comps.setdefault(computation, []).append(Instruction(
            name=m.group("name"), opcode=op, computation=computation,
            op_name=meta.group(1) if meta else "", operands=operands,
            refs=refs,
            kind=kind.group(1) if kind and op == "fusion" else "",
            target=target.group(1) if target else "", collective=coll))
    return Module(name=name, entry=entry,
                  computations={k: tuple(v) for k, v in comps.items()})


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """All collective instructions in one optimized-HLO module dump, each
    tagged with its enclosing computation and whether that computation is
    a while-loop body. (`-done` lines carry no group and are no `-start`.)"""
    return [i.collective for instrs in parse_module(hlo_text
                                                    ).computations.values()
            for i in instrs if i.collective is not None]


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


# ------------------------------------------------------ instructions by scope

# what `holds` lists of a fused computation besides its collectives: the
# opcodes that say what kind of work the fusion is
HELD_OPCODES = ("dot", "convolution", "dynamic-update-slice", "sort",
                "gather", "scatter")
_JIT_PART_RE = re.compile(r"^p?jit\(.*\)$")
_SCOPE_NAME_RE = re.compile(r"[\w.\-]+")


def split_path(path: str) -> List[str]:
    """`a/transpose(jvp(b/c))/d` -> [`a`, `transpose(jvp(b/c))`, `d`]."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def scope_of(op_name: str) -> str:
    """An instruction's `op_name` with the `jit(...)` / `pjit(...)` parts of
    its path taken off: what is left is the `jax.named_scope` and flax
    module names, JAX's own `jvp(...)` / `transpose(jvp(...))` and, last,
    the primitive. Of a fusion's merged metadata (`a;b`) the first."""
    return "/".join(p for p in split_path(op_name.split(";", 1)[0])
                    if not _JIT_PART_RE.match(p))


def phase_of(scope: str) -> Optional[str]:
    """`bwd` under a `transpose(`, `fwd` under a `jvp(` alone, else None."""
    if "transpose(" in scope:
        return "bwd"
    return "fwd" if "jvp(" in scope else None


def scope_names(scope: str) -> Tuple[str, ...]:
    """Every name in a scope, the ones inside `jvp(...)` included."""
    return tuple(_SCOPE_NAME_RE.findall(scope))


def _held_label(i: Instruction, sizes_map: Optional[Dict[str, int]]
                ) -> Optional[str]:
    if i.collective is not None:
        if sizes_map is None:
            return i.opcode
        axes, _ = op_axes(i.collective, sizes_map)
        return f"{i.opcode}[{','.join(axes)}]"
    if i.opcode == "custom-call":
        return f"custom-call:{i.target}"
    return i.opcode if i.opcode in HELD_OPCODES else None


def instruction_rows(hlo_text: str,
                     sizes_map: Optional[Dict[str, int]] = None
                     ) -> Tuple[str, List[Dict[str, object]]]:
    """(module name, rows): one row for each instruction the device line
    of a profile can name, which is every instruction of the entry
    computation and of the computations control flow reaches from it
    (`while` bodies and conditions, a conditional's branches, a `call`'s
    target); the inside of a fusion runs as ONE op and is summed up in the
    fusion's `holds`.

    A row: `instr`, `module`, `opcode`, `scope` (`scope_of`), `phase`
    (`phase_of`), `loop` (the innermost `while` body it lies in, else
    None) and `holds`: for a `fusion`, a `call` or an async wrapper the
    sorted `HELD_OPCODES`, `custom-call:<target>` and collectives of the
    computation it calls, followed through nested calls; for any other
    instruction its own label, so that "is, or holds" is one test. A
    collective is written `all-reduce[data]`, `all-gather-start[data,model]`
    with the mesh axes `op_axes` decodes under `sizes_map` (bare without
    one). An instruction that carries no metadata of its own (a copy, a
    product the compiler placed) takes the scope of the first of its
    operands that has one, else, in a loop body, the `while`'s, and says
    so (`inferred`)."""
    mod = parse_module(hlo_text)
    comps = mod.computations

    held_of: Dict[str, Tuple[str, ...]] = {}

    def held(comp: str, seen: Tuple[str, ...] = ()) -> Tuple[str, ...]:
        if comp in held_of:
            return held_of[comp]
        out = set()
        for i in comps.get(comp, ()):
            label = _held_label(i, sizes_map)
            if label:
                out.add(label)
            for c in i.called("calls", "called_computations"):
                if c not in seen:
                    out.update(held(c, seen + (comp,)))
        held_of[comp] = tuple(sorted(out))
        return held_of[comp]

    rows: List[Dict[str, object]] = []
    # (computation, the loop body it lies in, the scope of that `while`)
    todo, done = [(mod.entry, None, "")], set()
    while todo:
        comp, loop, loop_scope = todo.pop()
        if comp in done or comp not in comps:
            continue
        done.add(comp)
        scopes: Dict[str, str] = {}     # of this computation's instructions
        for i in comps[comp]:
            scope, inferred = "", False
            if i.opcode != "parameter":   # its `op_name` is the argument's
                scope = scope_of(i.op_name)
                if not scope:
                    scope = next((scopes[o] for o in i.operands
                                  if scopes.get(o)), loop_scope)
                    inferred = bool(scope)
            scopes[i.name] = scope
            called = i.called("calls", "called_computations")
            if called and i.opcode != "custom-call":
                holds = tuple(sorted({h for c in called for h in held(c)}))
            else:
                label = _held_label(i, sizes_map)
                holds = (label,) if label else ()
            row: Dict[str, object] = {
                "instr": i.name, "module": mod.name, "opcode": i.opcode,
                "scope": scope, "phase": phase_of(scope), "loop": loop,
                "holds": list(holds)}
            if inferred:
                row["inferred"] = True
            rows.append(row)
            if i.opcode == "while":
                body = (i.called("body") or (loop,))[0]
                for c in i.called("body", "condition"):
                    todo.append((c, body, scope))
            elif i.opcode in ("call", "conditional"):
                for c in i.called("to_apply", "calls", "branch_computations"):
                    todo.append((c, loop, loop_scope))
    return mod.name, rows
