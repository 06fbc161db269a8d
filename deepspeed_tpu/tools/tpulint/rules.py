"""The tpulint rules — each one a CLAUDE.md/docs invariant distilled to AST.

Rule ids, the prose invariant each encodes, and the incident it prevents
are cataloged in docs/static_analysis.md. Keep messages LINE-FREE and
deterministic: the baseline keys on (rule, path, message).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set

from deepspeed_tpu.tools.tpulint.astutil import (
    TracedIndex,
    build_alias_map,
    dotted_chain,
    loop_body_nodes,
    resolve,
)
from deepspeed_tpu.tools.tpulint.core import Finding, LintContext, Rule, register


def _f(rule: Rule, ctx: LintContext, node: ast.AST, message: str,
       fix: Optional[str] = None) -> Finding:
    return Finding(rule=rule.id, path=ctx.path,
                   line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0),
                   message=message, fix=fix)


def _in_tools(path: str) -> bool:
    return "tools/tpulint/" in path


# ----------------------------------------------------------------- rule 1


@register
class LayoutShimRouting(Rule):
    id = "layout-shim-routing"
    doc = ("jax.experimental.layout spells differently across jax versions; "
           "only utils/layouts.py may touch it (use auto_input_format / "
           "compiled_input_formats)")

    _MOD = "jax.experimental.layout"

    def applies(self, path: str) -> bool:
        return not path.endswith("deepspeed_tpu/utils/layouts.py") and \
            not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        msg = ("import of jax.experimental.layout outside utils/layouts.py "
               "— the layout API is version-split (Format/Layout vs "
               "DeviceLocalLayout); route through "
               "deepspeed_tpu.utils.layouts")
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith(self._MOD):
                        yield _f(self, ctx, node, msg)
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module.startswith(self._MOD):
                    names = {a.name for a in node.names}
                    fixable = names <= {"Format", "Layout",
                                        "DeviceLocalLayout"}
                    yield _f(self, ctx, node, msg,
                             fix="layout-import" if fixable else None)
                elif node.module == "jax.experimental" and any(
                        a.name == "layout" for a in node.names):
                    yield _f(self, ctx, node, msg)
            elif isinstance(node, ast.Attribute):
                resolved = resolve(node, aliases)
                if resolved and resolved.startswith(self._MOD):
                    yield _f(self, ctx, node,
                             "direct jax.experimental.layout attribute use "
                             "— route through deepspeed_tpu.utils.layouts")


# ----------------------------------------------------------------- rule 2


@register
class CompatShimRouting(Rule):
    id = "compat-shim-routing"
    doc = ("shard_map/pcast must ride the jax_compat shim: call "
           "jax.shard_map / jax.lax.pcast as attributes; never import the "
           "old jax.experimental.shard_map home or bind the names at "
           "import time")

    def applies(self, path: str) -> bool:
        return not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith("jax.experimental.shard_map"):
                        yield _f(self, ctx, node,
                                 "import of jax.experimental.shard_map "
                                 "bypasses the utils/jax_compat adapter "
                                 "(axis_names/check_vma translation) — "
                                 "call jax.shard_map")
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module.startswith("jax.experimental.shard_map") or (
                        node.module == "jax.experimental" and any(
                            a.name == "shard_map" for a in node.names)):
                    names = {a.name for a in node.names}
                    yield _f(self, ctx, node,
                             "import of jax.experimental.shard_map "
                             "bypasses the utils/jax_compat adapter "
                             "(axis_names/check_vma translation) — "
                             "call jax.shard_map",
                             fix="shard-map-import"
                             if names == {"shard_map"} else None)
                elif node.module == "jax" and any(
                        a.name == "shard_map" for a in node.names):
                    yield _f(self, ctx, node,
                             "from-import of jax.shard_map binds before "
                             "the jax_compat shim can install it on 0.4.x "
                             "— use the jax.shard_map attribute")
                elif node.module == "jax.lax" and any(
                        a.name in ("pcast", "pvary") for a in node.names):
                    yield _f(self, ctx, node,
                             "from-import of jax.lax.pcast/pvary binds "
                             "before the jax_compat shim can install them "
                             "on 0.4.x — use the jax.lax attribute")
            elif isinstance(node, ast.Attribute):
                resolved = resolve(node, aliases)
                if resolved and resolved.startswith(
                        "jax.experimental.shard_map"):
                    yield _f(self, ctx, node,
                             "direct jax.experimental.shard_map use "
                             "bypasses the utils/jax_compat adapter — "
                             "call jax.shard_map")


# ----------------------------------------------------------------- rule 3


@register
class NoSetMesh(Rule):
    id = "no-set-mesh"
    doc = ("jax.set_mesh / jax.lax.axis_size are DELIBERATELY unshimmed: "
           "the programs behind them SIGABRT 0.4.x XLA:CPU at "
           "backend_compile; a new call site needs a pragma arguing why "
           "its program class is already 0.4.x-incompatible")

    _BANNED = {"jax.set_mesh", "jax.lax.axis_size"}

    def applies(self, path: str) -> bool:
        return not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    if f"{node.module}.{a.name}" in self._BANNED:
                        yield _f(self, ctx, node,
                                 f"import of {node.module}.{a.name} — "
                                 "deliberately unshimmed (0.4.x XLA:CPU "
                                 "SIGABRT class); see utils/jax_compat.py")
            elif isinstance(node, ast.Attribute):
                resolved = resolve(node, aliases)
                if resolved in self._BANNED:
                    yield _f(self, ctx, node,
                             f"{resolved} is deliberately unshimmed (its "
                             "program class SIGABRTs 0.4.x XLA:CPU); new "
                             "sites must justify with a pragma — prefer "
                             "mesh.shape / groups topology for sizes")


# ----------------------------------------------------------------- rule 4


@register
class ManualRegionPurity(Rule):
    id = "manual-region-purity"
    doc = ("shard_map manual-region bodies in ops/pallas must not call "
           "axis_index/axis_size (compiles to PartitionId, UNIMPLEMENTED "
           "on the 0.4.x partitioner) — shard identity rides a sharded "
           "arange input, sizes come from mesh.shape")

    def applies(self, path: str) -> bool:
        return "ops/pallas/" in path

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        defs: Dict[str, ast.AST] = {
            n.name: n for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        bodies: List[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted_chain(node.func)
            if not chain or chain[-1] != "shard_map":
                continue
            if node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Lambda):
                    bodies.append(arg)
                elif isinstance(arg, ast.Name) and arg.id in defs:
                    bodies.append(defs[arg.id])
        for body in bodies:
            for node in ast.walk(body):
                if isinstance(node, (ast.Attribute, ast.Name)):
                    resolved = resolve(node, aliases)
                    if resolved in ("jax.lax.axis_index",
                                    "jax.lax.axis_size"):
                        yield _f(self, ctx, node,
                                 f"{resolved} inside a shard_map manual "
                                 "region — compiles to PartitionId "
                                 "(UNIMPLEMENTED on 0.4.x); derive shard "
                                 "identity from a sharded arange input "
                                 "(ops/pallas/sharded.py portability "
                                 "rules)")


# ----------------------------------------------------------------- rule 5


@register
class HostOnlyFaultPoints(Rule):
    id = "host-only-fault-points"
    doc = ("resilience fault points are HOST-only (a fault_point inside a "
           "traced body would bake syncs/recompiles into the program); "
           "never reachable from jit/scan/while_loop/shard_map bodies")

    def applies(self, path: str) -> bool:
        return not path.endswith("resilience/faults.py") and \
            not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        index = TracedIndex(ctx.tree, aliases)
        for _fn, node in index.walk_traced():
            if not isinstance(node, ast.Call):
                continue
            resolved = resolve(node.func, aliases) or ""
            bare = (isinstance(node.func, ast.Name)
                    and node.func.id == "fault_point")
            in_faults = ("resilience" in resolved
                         and resolved.rsplit(".", 1)[-1] in ("fault_point",
                                                             "inject"))
            if bare or in_faults or resolved.endswith("faults.fault_point"):
                yield _f(self, ctx, node,
                         "fault_point reachable from a traced function — "
                         "fault points are host-only by contract "
                         "(resilience/faults.py: no syncs, no recompiles, "
                         "pinned program identity)")


# ----------------------------------------------------------------- rule 6

_HOT_LOOP_FILES = (
    "deepspeed_tpu/runtime/engine.py",
    "deepspeed_tpu/inference/engine.py",
    "deepspeed_tpu/inference/capacity_scan.py",
    "deepspeed_tpu/inference/speculative.py",
)


@register
class NoHotLoopFetch(Rule):
    id = "no-hot-loop-fetch"
    doc = ("no device_get/np.asarray/block_until_ready inside the "
           "dispatch loops of the engine hot paths (each one syncs the host "
           "with the device; telemetry defers refs and fetches ONE batched "
           "device_get at flush) — deliberate fetch sites carry a pragma "
           "with the justification")

    _FETCHES = {"jax.device_get", "jax.block_until_ready",
                "numpy.asarray", "numpy.array"}

    def applies(self, path: str) -> bool:
        return any(path.endswith(p) for p in _HOT_LOOP_FILES)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        for node in loop_body_nodes(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = resolve(node.func, aliases)
            if resolved in self._FETCHES:
                yield _f(self, ctx, node,
                         f"{resolved} inside a dispatch loop — a host "
                         "fetch per iteration, each a host-device sync; "
                         "defer refs and batch the fetch, or pragma with "
                         "why this site must fetch")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "block_until_ready"):
                yield _f(self, ctx, node,
                         ".block_until_ready() inside a dispatch loop — "
                         "a device sync per iteration; defer or pragma "
                         "with why this site must sync")


# ----------------------------------------------------------------- rule 7


@register
class NoWallclockInTraced(Rule):
    id = "no-wallclock-in-traced"
    doc = ("wall-clock reads inside traced bodies execute at TRACE time "
           "and freeze into the compiled program (and silently re-stamp "
           "on recompile) — time/telemetry belongs on the host side")

    _CLOCKS = {"time.time", "time.time_ns", "time.perf_counter",
               "time.perf_counter_ns", "time.monotonic",
               "time.monotonic_ns", "datetime.datetime.now",
               "datetime.datetime.utcnow"}

    def applies(self, path: str) -> bool:
        return not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        index = TracedIndex(ctx.tree, aliases)
        for _fn, node in index.walk_traced():
            if isinstance(node, ast.Call):
                resolved = resolve(node.func, aliases)
                if resolved in self._CLOCKS:
                    yield _f(self, ctx, node,
                             f"{resolved}() inside a traced function — "
                             "evaluates once at trace time and freezes "
                             "into the program; stamp on the host instead")


# ----------------------------------------------------------------- rule 8


def parse_telemetry_doc(root: str) -> Dict[str, Set[str]]:
    """{event kind: documented field tokens} from docs/telemetry.md —
    ``### `kind``` headers open a section; backticked identifiers in the
    section body are that kind's fields. Shared by telemetry-schema-sync
    (code → doc) and telemetry-append-only (doc → committed snapshot)."""
    kinds: Dict[str, Set[str]] = {}
    doc = os.path.join(root, "docs", "telemetry.md")
    try:
        with open(doc, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return kinds  # no schema doc in this tree: rules report nothing
    section_kind: Optional[str] = None
    for line in text.splitlines():
        m = re.match(r"^###\s+`([A-Za-z0-9_]+)`", line)
        if m:
            section_kind = m.group(1)
            kinds.setdefault(section_kind, set())
            continue
        if line.startswith("## "):
            section_kind = None
        tokens: Set[str] = set()
        for span in re.findall(r"`([^`]+)`", line):
            tokens.update(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", span))
        if section_kind is not None:
            kinds[section_kind].update(tokens)
    return kinds


TELEMETRY_SNAPSHOT = os.path.join("docs", "telemetry_schema.json")


def load_telemetry_snapshot(root: str) -> Optional[Dict[str, Set[str]]]:
    """The committed schema snapshot, or None when the tree has none."""
    import json
    path = os.path.join(root, TELEMETRY_SNAPSHOT)
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    return {k: set(v) for k, v in raw.get("kinds", {}).items()}


def save_telemetry_snapshot(root: str) -> str:
    """Regenerate the snapshot from the current docs/telemetry.md (the
    --update-telemetry-snapshot flow). Returns the path written."""
    import json
    path = os.path.join(root, TELEMETRY_SNAPSHOT)
    kinds = parse_telemetry_doc(root)
    payload = {
        "_comment": ("Committed snapshot of the docs/telemetry.md event "
                     "schema. tpulint's telemetry-append-only rule fails "
                     "when a kind or field present here disappears from "
                     "the doc — the JSONL schema only grows. Regenerate "
                     "with: python -m deepspeed_tpu.tools.tpulint "
                     "--update-telemetry-snapshot"),
        "version": 1,
        "kinds": {k: sorted(v) for k, v in sorted(kinds.items())},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


@register
class TelemetrySchemaSync(Rule):
    id = "telemetry-schema-sync"
    doc = ("every telemetry event kind/field emitted through the hub must "
           "be documented in docs/telemetry.md — the schema is append-only "
           "by contract (tooling keys on field names)")

    def __init__(self):
        self._kinds: Dict[str, Set[str]] = {}
        self._common: Set[str] = {"ts", "kind", "step"}
        self._loaded_root: Optional[str] = None

    def applies(self, path: str) -> bool:
        if _in_tools(path) or path.startswith("tests/"):
            return False
        return path.startswith(("deepspeed_tpu/", "benchmarks/"))

    def begin_run(self, root: str) -> None:
        if self._loaded_root == root:
            return
        self._loaded_root = root
        self._kinds = parse_telemetry_doc(root)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if not self._kinds:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            is_emit = (isinstance(node.func, ast.Attribute)
                       and node.func.attr == "emit")
            is_helper = (isinstance(node.func, ast.Name)
                         and node.func.id == "_emit_event")
            if not (is_emit or is_helper):
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue
            kind = node.args[0].value
            if kind not in self._kinds:
                yield _f(self, ctx, node,
                         f"telemetry event kind '{kind}' is not documented "
                         "in docs/telemetry.md — the JSONL schema is "
                         "append-only; add a section for it")
                continue
            documented = self._kinds[kind] | self._common
            for kw in node.keywords:
                if kw.arg is None:  # **fields — not statically checkable
                    continue
                if kw.arg not in documented:
                    yield _f(self, ctx, node,
                             f"telemetry field '{kw.arg}' of event "
                             f"'{kind}' is not documented in "
                             "docs/telemetry.md — append it to that "
                             "event's section (never rename existing "
                             "fields)")


# ---------------------------------------------------------------- rule 8b


@register
class TelemetryAppendOnly(Rule):
    id = "telemetry-append-only"
    doc = ("the docs/telemetry.md event schema only grows: every kind and "
           "field in the committed docs/telemetry_schema.json snapshot "
           "must still be documented (field names are a stability "
           "contract — downstream tooling keys on them); additions must "
           "be re-snapshotted via --update-telemetry-snapshot")

    # anchored to the hub so the doc↔snapshot diff runs exactly once per
    # scan (the rule engine is per-.py-file; the findings carry doc paths)
    _ANCHOR = "deepspeed_tpu/telemetry/hub.py"

    def __init__(self):
        self._doc: Dict[str, Set[str]] = {}
        self._snapshot: Optional[Dict[str, Set[str]]] = None
        self._loaded_root: Optional[str] = None

    def applies(self, path: str) -> bool:
        return path == self._ANCHOR

    def begin_run(self, root: str) -> None:
        if self._loaded_root == root:
            return
        self._loaded_root = root
        self._doc = parse_telemetry_doc(root)
        self._snapshot = load_telemetry_snapshot(root)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if self._snapshot is None or not self._doc:
            return  # no snapshot committed yet (bootstrap) or no doc
        doc_path = "docs/telemetry.md"
        for kind in sorted(self._snapshot):
            if kind not in self._doc:
                yield Finding(
                    rule=self.id, path=doc_path, line=1, col=0,
                    message=f"telemetry event kind '{kind}' was removed "
                            "from docs/telemetry.md but exists in the "
                            "committed schema snapshot — the schema is "
                            "append-only (restore the section)")
                continue
            for field in sorted(self._snapshot[kind] - self._doc[kind]):
                yield Finding(
                    rule=self.id, path=doc_path, line=1, col=0,
                    message=f"telemetry field '{field}' of event "
                            f"'{kind}' was removed from docs/telemetry.md "
                            "but exists in the committed schema snapshot "
                            "— the schema is append-only (restore it; "
                            "fields are never renamed)")
        stale = sorted(set(self._doc) - set(self._snapshot)) + sorted(
            f"{kind}.{field}"
            for kind in self._doc if kind in self._snapshot
            for field in sorted(self._doc[kind] - self._snapshot[kind]))
        if stale:
            yield Finding(
                rule=self.id, path="docs/telemetry_schema.json", line=1,
                col=0,
                message="schema snapshot is stale — docs/telemetry.md "
                        f"gained {', '.join(stale[:6])}"
                        f"{'…' if len(stale) > 6 else ''}; run "
                        "python -m deepspeed_tpu.tools.tpulint "
                        "--update-telemetry-snapshot")


# ---------------------------------------------------------------- rule 8c


@register
class TelemetryKindDeclared(Rule):
    id = "telemetry-kind-declared"
    doc = ("every hub.emit(kind, ...) kind appearing in source must be "
           "declared in the committed docs/telemetry_schema.json snapshot "
           "— documenting a new kind in docs/telemetry.md is not enough; "
           "re-snapshot with --update-telemetry-snapshot so downstream "
           "schema validators see it")

    def __init__(self):
        self._snapshot: Optional[Dict[str, Set[str]]] = None
        self._loaded_root: Optional[str] = None

    def applies(self, path: str) -> bool:
        if _in_tools(path) or path.startswith("tests/"):
            return False
        return path.startswith(("deepspeed_tpu/", "benchmarks/"))

    def begin_run(self, root: str) -> None:
        if self._loaded_root == root:
            return
        self._loaded_root = root
        self._snapshot = load_telemetry_snapshot(root)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if self._snapshot is None:  # no snapshot committed yet (bootstrap)
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            is_emit = (isinstance(node.func, ast.Attribute)
                       and node.func.attr == "emit")
            is_helper = (isinstance(node.func, ast.Name)
                         and node.func.id == "_emit_event")
            if not (is_emit or is_helper):
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue
            kind = node.args[0].value
            if kind not in self._snapshot:
                yield _f(self, ctx, node,
                         f"telemetry event kind '{kind}' is not declared "
                         "in docs/telemetry_schema.json — document it in "
                         "docs/telemetry.md, then run python -m "
                         "deepspeed_tpu.tools.tpulint "
                         "--update-telemetry-snapshot")


# ----------------------------------------------------------------- rule 9


@register
class WarnOnceDiscipline(Rule):
    id = "warn-once-discipline"
    doc = ("a raw logger.warning in per-iteration code spams the log under "
           "retry/degradation loops — use utils.logging.warn_once (the one "
           "WARNED_ONCE registry) or pragma why repetition is the intent")

    def applies(self, path: str) -> bool:
        return path.startswith("deepspeed_tpu/") and \
            not path.endswith("utils/logging.py") and not _in_tools(path)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in loop_body_nodes(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr in (
                    "warning", "warn"):
                chain = dotted_chain(func)
                if chain and chain[-2] == "logger":
                    # autofixable only when the message is a one-line
                    # string literal (the literal doubles as the
                    # warn_once key, warning_once-style)
                    fixable = bool(node.args) and isinstance(
                        node.args[0], ast.Constant) and isinstance(
                        node.args[0].value, str) and \
                        node.args[0].lineno == node.args[0].end_lineno
                    yield _f(self, ctx, node,
                             "logger.warning inside a loop — repeated "
                             "iterations spam the log; use "
                             "utils.logging.warn_once (shared WARNED_ONCE "
                             "registry) or pragma why every iteration "
                             "must warn",
                             fix="warn-once" if fixable else None)


# ---------------------------------------------------------------- rule 10


@register
class SlowMarkDiscipline(Rule):
    id = "slow-mark-discipline"
    doc = ("tests touching known multi-second fixtures (zoo cached-decode "
           "parity, >=64k-token configs, the retrying-subprocess harness) "
           "must carry @pytest.mark.slow — protects the driver's 870 s "
           "tier-1 '-m not slow' budget")

    _BIG_SEQ = 65536  # 64k tokens: the smallest "long-ctx" config class

    def applies(self, path: str) -> bool:
        return path.startswith("tests/") and "/tools/" not in path

    @staticmethod
    def _has_slow(decorators: List[ast.AST]) -> bool:
        for dec in decorators:
            for node in ast.walk(dec):
                if isinstance(node, ast.Attribute) and node.attr == "slow":
                    return True
        return False

    @staticmethod
    def _module_slow(tree: ast.AST) -> bool:
        for node in getattr(tree, "body", []):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "pytestmark"
                    for t in node.targets):
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Attribute) and sub.attr == "slow":
                        return True
        return False

    def _indicator(self, fn: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
        if "cached_decode" in fn.name:
            return ("zoo cached-decode parity (per-token apply loop, "
                    "multi-second on the 1-core box)")
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                chain = dotted_chain(node.func)
                if chain and chain[-1] == "run_pytest_retry":
                    return ("retrying-subprocess harness (fresh "
                            "interpreter = fresh jax import, minutes "
                            "on the 1-core box)")
            elif isinstance(node, ast.Constant) and isinstance(
                    node.value, int) and not isinstance(node.value, bool):
                if node.value >= self._BIG_SEQ:
                    return (f"long-context constant {node.value} "
                            "(>=64k-token config class)")
        return None

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if self._module_slow(ctx.tree):
            return
        aliases = build_alias_map(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not node.name.startswith("test_"):
                continue
            if self._has_slow(node.decorator_list):
                continue
            why = self._indicator(node, aliases)
            if why:
                yield _f(self, ctx, node,
                         f"test touches {why} but is not marked "
                         "@pytest.mark.slow — tier-1 runs '-m not slow' "
                         "in a fixed 870 s budget")


# ---------------------------------------------------------------- rule 12


@register
class RawCollectiveDiscipline(Rule):
    id = "raw-collective-discipline"
    doc = ("raw jax.lax collectives (psum/all_gather/ppermute/...) are "
           "confined to ops/, runtime/, and comm/ — everywhere else the "
           "traffic must ride the declared helpers so tpucomms' "
           "axis-confinement contract sees every wire byte; deliberate "
           "manual-region sites (pipeline rotation, ring attention) "
           "carry a justified pragma")

    _COLLECTIVES = frozenset({
        "psum", "pmean", "pmax", "pmin", "all_gather", "psum_scatter",
        "ppermute", "pshuffle", "all_to_all",
    })
    _ALLOWED = ("deepspeed_tpu/ops/", "deepspeed_tpu/runtime/",
                "deepspeed_tpu/comm/", "deepspeed_tpu/tools/")

    def applies(self, path: str) -> bool:
        return path.startswith("deepspeed_tpu/") and \
            not any(path.startswith(p) for p in self._ALLOWED)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax.lax":
                for a in node.names:
                    if a.name in self._COLLECTIVES:
                        yield _f(self, ctx, node,
                                 f"import of jax.lax.{a.name} outside "
                                 "ops/runtime/comm — raw collectives "
                                 "must ride the declared helpers or "
                                 "carry a justified pragma")
            elif isinstance(node, ast.Call):
                resolved = resolve(node.func, aliases)
                if not resolved or not resolved.startswith("jax.lax."):
                    continue
                name = resolved[len("jax.lax."):]
                if name in self._COLLECTIVES:
                    yield _f(self, ctx, node,
                             f"raw jax.lax.{name} call outside "
                             "ops/runtime/comm — collectives must ride "
                             "the declared helpers (comm.comm, the "
                             "runtime wrappers) or carry a justified "
                             "pragma at the deliberate manual-region "
                             "site")


# ---------------------------------------------------------------- rule 13


@register
class AccountedPlacementRouting(Rule):
    id = "accounted-placement-routing"
    doc = ("host/pinned_host placements route through the accounted "
           "helpers (telemetry/memory.py, serve_modes, capacity_scan, the "
           "swapper) so the MemoryPlane ledger sees every byte; a "
           "device_put or sharding construction targeting a host memory "
           "kind anywhere else is an unaccounted residency change — "
           "deliberate sites carry a justified pragma")

    _HOST_KINDS = ("pinned_host", "unpinned_host")
    # files whose placements register into the MemoryPlane
    _ACCOUNTED = (
        "deepspeed_tpu/telemetry/memory.py",
        "deepspeed_tpu/inference/serve_modes.py",
        "deepspeed_tpu/inference/capacity_scan.py",
        "deepspeed_tpu/runtime/swap_tensor/",
    )
    _SHARDING_CTORS = frozenset({"NamedSharding", "SingleDeviceSharding",
                                 "TransferToMemoryKind"})

    def applies(self, path: str) -> bool:
        return path.startswith("deepspeed_tpu/") and \
            not any(path.startswith(p) or path == p
                    for p in self._ACCOUNTED) and not _in_tools(path)

    def _host_kind_in(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Constant) and sub.value in self._HOST_KINDS:
                return True
        return False

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        aliases = build_alias_map(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = resolve(node.func, aliases) or ""
            tail = resolved.rsplit(".", 1)[-1] if resolved else ""
            attr = node.func.attr if isinstance(node.func,
                                                ast.Attribute) else ""
            if resolved.endswith("device_put") or tail == "device_put":
                if self._host_kind_in(node):
                    yield _f(self, ctx, node,
                             "device_put targeting a host memory kind "
                             "outside the accounted placement helpers — "
                             "register the bytes with "
                             "telemetry.memory.get_plane() or route "
                             "through serve_modes/capacity_scan/the "
                             "swapper (pragma the site if deliberate)")
            elif tail in self._SHARDING_CTORS or attr == "with_memory_kind":
                # constructing a host-memory sharding is where placements
                # start even when the device_put lives elsewhere
                if any(self._host_kind_in(kw.value) for kw in node.keywords
                       if kw.arg == "memory_kind") or (
                        (tail == "TransferToMemoryKind"
                         or attr == "with_memory_kind")
                        and self._host_kind_in(node)):
                    yield _f(self, ctx, node,
                             "host-memory-kind sharding built outside the "
                             "accounted placement helpers — the placement "
                             "it feeds must register into the MemoryPlane "
                             "(pragma the site if deliberate)")
