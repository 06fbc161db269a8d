"""Tier-1 enforcement: the repo itself lints clean.

Runs tpulint in-process over the same trees the CLI defaults to. This is
deliberately NOT marked slow — the linter is stdlib-ast only and the
whole repo scan takes a few seconds on the 1-core box, so invariant
regressions (a stray jax.experimental.shard_map import, a fetch in a
dispatch loop, an undocumented telemetry field...) fail the timed tier-1
run instead of waiting for a human re-read of CLAUDE.md."""

import ast
import os
import re

from deepspeed_tpu.tools.tpulint import rules as _rules  # noqa: F401
from deepspeed_tpu.tools.tpulint import (
    lint_paths,
    load_baseline,
    new_findings,
)
from deepspeed_tpu.tools.tpulint.core import BASELINE_NAME

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
LINT_PATHS = ("deepspeed_tpu", "benchmarks", "tests")


def test_repo_comms_contracts_clean():
    """The compiled layer's tier-1 slice: fingerprint the ZeRO-3 train
    programs on the virtual mesh and hold them to the comms contracts
    (axis confinement + the 3×P volume budget). The full serving matrix
    rides the slow marker in test_tpucomms.py; the train component alone
    compiles in a couple of seconds and is the one whose drift (a
    PartitionSpec edit quietly changing the collective schedule) tier-1
    exists to catch."""
    from deepspeed_tpu.tools.tpucomms import verify
    from deepspeed_tpu.tools.tpucomms.core import (
        BASELINE_NAME as COMMS_BASELINE, load_baseline as load_comms,
        new_violations)
    from deepspeed_tpu.tools.tpucomms.put import build_comms_matrix

    violations = verify(build_comms_matrix(include=("train",)))
    baseline_path = os.path.join(REPO, COMMS_BASELINE)
    if os.path.exists(baseline_path):
        violations = new_violations(violations, load_comms(baseline_path))
    assert violations == [], (
        "tpucomms found new comms-contract violations:\n"
        + "\n".join(v.render() for v in violations)
        + "\nSee docs/static_analysis.md (compiled layer).")


def test_repo_lints_clean():
    paths = [os.path.join(REPO, p) for p in LINT_PATHS
             if os.path.exists(os.path.join(REPO, p))]
    assert paths, f"lint targets missing under {REPO}"
    findings = lint_paths(paths, root=REPO)
    baseline_path = os.path.join(REPO, BASELINE_NAME)
    if os.path.exists(baseline_path):
        findings = new_findings(findings, load_baseline(baseline_path))
    assert findings == [], (
        "tpulint found new invariant violations:\n"
        + "\n".join(f.render() for f in findings)
        + "\nFix them, or (for a deliberate exception) add a "
        "'# tpulint: disable=<rule>' pragma with a one-line justification "
        "(docs/static_analysis.md).")


# ----------------------------------------- one yardstick (PR 30's guards)

KEPT_BENCHMARKS = {"compile_cache", "hf7b_decode"}
ENV_NAME = re.compile(r"DS_(?:TPU|BENCH)_[A-Z0-9_]*[A-Z0-9]")


def _python_files():
    """The program's Python: the package, the kept harnesses, the benchmark,
    the tests and the root scripts (this file's own patterns aside)."""
    roots = [os.path.join(REPO, d)
             for d in ("deepspeed_tpu", "benchmarks", "perfbench", "tests")]
    out = [os.path.join(REPO, f) for f in sorted(os.listdir(REPO))
           if f.endswith(".py")]
    for root in roots:
        for base, dirs, files in os.walk(root):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            out += [os.path.join(base, f) for f in sorted(files)
                    if f.endswith(".py")]
    return [p for p in out if os.path.abspath(p) != os.path.abspath(__file__)]


def test_nothing_imports_the_deleted_measurement_code():
    """`bench.py` and the probes under `benchmarks/` are gone: `perfbench/`
    and the program's spans are the one yardstick. What stays importable
    from `benchmarks/` is the compile-cache helper and the 7B harness."""
    bad = []
    for path in _python_files():
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mods = [f"{node.module}.{a.name}" for a in node.names] \
                    if node.module == "benchmarks" else [node.module or ""]
            else:
                continue
            for mod in mods:
                parts = mod.split(".")
                if parts[0] == "bench" or (
                        parts[0] == "benchmarks" and len(parts) > 1
                        and parts[1] not in KEPT_BENCHMARKS):
                    bad.append(f"{os.path.relpath(path, REPO)}:"
                               f"{node.lineno}: {mod}")
    assert bad == []
    assert sorted(f[:-3] for f in os.listdir(os.path.join(REPO, "benchmarks"))
                  if f.endswith(".py") and f != "__init__.py") == \
        sorted(KEPT_BENCHMARKS)


def test_every_environment_setting_is_in_the_readme_and_none_is_a_benchmarks():
    """A `DS_TPU_*` name the tree reads is a switch somebody has to know:
    `README.md`'s table of environment settings lists each, and none of
    them parameterises a benchmark (a `BENCH_` after `DS_` or `DS_TPU_`):
    the benchmark's parameters are files under `perfbench/`."""
    read = {}
    for path in _python_files():
        with open(path) as f:
            for name in ENV_NAME.findall(f.read()):
                read.setdefault(name, os.path.relpath(path, REPO))
    assert len(read) >= 20, sorted(read)     # the scan finds the tree
    with open(os.path.join(REPO, "README.md")) as f:
        table = f.read().split("## Environment settings", 1)[1]
    listed = set(ENV_NAME.findall(table))
    assert {n: p for n, p in read.items() if n not in listed} == {}
    assert sorted(listed - set(read)) == []  # and the table names no ghost
    assert [n for n in read if re.match(r"DS_(TPU_)?BENCH_", n)] == []


def test_no_record_from_before_the_chip_at_the_root():
    """The records are `PERF_LEDGER.jsonl` (the driver's) and `PERF.md`:
    no `BENCH_r*`, `MULTICHIP_r*` or `ledger_r*` file beside them."""
    assert [f for f in sorted(os.listdir(REPO))
            if re.match(r"(BENCH_r|MULTICHIP_r|ledger_r)", f)] == []
