"""Parent against change, one benchmark cell, one chip call: that naming
scopes and programs changed NO instruction.

    chiprun --timeout 1800 -- python tools/scope_proof.py \
        --parent _proof/parent --workload qwen2.5-0.5b.train-2k --seed 7 \
        --seconds 20

Runs the cell traced (`perfbench/run.py --trace 1`) in this checkout and in
`--parent` (an unpacked copy of the parent commit with THIS tree's benchmark
files laid over it, as the driver runs it), each a process of its own with
XLA dumping the optimised modules of the engines' programs, and prints one
JSON line:

- `first`: what each side's result line says of its first loss / first
  tokens (`notes`), `correct`, and the per-layer metrics only one side
  reports (the parent must leave the map's metrics out, not fail);
- `hlo`: the optimised modules of both sides paired by their text with
  `metadata={...}`, the module's own name and the debug locations inside a
  Pallas kernel's serialized body taken out. `differ` lists the
  modules of either side that found no partner: empty is the proof that the
  scopes and the programs' new names are metadata only.

This process never touches JAX (a chip belongs to one process at a time).
What it keeps goes under `chiprun_out/scope_proof/<workload>/`.
"""

from __future__ import annotations

import argparse
import base64
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

# the engines' programs, under the parent's names and under this tree's
MODULES = r"jit_(ds_|fused|_lambda|ev).*"
_META_RE = re.compile(r",?\s*metadata=\{(?:[^{}\"]|\"[^\"]*\")*\}")
_NAME_RE = re.compile(r"^HloModule\s+[\w.\-]+")


_FIRST_COMPUTATION_RE = re.compile(r"^(%|ENTRY )", re.M)
_KERNEL_BODY_RE = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_text(match) -> str:
    """A Pallas kernel's serialized Mosaic module (`custom_call_config`'s
    `body`, MLIR bytecode) as the hash of its assembly WITHOUT debug
    locations: those carry the checkout's path and the caller's line
    numbers, which differ between two trees that build the same kernel."""
    from jax._src.lib.mlir import ir   # the bindings alone: no backend
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(match.group(1))
                              ).operation.get_asm(enable_debug_info=False)
    return '"body":"mlir-sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def stripped(text: str) -> str:
    """Optimised HLO text less what a scope, a function's name or a
    checkout's path may change: every `metadata={...}`, the module's name,
    the tables of file and function names and stack frames above the first
    computation, and the debug locations inside a Pallas kernel's body."""
    head = _NAME_RE.sub("HloModule M", text.split("\n", 1)[0])
    body = text[_FIRST_COMPUTATION_RE.search(text).start():]
    return _KERNEL_BODY_RE.sub(_kernel_text,
                               _META_RE.sub("", head + "\n" + body))


def run_side(cwd: str, args, out: str, dump: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + f" --xla_dump_to={dump}"
                        " --xla_dump_hlo_as_text"
                        f" --xla_dump_hlo_module_re={MODULES}").strip()
    env["PERFBENCH_DUMP"] = out
    cmd = [sys.executable, "perfbench/run.py", "--workload", args.workload,
           "--seed", str(args.seed), "--seconds", str(args.seconds),
           "--trace", "1"] + (["--rehearsal"] if args.rehearsal else [])
    got = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                         text=True)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "stderr.txt"), "w") as f:
        f.write(got.stderr[-20000:])
    lines = [l for l in got.stdout.splitlines() if l.startswith("{")]
    if got.returncode or not lines:
        return {"rc": got.returncode, "stderr": got.stderr[-1500:]}
    line = json.loads(lines[-1])
    with open(os.path.join(out, "line.json"), "w") as f:
        json.dump(line, f, indent=1)
    return line


def modules_of(dump: str) -> dict:
    """{module file: sha of its stripped text} of the final optimised dumps."""
    out = {}
    for path in glob.glob(os.path.join(dump, "*after_optimizations.txt")):
        with open(path) as f:
            text = f.read()
        name = re.sub(r"^module_\d+\.", "", os.path.basename(path)).split(
            ".", 1)[0]
        out.setdefault(name, []).append(
            hashlib.sha256(stripped(text).encode()).hexdigest())
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", required=True)
    ap.add_argument("--change", default=".",
                    help="the change's checkout (this one; or an unpacked "
                         "`git archive $(git write-tree)`, which proves "
                         "the committed files are enough)")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rehearsal", action="store_true",
                    help="off the chip: toy sizes, the same control flow")
    args = ap.parse_args()
    keep = os.path.join("chiprun_out", "scope_proof", args.workload)
    shutil.rmtree(keep, ignore_errors=True)
    tmp = tempfile.mkdtemp(prefix="scope_proof_")
    sides, dumps = {}, {}
    for side, cwd in (("change", args.change), ("parent", args.parent)):
        dumps[side] = os.path.join(tmp, side)
        sides[side] = run_side(cwd, args, os.path.abspath(
            os.path.join(keep, side)), dumps[side])
    if any("rc" in s for s in sides.values()):
        print(json.dumps({"failed": sides}))
        return 1
    mods = {side: modules_of(d) for side, d in dumps.items()}
    left = {side: sorted(h for hs in m.values() for h in hs)
            for side, m in mods.items()}
    differ = {side: sorted(n for n, hs in mods[side].items()
                           if any(h not in left[other] for h in hs))
              for side, other in (("change", "parent"), ("parent", "change"))}
    if differ["change"] or differ["parent"]:    # keep the texts to read
        for side, d in dumps.items():
            for path in glob.glob(os.path.join(d, "*after_optimizations.txt")):
                if any(n in path for n in differ[side]):
                    with open(path) as f, open(os.path.join(
                            keep, side, os.path.basename(path)[:120]
                            + ".stripped"), "w") as g:
                        g.write(stripped(f.read()))
    metrics = {s: set(sides[s]["metrics"]) for s in sides}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "first": {s: {"correct": sides[s]["correct"],
                      "notes": sides[s]["notes"],
                      "compared": sides[s].get("compared")} for s in sides},
        "only_change_reports": sorted(metrics["change"] - metrics["parent"]),
        "only_parent_reports": sorted(metrics["parent"] - metrics["change"]),
        "change_metrics": {k: v["value"] for k, v in
                           sides["change"]["metrics"].items()},
        "parent_metrics": {k: v["value"] for k, v in
                           sides["parent"]["metrics"].items()},
        "device": {s: sides[s]["device"] for s in sides},
        "hlo": {"modules": {s: {n: len(h) for n, h in mods[s].items()}
                            for s in mods}, "differ": differ}}))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
