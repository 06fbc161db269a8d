"""`BENCHMARK.json` and the files its entries name.

Whatever belongs to one configuration, one traffic mix or one metric sits in
a file of its own, found by the NAME in `BENCHMARK.json`:

    configuration  its entry's `file`                    (sizes, as run)
      its family   <dir>/configs/<adapter|reference|counts>.py, named in the file
    traffic mix    <dir>/traffic/<traffic>.json          (parameters)
    metric         <dir>/metrics/<name>.json             (declaration: reader + params)
    reader         <dir>/readers/<module>.py             (one module per source)
    runner         <dir>/runners/<kind>.py               (one per kind of cell)

`<dir>` is searched in the manifest's own first `paths` entry and then in
this directory, so a manifest elsewhere (a test's temporary directory, a
later PR's files) adds files without touching one that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

from perfbench import flops

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# what `reduced` may never name: a width (the contract's list)
WIDTH_WORDS = ("hidden_size", "intermediate_size", "latent", "state_size",
               "proj", "head_dim", "expansion", "experts_per_tok")
# what it may name only as the chip's share of a deployment the file states
SHARE_WORDS = ("expert", "heads", "vocab")
CONFIG_KEYS = ("source", "reduced", "reduced_from", "assumed", "adapter",
               "reference", "rehearsal")


class Manifest:
    def __init__(self, path: Optional[str] = None):
        self.path = os.path.abspath(path or os.path.join(CHECKOUT,
                                                         "BENCHMARK.json"))
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.doc = json.load(f)
        own = os.path.join(self.root, self.doc["paths"][0])
        self.dirs = [own] if os.path.samefile(own, HERE) else [own, HERE]

    # ------------------------------------------------------------ entries
    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in {self.path}")

    def metrics_for(self, workload: str, group: str) -> List[Dict[str, Any]]:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or workload in m["workloads"]]

    # -------------------------------------------------------------- files
    def find(self, *parts: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, *parts)
            if os.path.exists(p):
                return p
        raise FileNotFoundError(
            f"{os.path.join(*parts)} not under any of {self.dirs}")

    def load_json(self, *parts: str) -> Dict[str, Any]:
        with open(self.find(*parts)) as f:
            return json.load(f)

    def config(self, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.root, self.config_entry(name)["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self.load_json("traffic", name + ".json")

    def metric(self, name: str) -> Dict[str, Any]:
        return self.load_json("metrics", name + ".json")

    def module(self, package: str, name: str):
        """`<dir>/<package>/<name>.py`, loaded by path (a new file in a
        manifest's own directory needs no import path of its own)."""
        path = self.find(package, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{package}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, spec: str) -> Callable:
        """'module:function' -> the function in `<dir>/readers/module.py`."""
        mod, _, fn = spec.partition(":")
        return getattr(self.module("readers", mod), fn)


# ------------------------------------------------------------- validation


def problems(m: Manifest) -> List[str]:
    """What the contract would refuse, as far as a file can show it: names
    and units in the allowed characters, every metric's file present and in
    step with its entry, `moves` reported by each of the metric's cells, the
    share of four-chip cells. An empty list is a manifest that may be sent."""
    doc, bad = m.doc, []
    e2e = {e["name"]: e for e in doc["end_to_end"]}
    cells = [w["name"] for w in doc["workloads"]]

    def cells_of(metric):
        return metric.get("workloads", cells)

    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[g]]
    for n in names + [w["traffic"] for w in doc["workloads"]]:
        if not NAME_RE.match(n):
            bad.append(f"name {n!r} outside the allowed characters")
    for group in ("configs", "workloads"):
        seen = [x["name"] for x in doc[group]]
        if len(set(seen)) != len(seen):
            bad.append(f"duplicate name in {group}")
    metric_names = [x["name"] for g in ("end_to_end", "per_layer")
                    for x in doc[g]]
    if len(set(metric_names)) != len(metric_names):
        bad.append("two metrics share a name")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for group in ("end_to_end", "per_layer"):
        for entry in doc[group]:
            name = entry["name"]
            if not UNIT_RE.match(entry["unit"]):
                bad.append(f"{name}: unit {entry['unit']!r}")
            if entry["better"] not in ("lower", "higher"):
                bad.append(f"{name}: better {entry['better']!r}")
            if entry["source"] not in SOURCES:
                bad.append(f"{name}: source {entry['source']!r}")
            if group == "end_to_end" and entry["source"] not in (
                    "host_clock", "device_trace"):
                bad.append(f"{name}: an end-to-end metric is taken by the "
                           "benchmark itself")
            for c in entry.get("workloads", []):
                if c not in cells:
                    bad.append(f"{name}: unknown workload {c!r}")
            try:
                decl = m.metric(name)
            except FileNotFoundError:
                bad.append(f"{name}: no metrics/{name}.json")
                continue
            for key in ("unit", "better", "source"):
                if decl.get(key) != entry[key]:
                    bad.append(f"{name}: {key} differs between "
                               "BENCHMARK.json and its file")
            if group == "per_layer":
                if decl.get("layer") != entry["layer"] or \
                        decl.get("moves") != entry["moves"]:
                    bad.append(f"{name}: layer/moves differ from its file")
                target = e2e.get(entry["moves"])
                if target is None:
                    bad.append(f"{name}: moves {entry['moves']!r}, which is "
                               "no end-to-end metric")
                else:
                    for c in cells_of(entry):
                        if c not in cells_of(target):
                            bad.append(f"{name}: cell {c} does not report "
                                       f"{entry['moves']}")
    for w in doc["workloads"]:
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips {w['chips']}")
        if len(w["why"]) > 200:
            bad.append(f"{w['name']}: why over 200 characters")
        if not [e for e in m.metrics_for(w["name"], "end_to_end")
                if e["name"] != "setup_s"]:
            bad.append(f"{w['name']}: no end-to-end metric besides setup_s")
        if not m.metrics_for(w["name"], "per_layer"):
            bad.append(f"{w['name']}: no per-layer metric")
        try:
            m.traffic(w["traffic"])
            m.config(w["config"])
        except (FileNotFoundError, KeyError) as e:
            bad.append(f"{w['name']}: {e}")
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    for c in doc["configs"]:
        if not any(w["config"] == c["name"] for w in doc["workloads"]):
            bad.append(f"config {c['name']} is used by no cell")
        bad.extend(config_problems(m, c["name"]))
    return bad


def _numbers(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The top-level numeric keys: what the contract compares."""
    return {k: v for k, v in sizes.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def config_problems(m: Manifest, name: str) -> List[str]:
    """One configuration against ITS OWN source, whatever its family: the
    entry and the file agree on `source` and `reduced`; the file gives, under
    `reduced_from`, the value the source publishes for each reduced key, and
    runs another; no reduced key is a width; a count of experts, heads or
    vocabulary rows is reduced only beside the `deployment` whose share it
    is; and two files of one source differ, among their numbers, in the keys
    their `reduced` lists and no others."""
    entry = m.config_entry(name)
    try:
        sizes = m.config(name)
    except (FileNotFoundError, ValueError) as e:
        return [f"config {name}: {e}"]
    bad = [f"no {key!r} in its file" for key in CONFIG_KEYS if key not in sizes]
    if bad:
        return [f"config {name}: {b}" for b in bad]
    if not entry["file"].startswith(tuple(p + "/" for p in m.doc["paths"])):
        bad.append(f"file {entry['file']!r} under none of `paths`")
    if sizes["source"] != entry["source"]:
        bad.append("source differs between BENCHMARK.json and its file")
    reduced = list(entry["reduced"])
    if sizes["reduced"] != reduced or list(sizes["reduced_from"]) != reduced:
        bad.append(f"reduced {reduced} in BENCHMARK.json, {sizes['reduced']} "
                   f"in its file, reduced_from {list(sizes['reduced_from'])}")
    for key in reduced:
        if not NAME_RE.match(key):
            bad.append(f"reduced key {key!r} outside the allowed characters")
        if any(w in key for w in WIDTH_WORDS) or key.endswith(("_dim", "_rank")):
            bad.append(f"reduced names a width, {key}")
        if any(w in key for w in SHARE_WORDS) and not (
                isinstance(sizes.get("deployment"), str) and sizes["deployment"]):
            bad.append(f"reduced names {key}, a chip's share, and the file "
                       "states no `deployment`")
        if key in sizes["reduced_from"] and \
                sizes.get(key) == sizes["reduced_from"][key]:
            bad.append(f"{key} is listed as reduced and runs at the "
                       "source's value")
    try:
        own = flops.family_counts(sizes, m)
        missing = [f for f in flops.COUNTS if not hasattr(own, f)] \
            if own else []
        if missing:
            bad.append(f"counts module {sizes['counts']} lacks {missing}")
    except (ValueError, FileNotFoundError) as e:
        bad.append(str(e))
    for other in m.doc["configs"]:
        if other["name"] == name or other["source"] != entry["source"]:
            continue
        try:
            theirs = m.config(other["name"])
        except (FileNotFoundError, ValueError):
            continue          # named under its own configuration
        a, b = _numbers(sizes), _numbers(theirs)
        differ = {k for k in set(a) | set(b) if a.get(k) != b.get(k)}
        ours, other_red = set(reduced), set(other["reduced"])
        if not (ours ^ other_red) <= differ <= (ours | other_red):
            bad.append(f"differs from {other['name']} (same source) in "
                       f"{sorted(differ)}, and their `reduced` list "
                       f"{sorted(ours | other_red)}")
    return [f"config {name}: {b}" for b in bad]
