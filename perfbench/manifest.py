"""`BENCHMARK.json` and the files its entries name.

Whatever belongs to one configuration, one traffic mix or one metric sits in
a file of its own, found by the NAME in `BENCHMARK.json`:

    configuration  its entry's `file`                    (sizes, as run)
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

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


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
    return bad
