"""BENCHMARK.json against the contract, as far as a file can show it, and the
benchmark's own arithmetic (FLOPs per token, peaks). No JAX."""

import copy
import json
import math
import os
import re

import pytest

from perfbench import flops
from perfbench.manifest import (CHECKOUT, CONFIG_KEYS, NAME_RE, UNIT_RE,
                                WIDTH_WORDS, Manifest, config_problems,
                                problems)

from .conftest import SECOND_FAMILY

M = Manifest()
DOC = M.doc
SECOND = Manifest(os.path.join(SECOND_FAMILY, "BENCHMARK.json"))
# every configuration of the checkout's manifest, and the second family's
CONFIGS = [pytest.param(m, c["name"], id=c["name"])
           for m in (M, SECOND) for c in m.doc["configs"]]
ALLOWED_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def test_the_manifest_has_no_problem():
    assert problems(M) == []


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    runs = 2 + 14 * 24     # the full 24 cells a later PR may reach
    assert runs * (DOC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(M.path) <= 64 * 1024
    assert DOC["command"][-1].startswith(DOC["paths"][0] + "/")
    for p in DOC["paths"]:
        assert os.path.isdir(os.path.join(CHECKOUT, p)) and ".." not in p


@pytest.mark.parametrize("group", sorted(ALLOWED_KEYS))
def test_entries_have_just_the_keys_shown(group):
    for entry in DOC[group]:
        assert set(entry) <= ALLOWED_KEYS[group], entry["name"]
        required = ALLOWED_KEYS[group] - {"workloads"}
        assert required <= set(entry), entry["name"]


def test_names_units_and_lines():
    for group in ALLOWED_KEYS:
        for entry in DOC[group]:
            assert NAME_RE.match(entry["name"])
            if "unit" in entry:
                assert UNIT_RE.match(entry["unit"]) and entry["unit"].isascii()
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads",
                                              "per_layer"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text


def test_bounds():
    for e in DOC["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1, e["name"]
    assert {e["name"]: e["bound"] for e in DOC["end_to_end"]}["setup_s"] <= 0.1


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_reports_setup_one_more_and_a_layer(cell):
    e2e = [e["name"] for e in M.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_moves_is_reported_by_each_of_the_metrics_cells(metric):
    entry = next(m for m in DOC["per_layer"] if m["name"] == metric)
    cells = entry.get("workloads", [w["name"] for w in DOC["workloads"]])
    for cell in cells:
        assert entry["moves"] in [e["name"] for e in
                                  M.metrics_for(cell, "end_to_end")]


@pytest.mark.parametrize("metric", [m["name"] for g in ("end_to_end", "per_layer")
                                    for m in DOC[g]])
def test_each_metric_has_its_own_file_and_a_reader_that_exists(metric):
    decl = M.metric(metric)
    assert decl["name"] == metric
    assert callable(M.reader(decl["reader"]))


def test_one_four_chip_cell_of_four():
    chips = [w["chips"] for w in DOC["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 4)
    assert set(chips) <= {1, 4}


@pytest.mark.parametrize("m, config", CONFIGS)
def test_a_configuration_is_checked_against_its_own_source(m, config):
    """Whatever its family: `reduced` in BENCHMARK.json, in the file and as
    the keys of `reduced_from` are one list; none is a width; each runs at
    another value than the source publishes; a chip's share stands beside its
    `deployment`; files of one source differ in what `reduced` lists."""
    entry, sizes = m.config_entry(config), m.config(config)
    assert set(CONFIG_KEYS) <= set(sizes)
    assert entry["reduced"] == sizes["reduced"] == list(sizes["reduced_from"])
    for key in entry["reduced"]:
        assert not any(w in key for w in WIDTH_WORDS) and \
            not key.endswith(("_dim", "_rank"))
        assert sizes[key] != sizes["reduced_from"][key]
    assert config_problems(m, config) == []


def test_files_of_one_source_differ_in_what_reduced_lists():
    """The pair the benchmark has: the depth cut of the 3B file differs
    from the whole one in its depth, among the numbers, and in nothing else."""
    whole, cut = M.config("qwen2.5-3b"), M.config("qwen2.5-3b-l20")
    assert whole["source"] == cut["source"]
    differ = [k for k in cut if isinstance(cut[k], (int, float))
              and cut[k] != whole.get(k)]
    assert differ == cut["reduced"] == ["num_hidden_layers"]
    assert whole["num_hidden_layers"] == cut["reduced_from"]["num_hidden_layers"]


def _reduced_out_of_step(doc, sizes):
    doc["configs"][0]["reduced"] = ["num_hidden_layers"]


def _no_reduced_from(doc, sizes):
    del sizes["reduced_from"]


def _a_width(doc, sizes):
    doc["configs"][0]["reduced"] = sizes["reduced"] = ["intermediate_size"]
    sizes["reduced_from"] = {"intermediate_size": 1024}


def _runs_at_the_sources_value(doc, sizes):
    sizes["reduced_from"]["num_hidden_layers"] = sizes["num_hidden_layers"]


def _a_share_without_its_deployment(doc, sizes):
    del sizes["deployment"]


def _source_differs(doc, sizes):
    sizes["source"] = "somewhere else"


def _experts_and_no_counts(doc, sizes):
    del sizes["counts"]


def _counts_that_is_no_file(doc, sizes):
    sizes["counts"] = "no_such_counts"


def _sibling_differs_in_an_unlisted_key(doc, sizes):
    """A second entry of the same source whose file is the first's: it
    lists a reduced key more, and runs it at the same value."""
    doc["configs"].append({**doc["configs"][0], "name": "toy-moe-twin",
                           "reduced": sizes["reduced"] + ["vocab_size"]})
    doc["workloads"].append({**doc["workloads"][0], "name": "twin.serve-toy",
                             "config": "toy-moe-twin"})


@pytest.mark.parametrize("edit, said", [
    (_reduced_out_of_step, "reduced ['num_hidden_layers'] in BENCHMARK.json"),
    (_no_reduced_from, "no 'reduced_from' in its file"),
    (_a_width, "reduced names a width, intermediate_size"),
    (_runs_at_the_sources_value, "runs at the source's value"),
    (_a_share_without_its_deployment, "states no `deployment`"),
    (_source_differs, "source differs"),
    (_experts_and_no_counts, "names no `counts` module"),
    (_counts_that_is_no_file, "no_such_counts.py not under any of"),
    (_sibling_differs_in_an_unlisted_key, "differs from toy-moe-twin"),
], ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_a_configuration_out_of_step_is_a_problem(second_family, edit, said):
    assert problems(Manifest(second_family())) == []
    found = "\n".join(problems(Manifest(second_family(edit))))
    assert said in found, found


def test_config_files_are_distinct():
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)


def test_problems_are_found(tmp_path):
    """The checker is not vacuous: a wrong `moves`, a bad unit and a second
    four-chip cell are each named."""
    doc = copy.deepcopy(DOC)
    doc["per_layer"][0]["moves"] = "out_tok_s"      # serve-chat does not report it
    doc["per_layer"][1]["unit"] = "tokens per second"
    doc["workloads"][0]["chips"] = 4
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    (tmp_path / "perfbench").mkdir()
    found = "\n".join(problems(Manifest(str(path))))
    assert "does not report out_tok_s" in found
    assert "tokens per second" in found
    assert "four-chip cells" in found


# ------------------------------------------------------------- arithmetic

# matmul_params, total_params, train_flops_per_token(., 2048) and
# kv_bytes_per_token as the parent (78ac6f6) returns them: the dispatch on
# `counts` may not move a digit of a file that names none
PINNED = {
    "qwen2.5-3b": (3085697024, 3085938688, 19420151808.0, 36864),
    "qwen2.5-0.5b": (493961216, 494032768, 3228008448.0, 12288),
    "qwen2.5-3b-l20": (1852571648, 1852706816, 11618746368.0, 20480),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_counts_of_the_files_that_are_here_are_what_they_were(config):
    cfg = M.config(config)
    assert "counts" not in cfg
    assert (flops.matmul_params(cfg), flops.total_params(cfg),
            flops.train_flops_per_token(cfg, 2048),
            flops.kv_bytes_per_token(cfg)) == PINNED[config]


def test_a_family_that_names_counts_is_asked_for_its_active_parameters():
    """Worked by hand for the toy file: hidden 64, 4 heads of 16 on 2 KV
    heads, 4 experts of width 96 of which a token runs 2, 2 layers, 128
    rows of vocabulary."""
    cfg = SECOND.config("toy-moe")
    attn = 2 * 64 * 64 + 2 * 64 * 32                      # q, o; k, v
    active = 2 * (attn + 2 * 3 * 64 * 96 + 64 * 4) + 64 * 128
    held = 2 * (attn + 4 * 3 * 64 * 96 + 64 * 4 + 2 * 64) + 64 + 2 * 64 * 128
    assert flops.matmul_params(cfg, manifest=SECOND) == active
    assert flops.total_params(cfg, manifest=SECOND) == held
    assert flops.train_flops_per_token(cfg, 32, manifest=SECOND) == \
        6 * active + 6 * 2 * 4 * 16 * 32
    assert flops.kv_bytes_per_token(cfg, manifest=SECOND) == 2 * 2 * 2 * 16 * 2
    # one dense FFN of that width would be a quarter of the experts held
    # and half of those a token runs
    dense = {k: v for k, v in cfg.items()
             if k not in ("counts", "num_local_experts")}
    assert flops.matmul_params(dense) == active - 2 * (3 * 64 * 96 + 64 * 4)


@pytest.mark.parametrize("key", flops.EXPERT_KEYS)
def test_experts_and_no_counts_is_an_error_never_a_dense_guess(key):
    cfg = {**M.config("qwen2.5-0.5b"), key: 8}
    for count in (flops.matmul_params, flops.total_params,
                  flops.kv_bytes_per_token):
        with pytest.raises(ValueError, match="names no `counts`"):
            count(cfg)
    with pytest.raises(ValueError, match="names no `counts`"):
        flops.train_flops_per_token(cfg, 2048)


@pytest.mark.parametrize("mesh, chips, want", [
    ({"dp": 1, "tp": 1}, 1, {"dp": 1, "tp": 1, "ep": 1}),
    ({"dp": 2, "tp": 2}, 4, {"dp": 2, "tp": 2, "ep": 1}),
    ({"dp": 1, "tp": 1, "ep": 4}, 4, {"dp": 1, "tp": 1, "ep": 4}),
    ({"dp": 2, "ep": 2}, 4, {"dp": 2, "tp": 1, "ep": 2}),
    ({"dp": 2, "tp": 2, "ep": 2}, 4, "dp2 x tp2 x ep2 on 4 device(s)"),
    ({"dp": 2, "tp": 2}, 1, "on 1 device(s)"),
    ({"dp": 2, "pp": 2}, 4, "mesh axis 'pp' is none of dp, tp, ep"),
])
def test_a_train_cells_mesh(mesh, chips, want):
    """`ep` is 1 unless stated; the product is the cell's chips; an axis
    the runner does not build is refused."""
    mesh_of = M.module("runners", "train").mesh_of
    if isinstance(want, dict):
        assert mesh_of({"mesh": mesh}, chips) == want
    else:
        with pytest.raises(SystemExit, match=re.escape(want)):
            mesh_of({"mesh": mesh}, chips)


@pytest.mark.parametrize("traffic, mesh, want", [
    (M.traffic("train-2k"), None, 4),                # 8 rows / (2 x dp1)
    (M.traffic("train-zero3-x4"), None, 4),          # 16 rows / (2 x dp2)
    ({"sequences_per_step": 16, "micro_batch": 2},
     {"dp": 2, "tp": 1, "ep": 2}, 2),                # 16 rows / (2 x dp2 x ep2)
], ids=["train-2k", "train-zero3-x4", "dp2-ep2"])
def test_steps_of_gradient_accumulation(traffic, mesh, want):
    """Where the mesh states no `ep` they are rows over (micro-batch x dp),
    as before a mesh could state one."""
    train = M.module("runners", "train")
    if mesh is None:
        assert "ep" not in traffic["mesh"]
        mesh = train.mesh_of(traffic, math.prod(traffic["mesh"].values()))
    assert train.accumulation_steps(traffic, mesh) == want


def test_parameter_counts_match_the_published_models():
    assert abs(flops.total_params(M.config("qwen2.5-3b")) - 3.086e9) < 2e6
    assert abs(flops.total_params(M.config("qwen2.5-0.5b")) - 494.0e6) < 1e6
    assert abs(flops.total_params(M.config("qwen2.5-3b-l20")) - 1.85e9) < 2e7


def test_train_flops_per_token():
    cfg = M.config("qwen2.5-0.5b")
    want = 6 * flops.matmul_params(cfg) + 6 * 24 * 14 * 64 * 2048
    assert flops.train_flops_per_token(cfg, 2048) == want
    assert 3.0e9 < want < 3.4e9          # "3.2 GFLOP per token"
    assert 11.0e9 < flops.train_flops_per_token(
        M.config("qwen2.5-3b-l20"), 2048) < 12.2e9


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(M.config("qwen2.5-3b")) == 36864


def test_peaks_known_kind_and_unknown_kind():
    assert flops.peaks_for("TPU v5 lite")["bf16_tflops"] == 197.0
    assert flops.peaks_for("TPU v5 lite")["hbm_gbps"] == 819.0
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError):
            flops.peaks_for(kind)
