"""BENCHMARK.json against the contract, as far as a file can show it, and the
benchmark's own arithmetic (FLOPs per token, peaks). No JAX."""

import copy
import json
import os

import pytest

from perfbench import flops
from perfbench.manifest import (CHECKOUT, NAME_RE, UNIT_RE, Manifest,
                                problems)

M = Manifest()
DOC = M.doc
ALLOWED_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}
WIDTH_WORDS = ("hidden_size", "intermediate_size", "latent", "state_size",
               "proj", "head_dim", "expansion", "experts_per_tok")


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


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_reduced_names_no_width_and_lists_every_changed_key(config):
    entry = M.config_entry(config)
    assert entry["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
    for key in entry["reduced"]:
        assert NAME_RE.match(key)
        assert not any(w in key for w in WIDTH_WORDS) and \
            not key.endswith(("_dim", "_rank"))
    sizes = M.config(config)
    assert sizes["reduced"] == entry["reduced"]
    full = M.config("qwen2.5-3b" if "3b" in config else "qwen2.5-0.5b")
    changed = [k for k, v in sizes.items() if isinstance(v, (int, float))
               and not isinstance(v, bool) and full.get(k) != v]
    assert changed == entry["reduced"]


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
