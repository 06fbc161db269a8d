"""The command itself, end to end at toy sizes on the CPU (`--rehearsal`):
same control flow as on the chip, no number reported as a metric. And the
proof that a later PR adds a configuration, a traffic mix and a per-layer
metric as NEW FILES AND ENTRIES ONLY: once within the family the benchmark
has, once for ANOTHER family (sparse experts, with its own adapter, plain
reference and `counts`)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import flops
from perfbench.manifest import CHECKOUT, Manifest, problems

M = Manifest()
CELLS = [w["name"] for w in M.doc["workloads"]]
DEVICE_SOURCED = {m["name"] for m in M.doc["per_layer"]
                  if m["source"] == "device_trace"}


def run_cell(*args, manifest=None):
    cmd = [sys.executable, os.path.join(CHECKOUT, *M.doc["command"][1:]
                                        [0].split("/"))] + list(args)
    if manifest:
        cmd += ["--manifest", str(manifest)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    p = subprocess.run(cmd, cwd=CHECKOUT, env=env, capture_output=True,
                       text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p.returncode, lines, p.stderr


def result_of(lines):
    line = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell):
    rc, lines, err = run_cell("--workload", cell, "--seed", "2147483659",
                              "--seconds", "2", "--trace", "0", "--rehearsal")
    assert rc == 0, err[-2000:]
    line = result_of(lines)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a CPU's numbers are never written under the name of a metric
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    want = {e["name"] for e in M.metrics_for(cell, "end_to_end")}
    assert set(line["rehearsal_metrics"]) == want
    assert all(v["value"] > 0 for v in line["rehearsal_metrics"].values())


def test_traced_rehearsal_reads_no_device_metric():
    rc, lines, err = run_cell("--workload", "qwen2.5-3b.serve-chat", "--seed",
                              "3", "--seconds", "3", "--trace", "1",
                              "--rehearsal")
    assert rc == 0, err[-2000:]
    line = result_of(lines)
    got = set(line["rehearsal_metrics"])
    assert not got & DEVICE_SOURCED          # no trace of a chip, no number
    assert {"gen_late_p90_ms", "queue_wait_p50_ms", "batch_occupancy",
            "ttft_p50_ms", "tpot_p50_ms", "slo_share",
            "recompiles_in_window"} <= got
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["rehearsal_metrics"]["recompiles_in_window"]["value"] == 0


def test_a_measuring_run_on_a_cpu_prints_no_result():
    rc, lines, err = run_cell("--workload", "qwen2.5-0.5b.train-2k", "--seed",
                              "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and lines == []
    assert "no accelerator" in err


def test_unknown_workload_fails():
    rc, lines, _ = run_cell("--workload", "nope", "--rehearsal")
    assert rc != 0 and lines == []


def test_same_seed_same_offered_work():
    a = result_of(run_cell("--workload", "qwen2.5-3b.serve-chat", "--seed", "9",
                           "--seconds", "2", "--rehearsal")[1])
    b = result_of(run_cell("--workload", "qwen2.5-3b.serve-chat", "--seed", "9",
                           "--seconds", "2", "--rehearsal")[1])
    for key in ("requests", "prompt_tokens", "output_tokens"):
        assert a["notes"][key] == b["notes"][key]


# ------------------------------------------------- adding without editing


def benchmark_mtimes():
    return {os.path.join(r, f): os.path.getmtime(os.path.join(r, f))
            for p in M.doc["paths"]
            for r, _, fs in os.walk(os.path.join(CHECKOUT, p))
            for f in fs if not f.endswith(".pyc")}


def test_a_second_family_is_new_files_only(second_family):
    """A sparse-expert decoder the harness has never seen (4 experts, top-2,
    over `models/mixtral.py` as it stands): its configuration file, `counts`,
    adapter, plain reference, traffic mix and BENCHMARK.json sit in a
    temporary directory, no file of the benchmark is edited, the manifest
    has no problem, its FLOPs count the experts a token runs, and the
    command serves it through v2 `put` with every first token the plain
    reference's."""
    before = benchmark_mtimes()
    path = second_family()
    m = Manifest(path)
    assert problems(m) == []
    sizes = m.config("toy-moe")
    assert sizes["model_type"] != M.config(M.doc["configs"][0]["name"])["model_type"]
    dense_guess = {k: v for k, v in sizes.items()
                   if k not in ("counts", "num_local_experts")}
    assert flops.train_flops_per_token(sizes, 32, manifest=m) > \
        flops.train_flops_per_token(dense_guess, 32)
    rc, lines, err = run_cell("--workload", "toy-moe.serve-toy", "--seed",
                              "2147483777", "--seconds", "3", "--trace", "0",
                              "--rehearsal", manifest=path)
    assert rc == 0, err[-2000:]
    line = result_of(lines)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 15
    assert line["notes"]["check"]["checked"] == 4
    assert set(line["rehearsal_metrics"]) == {"ttft_p80_ms", "tpot_p80_ms",
                                              "setup_s"}
    rc, lines, err = run_cell("--workload", "toy-moe.serve-toy", "--seed", "5",
                              "--seconds", "2", "--trace", "1", "--rehearsal",
                              manifest=path)
    assert rc == 0, err[-2000:]
    assert result_of(lines)["rehearsal_metrics"]["round_p50_ms"]["value"] > 0
    assert before == benchmark_mtimes()


def test_the_command_refuses_what_the_manifest_test_would(second_family):
    def edit(doc, sizes):
        del sizes["reduced_from"]
    rc, lines, err = run_cell("--workload", "toy-moe.serve-toy", "--rehearsal",
                              manifest=second_family(edit))
    assert rc != 0 and lines == []
    assert "no 'reduced_from' in its file" in err


def test_new_config_traffic_and_metric_are_new_files_only(tmp_path):
    """A throw-away configuration, a bursty traffic mix and a per-layer
    metric with a reader of its own, in a directory that holds nothing
    else: no file of the benchmark is edited, and the command runs them."""
    bench = tmp_path / "perfbench"
    for sub in ("configs", "traffic", "metrics", "readers"):
        (bench / sub).mkdir(parents=True)
    toy = dict(M.config("qwen2.5-0.5b"))
    toy.update(name="toy-qwen", hidden_size=48, intermediate_size=96,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=128)
    toy["rehearsal"] = {}
    (bench / "configs" / "toy-qwen.json").write_text(json.dumps(toy))
    burst = dict(M.traffic("serve-chat"))
    burst = {**burst, **{k: v for k, v in burst["rehearsal"].items()
                         if k != "arrivals"},
             "arrivals": {"process": "gamma", "cv": 3.0, "rate": 5.0},
             "rehearsal": {}}
    burst["prompt"] = {**M.traffic("serve-chat")["prompt"],
                       **burst["rehearsal"].get("prompt", {}),
                       "median": 10, "min": 4, "max": 30}
    burst["output"] = {"dist": "lognormal", "median": 4, "sigma": 0.7,
                       "min": 2, "max": 6}
    (bench / "traffic" / "serve-burst.json").write_text(json.dumps(burst))
    (bench / "readers" / "toy.py").write_text(
        "def rounds(ctx, scale=1.0):\n"
        "    return ctx.counters.get('rounds', 0) * scale or None\n")
    cell = "toy-qwen.serve-burst"
    metric = {"name": "rounds_in_window", "unit": "count", "better": "higher",
              "source": "host_clock", "layer": "v2 engine (host loop)",
              "moves": "tpot_p80_ms", "workloads": [cell]}
    (bench / "metrics" / "rounds_in_window.json").write_text(json.dumps(
        {**metric, "reader": "toy:rounds", "params": {"scale": 1.0}}))
    e2e = [{**e, "workloads": [cell]} if "workloads" in e else e
           for e in M.doc["end_to_end"]
           if e["name"] in ("ttft_p80_ms", "tpot_p80_ms", "setup_s")]
    doc = {"command": M.doc["command"], "paths": ["perfbench"],
           "run_seconds": M.doc["run_seconds"],
           "configs": [{"name": "toy-qwen", "source": toy["source"],
                        "file": "perfbench/configs/toy-qwen.json",
                        "reduced": [], "why": "throw-away"}],
           "workloads": [{"name": cell, "config": "toy-qwen",
                          "traffic": "serve-burst", "chips": 1,
                          "why": "throw-away"}],
           "end_to_end": e2e, "per_layer": [metric]}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))

    before = benchmark_mtimes()
    rc, lines, err = run_cell("--workload", cell, "--seed", "4", "--seconds",
                              "3", "--trace", "1", "--rehearsal",
                              manifest=path)
    assert rc == 0, err[-2000:]
    line = result_of(lines)
    assert line["correct"] is True and line["attempted"] == 15
    assert line["rehearsal_metrics"]["rounds_in_window"]["value"] > 0
    rc, lines, err = run_cell("--workload", cell, "--seed", "4", "--seconds",
                              "3", "--trace", "0", "--rehearsal",
                              manifest=path)
    assert rc == 0, err[-2000:]
    assert set(result_of(lines)["rehearsal_metrics"]) == {
        "ttft_p80_ms", "tpot_p80_ms", "setup_s"}
    assert before == benchmark_mtimes()
