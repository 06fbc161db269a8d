"""`chip_smoke.py` rehearsed at its tiny size on the CPU, in a subprocess as
the driver runs it: same phases and control flow as on the chip, toy sizes,
Pallas kernels in interpret mode. What a rehearsal cannot show (that the
kernels compile for the chip) is tests/unit/ops/test_chip_compile.py's.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PHASES = ["start", "kernels", "train", "v1", "v2", "scopes"]


def _run(args, cache_dir, **extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("DS_TPU_FAULTS", "XLA_FLAGS",
                        "DS_TPU_PALLAS_INTERPRET", "DS_TPU_DISABLE_PALLAS")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               **extra)
    out = subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


def _checkout_cache():
    path = os.path.join(ROOT, ".jax_cache")
    return set(os.listdir(path)) if os.path.isdir(path) else set()


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("jax_cache")
    before = _checkout_cache()
    out, lines = _run(["--tiny", "--rehearsal"], cache_dir)
    return {"out": out, "lines": lines, "cache_dir": str(cache_dir),
            "checkout_cache_untouched": _checkout_cache() == before}


def test_rehearsal_exits_zero_with_every_phase_ok(rehearsal):
    assert rehearsal["out"].returncode == 0, rehearsal["out"].stderr[-3000:]
    phases = [l for l in rehearsal["lines"] if "phase" in l]
    assert [p["phase"] for p in phases] == PHASES
    assert all(p["ok"] for p in phases[1:])


def test_last_line_is_the_contract_and_names_the_cpu(rehearsal):
    last = rehearsal["out"].stdout.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_phase_lines_carry_what_is_worth_knowing(rehearsal):
    by = {l["phase"]: l for l in rehearsal["lines"] if "phase" in l}
    assert by["start"]["jax"] and by["start"]["sizes"] == "tiny"
    assert by["kernels"]["cases"] == len(by["kernels"]["rel_err"]) >= 24
    train = by["train"]
    assert train["loss_last"] < train["loss_first"]
    assert len(train["losses"]) >= 4 and train["mesh"] == {"dp": 1, "tp": 1}
    for name in ("train", "v1", "v2"):
        assert by[name]["compile_s"] >= 0 and by[name]["run_s"] > 0
        assert by[name]["dispatch"], "no attention implementation recorded"
        assert isinstance(by[name]["peak_bytes"], list)
    assert by["v1"]["serve_mode"] == by["v2"]["serve_mode"] == "dequant"
    assert by["v2"]["kv_layout"] == "paged"
    assert by["v2"]["requests"] > by["v2"]["slots"]  # sequences join and leave
    assert by["v2"]["flushed_sequences"] >= by["v2"]["requests"]
    n = by["v1"]["tokens_generated"]
    for name in ("v1", "v2"):
        checked = by[name]["vs_forward"]
        assert checked["tokens_argmax"] + checked["tokens_tied"] == n
    assert by["v2"]["vs_v1"]["identical_sequences"] <= by["v2"]["requests"]


def test_the_scopes_phase_holds_the_first_loss_and_names_its_programs(
        rehearsal):
    """Off the chip a profile has no device line, so the share the program
    map names is not judged here; what is: the first loss with the scopes
    and with every `with jax.named_scope` a no-op, bit for bit, and the map
    built from the executables that ran."""
    scopes = {l["phase"]: l for l in rehearsal["lines"]
              if "phase" in l}["scopes"]
    assert scopes["loss_first_hex"] == scopes["loss_first_hex_without_scopes"]
    assert scopes["train"]["programs"] == ["train:train_batch"]
    assert [p.split(":")[:2] for p in scopes["v1"]["programs"]] == \
        [["v1", "generate"]]
    for part in ("train", "v1"):
        assert scopes[part]["map_cache"] == ["memory"]
        assert scopes[part]["named_share"] is None


def test_cache_goes_where_the_environment_says_and_nowhere_else(rehearsal):
    start = rehearsal["lines"][0]
    assert start["compile_cache"] == rehearsal["cache_dir"]
    assert os.listdir(rehearsal["cache_dir"])
    assert rehearsal["checkout_cache_untouched"]


@pytest.mark.parametrize("env_dir", [None, "somewhere"])
def test_cache_helper_sets_a_directory_only_when_none_is_given(env_dir,
                                                               tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(ROOT, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("from benchmarks.compile_cache import enable_compile_cache\n"
            "import jax\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    assert out.stdout.split() == [want, want, "0.5"]


def test_without_the_rehearsal_flag_a_cpu_is_refused(tmp_path):
    out, lines = _run(["--tiny"], tmp_path)
    assert out.returncode == 2
    assert lines == [] and "not a TPU" in out.stderr


def test_a_phase_that_raises_fails_the_run(rehearsal):
    """An injected dispatch fault fails v1 (and v2, which compares with
    it); the other phases still run and report, the last line says
    `"ok": false` and the exit code is non-zero. Every phase runs whatever
    fails, so the run cannot end early; it takes its programs from the
    module's warm cache directory instead of compiling them anew (what is
    left, some 50-70 s, is the `kernels` phase: interpreted, and not
    cached)."""
    out, lines = _run(["--tiny", "--rehearsal"], rehearsal["cache_dir"],
                      DS_TPU_FAULTS="generate_dispatch:raise")
    assert out.returncode == 1
    by = {l["phase"]: l for l in lines if "phase" in l}
    assert [by[p]["ok"] for p in PHASES[1:]] == [True, True, False, False,
                                                 False]
    assert "InjectedFault" in by["v1"]["error"]
    assert lines[-1]["ok"] is False and lines[-1]["device"]["platform"] == "cpu"
