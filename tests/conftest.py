"""Test harness: 8 virtual CPU devices on one host.

Counterpart of the reference's `tests/unit/common.py` DistributedTest
machinery (`common.py:416`): where the reference forks N processes per test to
fake a cluster over NCCL/gloo, the TPU build runs SPMD over a virtual
8-device CPU mesh (`--xla_force_host_platform_device_count`), which exercises
the same collectives XLA emits on a real pod.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# Force the CPU backend even where a TPU is attached: unit tests always run
# on the virtual 8-device mesh. The config update covers an interpreter
# that imported jax before this file set the environment.
if not os.environ.get("DS_TPU_TEST_REAL"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DS_ACCELERATOR"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
import pytest  # noqa: E402

from deepspeed_tpu.utils import groups  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_topology():
    groups.reset_topology()
    yield
    groups.reset_topology()


@pytest.fixture
def devices():
    return jax.devices()


# `perfbench/harness.py` traces into ONE directory a checkout and clears it
# first (PERF.md, section 7), so two traced rehearsals in two workers can
# delete each other's trace: `stop_trace` then fails with NOT_FOUND (one
# whole run in three at PR 48, one in two of PR 50's once the long files
# went out first and tests/perfbench/ ran closer together). The benchmark's
# files are not a test's to edit, so its traced rehearsals take turns, on a
# lock file beside that directory. A traced rehearsal a `benchmark` PR adds
# is named here too, until the harness traces into a directory a run.
TRACED_REHEARSALS = frozenset((
    "test_the_traced_rehearsal_of_the_cell_runs_on_the_cpu",
    "test_the_traced_rehearsal_of_the_keye_cell_runs_on_the_cpu",
    "test_the_traced_rehearsal_of_the_deepseek_cell_runs_on_the_cpu",
    "test_the_traced_rehearsal_of_the_openpangu_cell_runs_on_the_cpu",
    "test_the_traced_rehearsal_of_the_afmoe_cell_runs_on_the_cpu",
    "test_the_traced_rehearsal_of_the_qwen3_next_cell_runs_on_the_cpu",
    "test_traced_rehearsal_lists_every_new_program_metric",
    "test_setup_metrics_in_the_other_kinds_of_cell",
    "test_traced_rehearsal_reads_no_device_metric",
    "test_a_second_family_is_new_files_only",
    "test_new_config_traffic_and_metric_are_new_files_only",
    "test_traced_rehearsal_lists_the_new_metrics"))


@pytest.fixture(autouse=True)
def _one_traced_rehearsal_at_a_time(request):
    if request.node.originalname not in TRACED_REHEARSALS \
            or "tests/perfbench/" not in request.node.nodeid:
        yield
        return
    import fcntl
    cache_dir = os.path.join(str(request.config.rootpath), ".perfbench_cache")
    os.makedirs(cache_dir, exist_ok=True)
    with open(os.path.join(cache_dir, "trace.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)       # released when it is closed
        yield


# `--dist loadfile` gives a file to ONE worker, and xdist hands files out by
# NUMBER OF TESTS, most first (`--loadscope-reorder`, its default): the long
# files of few tests (a subprocess harness of 8 tests and four minutes, a
# rehearsal of 14 and six) go out LAST, and the run ends waiting for them,
# five workers idle (PR 50: 66 s of a 976 s run; 152 s of PR 49's 1163 s).
# So the reorder is turned off, files go out in collection order, and the
# files below are collected first: the longest of tier 1, longest first
# (`python tools/tier1_times.py` prints them: those over 100 s). Order only:
# a file that is missing here, or no longer long, costs seconds and never a
# result; none leans on state another file leaves in its worker.
LONGEST_FIRST = (
    "tests/perfbench/test_rehearsal.py",
    "tests/unit/test_chip_smoke.py",
    "tests/unit/inference/test_filled_chunk_rounds.py",
    "tests/unit/pipe/test_pipeline_zoo.py",
    "tests/unit/ops/test_decode_attention.py",
    "tests/unit/inference/test_kv_pool_decode_kernel.py",
    "tests/unit/models/test_llama_tp_exchange.py",
    "tests/unit/models/test_qwen3_next.py",
    "tests/unit/models/test_ling_linear.py",
    "tests/unit/models/test_afmoe.py",
    "tests/unit/inference/test_kv_pool_in_place.py",
    "tests/perfbench/test_keye_cell.py",
    "tests/unit/models/test_keye_sparse.py",
    "tests/unit/models/test_deepseek_sparse.py",
    "tests/perfbench/test_ling_cell.py",
    "tests/unit/ops/test_chip_compile.py",
    "tests/perfbench/test_oracle.py",
    "tests/unit/sequence/test_sequence.py",
    "tests/unit/moe/test_moe.py",
    "tests/unit/inference/test_paged_kv.py",
    "tests/unit/inference/test_kv_pool_prefill_writer_kernels.py",
    "tests/unit/pipe/test_pipeline.py",
    "tests/unit/models/test_nemotron_h.py",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):      # xdist is loaded
        config.option.loadscopereorder = False


# `tests/perfbench/test_oracle.py::test_a_dense_configuration_owes_no_margin`
# is parametrized over EVERY configuration of BENCHMARK.json and asserts that
# its reference exports no routing margin: true while every configuration
# was dense (PR 33). A configuration with routed experts must export one
# (`manifest.config_problems` refuses it otherwise), so its case asserts
# what cannot hold. The benchmark's files are not a `model_config` PR's to
# edit (PR 41): a case whose reference DOES export the margin is marked
# here as expected to fail, strictly, until a `benchmark` PR words the test
# for dense configurations only (ROADMAP B2 (i)).


#
# `tests/perfbench/test_manifest.py::test_problems_are_found` makes ONE more
# cell of BENCHMARK.json a four-chip cell and asserts that `problems` names
# the share of four-chip cells: true while the benchmark had four to seven
# cells (a quarter, rounded down, is one). With the eighth cell (PR 51) two
# may ask for four chips, and the edit the test makes is no problem any more.
# The file is the benchmark's; it is held here, strictly, until a `benchmark`
# PR makes its edit one cell more than the quarter (ROADMAP B2 (i)), and
# `tests/perfbench/test_keye_cell.py` plants the same three faults one cell
# past the quarter meanwhile.
PREDATES_THE_EIGHTH_CELL = \
    "tests/perfbench/test_manifest.py::test_problems_are_found"


# `tests/perfbench/test_deepseek_cell.py::test_the_manifest_may_be_sent_and_
# the_cut_is_the_issue_s` ends on `len(workloads) == 9`, true until the tenth
# cell (PR 58). The file is the benchmark's; it is held here, strictly, until
# a `benchmark` PR words the count as "at least" (ROADMAP B2 (i)), and
# `tests/perfbench/test_openpangu_cell.py` makes EVERY other assertion of it
# meanwhile, line for line, from that file's own tables (its source's widths
# key for key, `reduced`, `assumed`, the deployment, the rehearsal sizes, the
# metrics' lists, the one four-chip cell).
PREDATES_THE_TENTH_CELL = (
    "tests/perfbench/test_deepseek_cell.py::"
    "test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s")
# `tests/perfbench/test_afmoe_cell.py::test_the_manifest_may_be_sent_and_the_
# cut_is_the_issue_s` asserts that `full_decode_attn_roofline.gen` lists
# Trinity's cell ALONE, true until ISSUE 64's cell joined that list (PR 64).
# The file is the benchmark's; it is held here, strictly, until a `benchmark`
# PR words that line as "lists the cell", and
# `tests/perfbench/test_qwen3_next_cell.py` runs the test's own body on the
# manifest less that one entry meanwhile.
PREDATES_THE_SECOND_FULL_ROW_CELL = (
    "tests/perfbench/test_afmoe_cell.py::"
    "test_the_manifest_may_be_sent_and_the_cut_is_the_issue_s")
# a test's node id -> why it is expected to fail, strictly
PREDATES = {
    PREDATES_THE_SECOND_FULL_ROW_CELL: "full_decode_attn_roofline.gen lists "
                                       "two cells; the test names the one "
                                       "it was written at",
    PREDATES_THE_EIGHTH_CELL: "one four-chip cell more is within the quarter "
                              "the contract allows of eight cells",
    PREDATES_THE_TENTH_CELL: "the benchmark has ten cells; the test counts "
                             "the nine it was written at"}


def pytest_collection_modifyitems(config, items):
    rank = {path: i for i, path in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.nodeid.split("::")[0],
                                         len(rank)))    # stable for the rest
    for item in items:
        held = next((why for node, why in PREDATES.items()
                     if item.nodeid.endswith(node)), None)
        if held:
            item.add_marker(pytest.mark.xfail(strict=True, reason=held))
            continue
        if getattr(item, "originalname", None) != \
                "test_a_dense_configuration_owes_no_margin":
            continue
        from perfbench.manifest import Manifest
        from perfbench.runners_common import MARGIN_FN
        manifest = Manifest()
        sizes = manifest.config(item.callspec.params["config"])
        if hasattr(manifest.module("configs", sizes["reference"]), MARGIN_FN):
            item.add_marker(pytest.mark.xfail(strict=True, reason=(
                "a routed configuration owes the margin this case asserts "
                "it lacks; the test predates it (PR 33)")))
