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


# `tests/perfbench/test_oracle.py::test_a_dense_configuration_owes_no_margin`
# is parametrized over EVERY configuration of BENCHMARK.json and asserts that
# its reference exports no routing margin: true while every configuration
# was dense (PR 33). A configuration with routed experts must export one
# (`manifest.config_problems` refuses it otherwise), so its case asserts
# what cannot hold. The benchmark's files are not a `model_config` PR's to
# edit (PR 41): a case whose reference DOES export the margin is marked
# here as expected to fail, strictly, until a `benchmark` PR words the test
# for dense configurations only (ROADMAP B2 (i)).


def pytest_collection_modifyitems(config, items):
    for item in items:
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
