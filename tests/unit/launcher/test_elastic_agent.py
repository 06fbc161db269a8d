"""Elastic agent tests (reference tests: torch-elastic DSElasticAgent):
kill-a-rank on the 2-process CPU rendezvous harness must restart the
generation and resume training from the latest checkpoint; runner classes
must build correct backend argvs."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


WORKER = """\
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.utils import groups
from tests.simple_model import base_config, simple_params

deepspeed_tpu.init_distributed()
assert jax.process_count() == 2
rank = jax.process_index()
ckpt = os.environ["DS_TEST_CKPT"]
gen = int(os.environ["DS_ELASTIC_RESTART_COUNT"])

model, params = simple_params(hidden_dim=16)
topo = groups.MeshTopology(dp=2)
engine, *_ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, config=base_config(stage=2, mbs=4),
    topology=topo)
engine.load_checkpoint(ckpt)   # no-op on the first generation
start = int(engine.state.global_step)

rng = np.random.default_rng(7)
losses = []
for step in range(start, 4):
    local = {"x": rng.normal(size=(4, 8)).astype(np.float32),
             "y": rng.normal(size=(4, 8)).astype(np.float32)}
    losses.append(float(engine.train_batch(batch=local)))
    engine.save_checkpoint(ckpt)
    if step == 1 and gen == 0 and rank == 1:
        # simulated hardware failure AFTER step 2's checkpoint. A failed
        # host runs no exit handlers, so os._exit: a polite sys.exit goes
        # through jax's exit hook into jax.distributed's shutdown barrier
        # and waits its default 300 s for rank 0 (stuck in step 3's
        # collective) before the agent can see an exit code
        # (docs/resilience.md; the test took 330 s for it).
        os._exit(17)

with open(os.environ["DS_TEST_OUT"] + str(rank), "w") as f:
    f.write(f"{gen} {int(engine.state.global_step)} {losses[-1]:.8f}")
"""


def test_elastic_agent_restarts_after_rank_failure(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    runner = tmp_path / "agent.py"
    runner.write_text(textwrap.dedent(f"""\
        import os, sys
        os.environ["DS_TEST_CKPT"] = {str(tmp_path / "ckpt")!r}
        os.environ["DS_TEST_OUT"] = {str(tmp_path / "out")!r}
        os.environ["PYTHONPATH"] = {os.getcwd()!r} + os.pathsep + \
            os.environ.get("PYTHONPATH", "")
        from deepspeed_tpu.elasticity import DSElasticAgent
        agent = DSElasticAgent({str(script)!r}, num_procs=2, max_restarts=2)
        sys.exit(agent.run())
    """))
    proc = subprocess.run([sys.executable, str(runner)], timeout=600,
                          capture_output=True, text=True,
                          env={**os.environ,
                               "PYTHONPATH": os.getcwd() + os.pathsep +
                               os.environ.get("PYTHONPATH", "")})
    if "Multiprocess computations aren't implemented" in (proc.stdout +
                                                          proc.stderr):
        pytest.skip("this jaxlib's CPU backend cannot run multiprocess "
                    "computations (works on current jax / real TPU)")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    r0 = (tmp_path / "out0").read_text().split()
    r1 = (tmp_path / "out1").read_text().split()
    assert r0[0] == "1" and r1[0] == "1"      # finished on generation 1
    assert r0[1] == "4" and r1[1] == "4"      # 4 optimizer steps total
    assert r0[2] == r1[2]                     # ranks agree on the loss


def test_elastic_agent_gives_up_after_budget(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(9)\n")
    from deepspeed_tpu.elasticity import DSElasticAgent
    agent = DSElasticAgent(str(script), num_procs=2, max_restarts=1,
                           monitor_interval=0.05)
    assert agent.run() == 9
    assert agent.restart_count == 2  # initial + 1 restart, then give up


def test_elastic_env_batch_recompute(tmp_path):
    """On a world-size change the agent recomputes the (mbs, gas) split from
    the elasticity config and exports it to workers."""
    from deepspeed_tpu.elasticity import DSElasticAgent
    ds_config = {"elasticity": {
        "enabled": True, "max_train_batch_size": 64,
        "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 16,
        "min_time": 0, "version": 0.2}}
    agent = DSElasticAgent("x.py", ds_config=ds_config)
    # golden batch for this config is 60 (most compatible world sizes);
    # 10 and 5 are in its valid set — a shrink from 10 to 5 doubles GAS
    env10 = agent._elastic_env(10)
    env5 = agent._elastic_env(5)
    for env, world in ((env10, 10), (env5, 5)):
        gb = int(env["DS_ELASTIC_GLOBAL_BATCH"])
        mbs = int(env["DS_ELASTIC_MICRO_BATCH"])
        gas = int(env["DS_ELASTIC_GAS"])
        assert mbs * gas * world == gb <= 64
    assert env10["DS_ELASTIC_GLOBAL_BATCH"] == env5["DS_ELASTIC_GLOBAL_BATCH"]
    # an incompatible world no longer crashes the supervisor: run()
    # clamps to the NEAREST compatible size at or below BEFORE spawning
    # (ADVICE r3) — here 8 is invalid, 6 is the nearest below, and the
    # spawned world and the exported batch split agree
    w8 = agent._compatible_world(8)
    assert w8 == 6
    env8 = agent._elastic_env(w8)
    assert int(env8["DS_ELASTIC_WORLD_SIZE"]) == 6
    assert int(env8["DS_ELASTIC_GLOBAL_BATCH"]) % \
        (int(env8["DS_ELASTIC_MICRO_BATCH"]) * w8) == 0


# ---------------------------------------------------------------- runners
def _args(**kw):
    import argparse
    ns = argparse.Namespace(include="", exclude="", num_nodes=-1,
                            num_procs=-1, user_script="train.py",
                            user_args=["--flag"], launcher_args="")
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_runner_cmds():
    from deepspeed_tpu.launcher.multinode_runner import (
        IMPIRunner, MPICHRunner, OpenMPIRunner, SlurmRunner)
    hosts = {"n1": 2, "n2": 2}
    env = {"MASTER_ADDR": "n1", "MASTER_PORT": "29500"}

    r = OpenMPIRunner(_args(), hosts)
    r.add_export("COORDINATOR_ADDRESS", "n1:29500")
    cmd = r.get_cmd(env, {})
    assert cmd[:3] == ["mpirun", "-n", "4"]
    assert "n1:2,n2:2" in cmd
    assert "COORDINATOR_ADDRESS=n1:29500" in cmd
    assert cmd[-2:] == ["train.py", "--flag"]

    r = MPICHRunner(_args(), hosts)
    cmd = r.get_cmd(env, {})
    assert cmd[:3] == ["mpirun", "-n", "4"] and "-ppn" in cmd

    r = IMPIRunner(_args(), hosts)
    cmd = r.get_cmd(env, {})
    assert "-ppn" in cmd and cmd[-2:] == ["train.py", "--flag"]

    s = SlurmRunner(_args(num_nodes=2, include="n1@n2"), hosts)
    s.add_export("JAX_NUM_PROCESSES", "4")
    cmd = s.get_cmd(env, {})
    assert cmd[:3] == ["srun", "-n", "4"]
    assert "--nodelist" in cmd and "n1,n2" in cmd
    assert any(a.startswith("ALL,JAX_NUM_PROCESSES=4") for a in cmd)


def test_openmpi_rejects_filters():
    from deepspeed_tpu.launcher.multinode_runner import OpenMPIRunner
    with pytest.raises(ValueError, match="include"):
        OpenMPIRunner(_args(include="n1"), {"n1": 2}).validate_args()


def test_mpi_rank_env_discovery(tmp_path):
    """A worker launched with only SLURM/PMI-style env resolves its rank
    (comm.init_distributed backend env discovery)."""
    script = tmp_path / "w.py"
    script.write_text(textwrap.dedent("""\
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)
        import jax
        jax.config.update("jax_platforms", "cpu")
        import deepspeed_tpu
        deepspeed_tpu.init_distributed()
        assert jax.process_count() == 2, jax.process_count()
        print("RANK_OK", jax.process_index())
    """))
    import socket
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    procs = []
    for rank in range(2):
        env = {**os.environ,
               "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
               "SLURM_NTASKS": "2", "SLURM_PROCID": str(rank),
               # the MPI-family runners export the world size to every rank
               # — the rank itself must still be discovered from the
               # backend env (regression: discovery used to be gated on
               # the world size being unknown)
               "JAX_NUM_PROCESSES": "2",
               "PYTHONPATH": os.getcwd() + os.pathsep +
               os.environ.get("PYTHONPATH", "")}
        env.pop("JAX_PROCESS_ID", None)
        env.pop("RANK", None)
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=env, stdout=subprocess.PIPE,
                                      text=True))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert sorted(o.strip().splitlines()[-1] for o in outs) == \
        ["RANK_OK 0", "RANK_OK 1"]
