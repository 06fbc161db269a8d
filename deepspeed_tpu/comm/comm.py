"""Backend-agnostic communication facade.

Counterpart of the reference's `deepspeed/comm/comm.py` (787 LoC: module-level
collectives wrapped by `timed_op:101`, `init_distributed:619`) and
`comm/torch.py` (`TorchBackend`). Two planes exist on TPU:

1. **Traced plane** (the hot path): collectives *inside* jit over mesh axes —
   `psum`, `all_gather`, `reduce_scatter`, `all_to_all`, `ppermute`. These are
   the XLA/ICI counterpart of NCCL calls; most are inserted automatically by
   the partitioner from sharding annotations, and the explicit wrappers below
   are used inside `shard_map` regions (Ulysses, MoE dispatch, pipeline p2p).
2. **Host plane**: process-level coordination (rendezvous, barriers, scalar
   broadcast) via `jax.distributed` + multihost utils — the counterpart of the
   torch.distributed store/bootstrap.

Every wrapper logs to `CommsLogger` (volume at trace time; wall-clock for host
ops), mirroring `timed_op` → `utils/comms_logging.py`.
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence, Union

import numpy as np

from deepspeed_tpu.comm.comms_logging import get_comms_logger
from deepspeed_tpu.utils import groups as groups_mod
from deepspeed_tpu.utils.logging import logger

_INITIALIZED = False

# ---- reduce op enum for API parity (reference comm/comm.py ReduceOp) ----
class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "prod"


def _nbytes(x) -> int:
    try:
        return int(np.prod(x.shape)) * x.dtype.itemsize
    except Exception:
        return 0


def _axes(group: Union[str, Sequence[str], None]) -> Union[str, tuple]:
    """Resolve a group spec (axis name/alias or tuple) to canonical axis names."""
    if group is None:
        return tuple(groups_mod.MESH_AXES)
    if isinstance(group, str):
        return groups_mod.canonical_axis(group)
    return tuple(groups_mod.canonical_axis(g) for g in group)


# --------------------------------------------------------------------------
# Traced-plane collectives (usable inside jit / shard_map)
# --------------------------------------------------------------------------

def all_reduce(tensor, op: str = ReduceOp.SUM, group: Union[str, Sequence[str], None] = "data"):
    """lax.psum/pmax/... over a mesh axis. Reference comm.py:all_reduce:222."""
    import jax
    axes = _axes(group)
    get_comms_logger().record("all_reduce", _nbytes(tensor))
    if op == ReduceOp.SUM:
        return jax.lax.psum(tensor, axes)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(tensor, axes)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(tensor, axes)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(tensor, axes)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(tensor, group: Union[str, None] = "data", axis: int = 0, tiled: bool = True):
    """lax.all_gather; counterpart of all_gather_into_tensor (comm/torch.py:218)."""
    import jax
    get_comms_logger().record("all_gather", _nbytes(tensor))
    return jax.lax.all_gather(tensor, _axes(group), axis=axis, tiled=tiled)


def reduce_scatter(tensor, group: Union[str, None] = "data", scatter_dim: int = 0):
    """lax.psum_scatter; counterpart of reduce_scatter_tensor (comm/torch.py:268)."""
    import jax
    get_comms_logger().record("reduce_scatter", _nbytes(tensor))
    return jax.lax.psum_scatter(tensor, _axes(group), scatter_dimension=scatter_dim, tiled=True)


def reduce_scatter_by_exchange(tensor, group: Union[str, Sequence[str]] = "data",
                               scatter_dim: int = 0, sum_dtype=None):
    """`reduce_scatter`'s result, made of `n - 1` `ppermute`s and a LOCAL sum.

    Rank `i` keeps slice `i` of its own `tensor` along `scatter_dim`, sends
    slice `j` straight to rank `j`, and adds what the peers send it in
    `sum_dtype` (default: the tensor's own): `(n - 1) / n` of the tensor
    on the wire in each direction, a ring reduce-scatter's bytes, and no
    rounding between two partial sums. `group` may be a tuple of axes; ranks
    count in the axes' row-major order, as `axis_index` does.

    Why not `reduce_scatter`: on a v5e 2x2 the compiler keeps no
    reduce-scatter (`psum_scatter` over two chips comes out as an
    all-reduce of the WHOLE tensor and a slice, inside a `while` it is moved
    after the loop as well) and an `all_to_all` of the same slices takes as
    long as that all-reduce; the permute moves half the bytes and runs
    under the next matmul (my chip run, PR 52: `PERF.md` section 6)."""
    import jax
    axes = _axes(group)
    n = jax.lax.psum(1, axes)  # of a constant: the group's size, an int
    width = tensor.shape[scatter_dim] // n
    me = axis_index(axes)

    def slice_of(rank):
        return jax.lax.dynamic_slice_in_dim(tensor, rank * width, width,
                                            scatter_dim)

    total = slice_of(me).astype(sum_dtype or tensor.dtype)
    for shift in range(1, n):
        got = ppermute(slice_of((me + shift) % n),
                       [(i, (i + shift) % n) for i in range(n)], axes)
        total = total + got.astype(total.dtype)
    return total


# Ranks up to which `all_reduce_by_exchange` exchanges; past it, `psum`.
EXCHANGE_MAX_RANKS = 2


def all_reduce_by_exchange(tensor, group: Union[str, Sequence[str]] = "model"):
    """`all_reduce`'s sum over a SMALL group, made of `n - 1` `ppermute`s of
    the whole partial and a local sum; `psum` itself past
    `EXCHANGE_MAX_RANKS`.

    Every rank sends its partial to each peer and adds what it is sent in
    the tensor's own dtype. For `n` = 2 that is one permute each way and
    one add: the tensor once on the wire in each direction, a ring
    all-reduce's `2 (n - 1) / n` exactly, and `a + b` on one rank is
    `b + a` on the other, so both hold the same bits, the all-reduce's own.
    From `n` = 3 the exchange sends `n - 1` tensors where the ring sends
    `4 / 3` of one (`n` = 4: 3 against 1.5) and the ranks would add in
    different orders: there it is `psum`.

    Why not `psum` on two chips: on a v5e 2x2 an all-reduce is a synchronous
    op on the device's op line, nothing runs beside it; a
    `collective-permute` is asynchronous, and the scheduler starts it early
    and finishes it late around whatever independent work there is (my chip
    runs, PRs 52 and 53: `PERF.md` section 6)."""
    import jax
    axes = _axes(group)
    n = jax.lax.psum(1, axes)  # of a constant: the group's size, an int
    if n > EXCHANGE_MAX_RANKS:
        return all_reduce(tensor, group=group)
    total = tensor
    for shift in range(1, n):
        total = total + ppermute(
            tensor, [(i, (i + shift) % n) for i in range(n)], axes)
    return total


def all_to_all_single(tensor, group: Union[str, None] = "sequence",
                      split_axis: int = 0, concat_axis: int = 0, tiled: bool = True):
    """lax.all_to_all; counterpart of all_to_all_single (comm/torch.py:282)."""
    import jax
    get_comms_logger().record("all_to_all", _nbytes(tensor))
    return jax.lax.all_to_all(tensor, _axes(group), split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def ppermute(tensor, perm, group: str = "pipe"):
    """Point-to-point send/recv ring — the PP p2p analog (runtime/pipe/p2p.py)."""
    import jax
    get_comms_logger().record("ppermute", _nbytes(tensor))
    return jax.lax.ppermute(tensor, _axes(group), perm)


def axis_index(group: str = "data"):
    import jax
    return jax.lax.axis_index(_axes(group))


# --------------------------------------------------------------------------
# Host-plane API (process-level; mirrors torch.distributed surface)
# --------------------------------------------------------------------------

def init_distributed(dist_backend: Optional[str] = None,
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1) -> None:
    """Bootstrap multi-host JAX. Counterpart of reference comm.py:init_distributed:619.

    Single-process (or already-initialized) → no-op. Multi-host rendezvous uses
    `jax.distributed.initialize`, reading standard env (COORDINATOR_ADDRESS /
    JAX_PROCESS_ID / JAX_NUM_PROCESSES, with OMPI fallbacks mirroring the
    reference's MPI discovery at comm.py:688).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    import jax

    coord = os.environ.get("COORDINATOR_ADDRESS") or init_method
    nproc = int(os.environ.get("JAX_NUM_PROCESSES",
                os.environ.get("WORLD_SIZE", world_size if world_size > 0 else -1)))
    pid = int(os.environ.get("JAX_PROCESS_ID",
              os.environ.get("RANK", rank if rank >= 0 else -1)))
    if auto_mpi_discovery and (nproc < 0 or pid < 0):
        # launcher-family env discovery (reference comm.py:688 MPI discovery
        # + multinode_runner rank envs): OpenMPI, MPICH/Intel MPI (PMI),
        # SLURM srun, MVAPICH. The MPI-family runners export
        # JAX_NUM_PROCESSES to every rank but the RANK comes only from the
        # backend env — so the rank must be discoverable even when the
        # world size already is (pid < 0 alone triggers the scan).
        for size_k, rank_k in (
                ("OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK"),
                ("PMI_SIZE", "PMI_RANK"),
                ("SLURM_NTASKS", "SLURM_PROCID"),
                ("MV2_COMM_WORLD_SIZE", "MV2_COMM_WORLD_RANK")):
            # both halves required: an salloc shell exports SLURM_NTASKS
            # without SLURM_PROCID (srun-only) — that's not a launched rank
            if size_k in os.environ and rank_k in os.environ:
                if nproc < 0:
                    nproc = int(os.environ[size_k])
                if pid < 0:
                    pid = int(os.environ[rank_k])
                break

    if coord and nproc > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
        if verbose:
            logger.info(f"jax.distributed initialized: process {pid}/{nproc} @ {coord}")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank(group=None) -> int:
    import jax
    return jax.process_index()


def get_world_size(group=None) -> int:
    """Device-level world size (DeepSpeed's rank granularity is one device).
    A tuple group means the product of its axis sizes."""
    if group is not None:
        topo = groups_mod.get_topology()
        if isinstance(group, str):
            return topo.axis_size(group)
        import math
        return int(math.prod(topo.axis_size(g) for g in group))
    import jax
    return jax.device_count()


def get_local_rank() -> int:
    import jax
    return jax.process_index()


def barrier(group=None) -> None:
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("deepspeed_tpu.barrier")


def broadcast(tensor, src: int = 0, group=None):
    """Host-plane broadcast of a pytree from process `src` (reference comm.py:broadcast)."""
    import jax
    if jax.process_count() <= 1:
        return tensor
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(tensor, is_source=jax.process_index() == src)


def log_summary():
    get_comms_logger().log_all()


def initialize_mesh_device(mesh_shape, mesh_axis_names):
    """Reference comm/comm.py:603 — build a device mesh; returns jax Mesh."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh
    devs = mesh_utils.create_device_mesh(tuple(mesh_shape))
    return Mesh(devs, tuple(mesh_axis_names))
