"""`deepspeed_tpu.comm` — the `deepspeed.comm` counterpart (reference comm/comm.py)."""
from deepspeed_tpu.comm.comm import (
    ReduceOp,
    all_gather,
    all_reduce,
    all_reduce_by_exchange,
    all_to_all_single,
    axis_index,
    barrier,
    broadcast,
    get_local_rank,
    get_rank,
    get_world_size,
    init_distributed,
    initialize_mesh_device,
    is_initialized,
    log_summary,
    ppermute,
    reduce_scatter,
    reduce_scatter_by_exchange,
)
from deepspeed_tpu.comm.comms_logging import CommsLogger, get_comms_logger

__all__ = [
    "ReduceOp", "all_gather", "all_reduce", "all_reduce_by_exchange",
    "all_to_all_single", "axis_index", "barrier", "broadcast",
    "get_local_rank", "get_rank", "get_world_size", "init_distributed",
    "initialize_mesh_device", "is_initialized",
    "log_summary", "ppermute", "reduce_scatter", "reduce_scatter_by_exchange",
    "CommsLogger", "get_comms_logger",
]
