"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod.

Data plane: XLA collectives (psum / all_gather / all_to_all /
psum_scatter) over a ``jax.sharding.Mesh`` riding ICI/DCN.
Control plane: a native C++ coordination core (coordinator/worker tensor
negotiation, response cache, tensor fusion, stall detection) over a TCP
full mesh bootstrapped by an HTTP rendezvous — the role MPI/Gloo play in
the reference (see SURVEY.md for the reference layer map).

Top-level usage mirrors Horovod::

    import horovod_tpu as hvd
    hvd.init()
    ...
    avg = hvd.allreduce(grad, name="g")        # eager, handle-based under the hood
    # or, inside a pjit/shard_map training step (the TPU fast path):
    g = hvd.allreduce_ingraph(g, op=hvd.Average, axis="data")
"""

import time as _time

_IMPORT_BEGAN = _time.time()   # the launch's `import` span, filed below

__version__ = "0.2.0"

from horovod_tpu.common import (  # noqa: F401
    Compression,
    HorovodAbortedError,
    HorovodInternalError,
    HostsUpdatedInterrupt,
    ProcessSet,
    add_process_set,
    cross_rank,
    cross_size,
    dump_flight_record,
    get_process_set_ids,
    global_process_set,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    metrics_snapshot,
    rank,
    remove_process_set,
    shutdown,
    size,
    start_metrics_server,
    start_timeline,
    stop_metrics_server,
    stop_timeline,
)
from horovod_tpu.common.basics import (  # noqa: F401
    ccl_built,
    cuda_built,
    ddl_built,
    gloo_built,
    gloo_enabled,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rocm_built,
    tpu_built,
)
from horovod_tpu.ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    Sum,
    allgather,
    allgather_async,
    allgather_ingraph,
    allreduce,
    allreduce_async,
    allreduce_ingraph,
    alltoall,
    alltoall_async,
    alltoall_ingraph,
    barrier,
    broadcast,
    broadcast_async,
    broadcast_ingraph,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_allreduce_ingraph,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    reducescatter_ingraph,
    synchronize,
)
from horovod_tpu.common.objects import (  # noqa: F401
    allgather_object,
    broadcast_object,
)
from horovod_tpu.parallel import (  # noqa: F401
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    global_mesh,
    make_mesh,
    set_global_mesh,
)
from horovod_tpu.utils.timeline import (  # noqa: F401
    LAUNCH_LOG as _LAUNCH_LOG,
    launch_spans,
)


def run(*args, **kwargs):
    """Programmatic launcher at the package root (reference:
    horovod/__init__.py re-exports horovod.runner.run). Imported
    lazily: the runner pulls in cloudpickle/subprocess machinery that
    plain training imports never need."""
    from horovod_tpu.runner import run as _run

    return _run(*args, **kwargs)


def __getattr__(name):
    """Lazy subsystem attributes (PEP 562): ``hvd.serve`` loads the
    inference-serving subsystem (docs/serving.md) on first touch —
    training imports never pay for it, and the serve package itself
    defers jax until a replica loads a real model. ``hvd.plan`` (plus
    the Plan/Topology/Workload types) resolves the sharding planner
    (docs/planner.md) the same way: the planner drags in the whole
    parallel strategy stack, which data-parallel-only jobs never
    touch."""
    if name == "serve":
        import horovod_tpu.serve as _serve

        return _serve
    if name in ("plan", "Plan", "PlanError", "Topology", "Workload"):
        from horovod_tpu import parallel as _parallel

        return getattr(_parallel, name)
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))


_LAUNCH_LOG.record("import", _IMPORT_BEGAN, _time.time())
