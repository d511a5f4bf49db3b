"""Device-mesh management: the TPU-native substrate for every parallelism
strategy in horovod_tpu.

Where the reference builds MPI/NCCL/Gloo communicators
(reference: horovod/common/mpi/mpi_context.cc:1-263,
horovod/common/gloo/gloo_context.cc:150-230), the TPU build arranges chips
into a ``jax.sharding.Mesh`` and lets XLA lower collectives onto ICI/DCN.
Standard axis names:

- ``data``  — data parallelism (gradient psum rides this axis).
- ``model`` — tensor parallelism (matmul shard axis).
- ``seq``   — sequence/context parallelism (ring attention / Ulysses).
- ``expert``— expert parallelism for MoE all_to_all.
- ``pipe``  — pipeline stages.

Hierarchical collectives (the analog of NCCLHierarchicalAllreduce,
reference: horovod/common/ops/nccl_operations.cc:233-440) use a 2-level
factorization of the data axis: ``data_ici`` (intra-slice) x ``data_dcn``
(cross-slice); see ``horovod_tpu.parallel.hierarchical``.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


def shard_map_compat(f, *, mesh, in_specs, out_specs, axis_names=None,
                     check_vma=False):
    """``jax.shard_map`` with the framework's defaults.

    Replication checking defaults off: the framework's collectives use
    ``axis_index_groups``, which the checker does not support -- a
    caller shard-mapping plain jax code can opt back in with
    ``check_vma=True``. ``axis_names`` makes only those mesh axes
    manual (partial-manual); ``None`` makes every axis manual.

    This is the ONE sanctioned spelling of shard_map outside this
    module: the jaxcompat checker (docs/static_analysis.md#jax-compat)
    flags every direct ``jax.shard_map`` / ``jax.experimental``
    import elsewhere.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(axis_names or ()), check_vma=check_vma)


def traced_axis_size(axis) -> int:
    """Size of a bound mesh axis (or axis tuple) inside a trace.

    ``lax.axis_size``: a Python int at trace time, and ``NameError``
    for an unbound name, which callers use as their in-scope probe.
    """
    return jax.lax.axis_size(axis)


# Outer-to-inner mesh order. The hierarchical factorization of the
# data axis (parallel/hierarchical.py: "data_dcn" x "data_ici") sits in
# the data slot — data_dcn OUTERMOST so the slice boundary of a real
# multi-slice pod falls between dcn groups, and data_ici directly
# inside it so ici neighbors stay physically adjacent. (Before ISSUE
# 13 these two fell through to the custom-axes-last branch, which put
# any standard axis — e.g. a model axis — OUTSIDE them: on a real pod
# that routed blocking tensor-parallel collectives across DCN while
# the ladder's "slow" psum rode ICI, inverting the hierarchy's whole
# bandwidth argument.)
_STANDARD_ORDER = (PIPE_AXIS, "data_dcn", "data_ici", DATA_AXIS,
                   EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)

_lock = threading.Lock()
_global_mesh: Optional[Mesh] = None


def make_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh over ``devices`` (default: all visible devices).

    ``axis_sizes`` maps axis name -> size; a single ``-1`` entry is inferred
    from the device count. With no argument, returns a 1-D ``data`` mesh —
    the plain data-parallel layout matching the reference's single flat
    communicator.

    Axes are laid out in the order pipe, data_dcn, data_ici, data,
    expert, seq, model (outer to inner) so that the innermost (most
    communication-intensive) axes land on adjacent devices — on a real
    pod that keeps tensor/sequence collectives on the fastest ICI
    links, and puts the slice boundary of a multi-slice pod between
    ``data_dcn`` groups; axes not named in ``axis_sizes`` are omitted.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = len(devs)
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: n}
    axis_sizes = dict(axis_sizes)

    infer = [k for k, v in axis_sizes.items() if v == -1]
    if len(infer) > 1:
        raise ValueError("At most one axis size may be -1, got %r" % (axis_sizes,))
    known = math.prod(v for v in axis_sizes.values() if v != -1)
    if infer:
        if n % known:
            raise ValueError(
                "Cannot infer axis %r: %d devices not divisible by %d"
                % (infer[0], n, known)
            )
        axis_sizes[infer[0]] = n // known
    if math.prod(axis_sizes.values()) != n:
        raise ValueError(
            "Mesh axes %r multiply to %d but %d devices are available"
            % (axis_sizes, math.prod(axis_sizes.values()), n)
        )

    names = [a for a in _STANDARD_ORDER if a in axis_sizes]
    names += [a for a in axis_sizes if a not in names]  # custom axes last
    shape = [axis_sizes[a] for a in names]
    dev_array = np.asarray(devs).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(names))


def set_global_mesh(mesh: Optional[Mesh]):
    """Install the process-wide default mesh used by eager collectives and
    ``DistributedOptimizer`` when no mesh is passed explicitly."""
    global _global_mesh
    with _lock:
        _global_mesh = mesh


def global_mesh() -> Mesh:
    """The installed global mesh, creating a default 1-D data mesh on first
    use."""
    global _global_mesh
    with _lock:
        if _global_mesh is None:
            _global_mesh = make_mesh()
        return _global_mesh


def reset_global_mesh():
    set_global_mesh(None)


def data_sharding(mesh: Optional[Mesh] = None, *ranked_axes) -> NamedSharding:
    """NamedSharding that shards the leading dim over ``data`` (batch
    sharding), remaining dims replicated."""
    mesh = mesh or global_mesh()
    return NamedSharding(mesh, P(DATA_AXIS, *ranked_axes))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or global_mesh()
    return NamedSharding(mesh, P())


def axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or global_mesh()
    return mesh.shape.get(axis, 1)
