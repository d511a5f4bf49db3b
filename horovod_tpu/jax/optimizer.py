"""DistributedOptimizer for JAX/optax.

The reference wraps framework optimizers so that gradients are allreduced
before being applied (reference: horovod/torch/optimizer.py:35-590,
horovod/tensorflow/__init__.py:453-855). The JAX-native equivalent is an
``optax.GradientTransformation`` that averages the incoming gradient pytree
across the mesh's data axis before the inner optimizer sees it.

Two execution paths (SURVEY.md §7 "eager enqueue vs XLA tracing"):

- **In-graph (the TPU fast path)**: when ``update`` runs under a jit trace
  (gradients are tracers), the whole gradient tree goes to ONE
  ``C.grouped_allreduce`` under the scope ``hvd_sync``: ``lax.psum``
  over the tuple of leaves WHERE THEY LIE and the division, with no
  flat buffer packed before it and none unpacked after it, the leaves
  in the tree's own order (reverse order compiles to the same step;
  PERF.md, PR 28). How many collectives the step runs is the decision
  of XLA's all-reduce combiner, not of this module. Measured on four
  v5e chips (PERF.md, PR 22 and PR 27): GPT-2-medium's 1.42 GB of
  gradients become 11 ``all-reduce``s with tuple operands in the
  gradients' own tiled layouts; they are synchronous on libtpu 0.0.34
  (asynchronous only under compiler options, which belong to the
  caller of ``jit``), and nothing runs beside them
  (``sync.exposed_ms`` = ``sync.collective_ms``). Over an axis of ONE
  chip the leaves are returned as they came: the compiled step holds
  no instruction under ``hvd_sync``. With a two-level ``(dcn, ici)``
  axis and ``HOROVOD_HIERARCHICAL_ALLREDUCE=1`` the group rides the
  hierarchical ladder (``parallel/hierarchical.py``), which owns its
  packing: a ``psum_scatter`` needs one buffer divisible by the ``ici``
  size. (Until PR 28 the tree was cut into 4 MiB "buckets" for an
  overlap with the backward pass that the chip never showed.)
- **Eager**: with concrete arrays and world size > 1, each leaf is
  submitted to the native core's negotiation queue exactly like the
  reference's per-gradient async enqueue (named tensors, fused by the
  coordinator).

``backward_passes_per_step`` reproduces local gradient aggregation
(reference: horovod/torch/optimizer.py:72-74,
horovod/tensorflow/gradient_aggregation.py:16-270): gradients accumulate
locally for k steps and the collective fires on the k-th.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.common import basics
from horovod_tpu.common.process_sets import global_process_set
from horovod_tpu.jax.compression import Compression
from horovod_tpu.jax.introspect import SCOPE_SYNC, SCOPE_UPDATE
from horovod_tpu.ops import collective_ops as C
from horovod_tpu.ops import eager
from horovod_tpu.parallel.mesh import DATA_AXIS
from horovod_tpu.parallel.mesh import traced_axis_size
from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils.timeline import trace_span

# Counted at trace time (in-graph collectives are invisible to Python
# per step): which way each gradient leaf went. ``in_place``:
# reduced where it lies, no copy; ``packed``: copied into a hierarchical
# ladder's flat buffer; ``skipped``: returned untouched because the axis
# has one chip.
_M_LEAVES = _metrics.counter(
    "hvd_grad_leaves_total",
    "Gradient leaves through the in-graph gradient sync, by route "
    "(counted at trace time).", ("route",))


def _scaled(wires, factor):
    """``wires`` as they came, times ``factor`` when that is not 1.0."""
    if factor == 1.0:
        return list(wires)
    return [w * jnp.asarray(factor, w.dtype) for w in wires]


def _is_tracing(grads) -> bool:
    leaves = jax.tree_util.tree_leaves(grads)
    return any(isinstance(l, jax.core.Tracer) for l in leaves)


def _axis_in_scope(axis) -> bool:
    """Whether ``axis`` is a bound mesh axis in the current trace.

    Under pjit auto-sharding over a GLOBAL mesh (jax.distributed) there
    is no named axis: the gradient pytree is a single logical array and
    XLA inserts the cross-process reduction from sharding constraints
    on its own, so the correct transformation is the identity. In a
    launcher-style multi-process job, where each process's jax sees
    only its own devices, no-axis tracing instead takes the io_callback
    host bridge (see allreduce_gradients).
    """
    try:
        traced_axis_size(axis)
        return True
    except NameError:
        return False


def _name_for_path(path) -> str:
    return "DistributedOptimizer.grad." + "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path
    )


def allreduce_gradients(
    grads,
    *,
    op: int = C.Average,
    axis=DATA_AXIS,
    process_set=global_process_set,
    compression=Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
):
    """Allreduce a gradient pytree; dispatches in-graph vs eager.

    In-graph: one grouped ``psum`` of the tree's leaves where they lie;
    nothing at all over an axis of one chip.
    Eager: grouped submission to the native core, names derived from tree
    paths so every rank agrees on tensor identity.
    """
    kwargs = dict(op=op, axis=axis, process_set=process_set,
                  compression=compression, prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor)
    if _is_tracing(grads) and _axis_in_scope(axis):
        # Compress, collective, division, decompress: the in-graph sync
        # as one named scope of the compiled step.
        leaves = len(jax.tree_util.tree_leaves(grads))
        with trace_span("sync", leaves=leaves), jax.named_scope(SCOPE_SYNC):
            return _allreduce_gradients(grads, **kwargs)
    return _allreduce_gradients(grads, **kwargs)


def _allreduce_gradients(grads, *, op, axis, process_set, compression,
                         prescale_factor, postscale_factor):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads

    compressed = [compression.compress(l) for l in leaves]
    wires = [c[0] for c in compressed]
    ctxs = [c[1] for c in compressed]

    if _is_tracing(wires) and _axis_in_scope(axis):
        fusable = (op in (C.Average, C.Sum)
                   and C._is_global_set(process_set))
        if fusable and traced_axis_size(axis) == 1:
            # One chip on the axis: the sum of one value and the
            # division by one. XLA drops a one-device all-reduce itself;
            # tracing nothing also leaves it nothing to schedule around.
            _M_LEAVES.labels("skipped").inc(len(wires))
            outs = _scaled(wires, prescale_factor * postscale_factor)
        else:
            # The whole tree as one group. ``C`` owns the route: the
            # leaves where they lie, or packed for the ``(dcn, ici)``
            # ladder.
            _M_LEAVES.labels(
                "packed" if C._route_hierarchical(
                    op, process_set, axis,
                    "HOROVOD_HIERARCHICAL_ALLREDUCE")
                else "in_place").inc(len(wires))
            outs = C.grouped_allreduce(
                wires, op,
                axis=axis, process_set=process_set,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            )
    elif (_is_tracing(wires) and basics.is_initialized()
          and basics.size() > 1 and jax.process_count() == 1):
        # Plain jit in a MULTI-PROCESS job (one chip per process, the
        # hvdrun launch shape — each process's jax sees only its own
        # devices, process_count()==1): XLA compiles this process's
        # program in isolation and cannot know about peer processes,
        # so "let the compiler insert the reduction" (the pjit story)
        # would silently train without gradient sync. Bridge to the
        # native collective from inside the compiled step instead;
        # ordered=True keeps every rank's collective sequence
        # identical across steps. In a jax.distributed job
        # (process_count() > 1) XLA DOES own the cross-process
        # reduction and the identity branch below stays correct.
        from jax.experimental import io_callback

        def _host_sync(*flat):
            handle = eager.grouped_allreduce_async(
                list(flat), name="DistributedOptimizer",
                op=op, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                process_set=process_set)
            return tuple(np.asarray(o)
                         for o in eager.synchronize(handle))

        shapes = tuple(jax.ShapeDtypeStruct(w.shape, w.dtype)
                       for w in wires)
        outs = list(io_callback(_host_sync, shapes, *wires,
                                ordered=True))
    elif (not _is_tracing(wires) and basics.is_initialized()
          and basics.size() > 1):
        paths = [
            _name_for_path(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(grads)[0]
        ]
        handle = eager.grouped_allreduce_async(
            wires, name="DistributedOptimizer",
            op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=process_set,
        )
        del paths  # names are deterministic via the grouped base name
        outs = eager.synchronize(handle)
        outs = [jnp.asarray(o) for o in outs]
    else:
        # Single process, concrete values: identity semantics.
        outs = _scaled(wires, prescale_factor * postscale_factor)

    outs = [compression.decompress(o, ctx) for o, ctx in zip(outs, ctxs)]
    return jax.tree_util.tree_unflatten(treedef, outs)


class _AllreduceState(NamedTuple):
    pass


def allreduce_transformation(
    op: int = C.Average,
    *,
    axis=DATA_AXIS,
    process_set=global_process_set,
    compression=Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
) -> optax.GradientTransformation:
    """An optax transformation that allreduces updates across the mesh."""

    def init_fn(params):
        del params
        return _AllreduceState()

    def update_fn(updates, state, params=None):
        del params
        reduced = allreduce_gradients(
            updates, op=op, axis=axis, process_set=process_set,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )
        return reduced, state

    return optax.GradientTransformation(init_fn, update_fn)


def _scoped_update(optimizer) -> optax.GradientTransformationExtraArgs:
    """``optimizer`` with its ``update`` traced under ``SCOPE_UPDATE``:
    the same ``init`` and state, the same arithmetic, and a name for its
    instructions in the compiled step."""
    inner = optax.with_extra_args_support(optimizer)

    def update_fn(updates, state, params=None, **extra_args):
        with trace_span("update"), jax.named_scope(SCOPE_UPDATE):
            return inner.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(inner.init, update_fn)


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: int = C.Average,
    axis=DATA_AXIS,
    process_set=global_process_set,
    compression=Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    backward_passes_per_step: int = 1,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient averaging.

    Usage (the TPU fast path — inside a pjit'd train step over a mesh)::

        tx = hvd.jax.DistributedOptimizer(optax.adamw(1e-3))
        updates, opt_state = tx.update(grads, opt_state, params)

    With ``backward_passes_per_step=k``, gradients accumulate locally and
    the allreduce + inner update fire every k-th call (zero updates are
    emitted in between).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    chained = optax.chain(
        allreduce_transformation(
            op, axis=axis, process_set=process_set, compression=compression,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        ),
        _scoped_update(optimizer),
    )
    if backward_passes_per_step == 1:
        return chained
    ms = optax.MultiSteps(chained, every_k_schedule=backward_passes_per_step)
    return optax.GradientTransformation(ms.init, ms.update)
