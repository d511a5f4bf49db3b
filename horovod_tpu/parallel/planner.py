"""One cost-model-driven sharding planner over the whole parallel/ stack.

``parallel/`` grew mesh, hierarchical, Adasum, MoE, pipeline and
sequence modules, but composing them was manual: every training
script hand-picked axis sizes and hand-wired the gradient-sync
strategy. This module is the single owner of layout — the seam
GSPMD/Alpa-style systems put their auto-sharding pass behind, and the
reference never needed because it only does data parallelism
(PAPER.md layer map L5/L6).

``plan()`` takes a workload description (a params pytree or byte
count, batch/seq/model dims, optional MoE/pipeline counts) and a
device topology (chip count with its ICI x DCN factorization) and
returns a :class:`Plan`: the mesh axis dict, per-leaf PartitionSpecs,
and the gradient-sync strategy (flat psum vs the hierarchical
ladder). Axis assignment is scored by the explicit cost model in
``parallel/costmodel.py`` — every legal factorization is enumerated
and the report shows the losers and why.

Three surfaces (docs/planner.md):

- ``hvd.plan(...)`` → Plan + ``Plan.report()`` human-readable debug
  report (pure Python over the cost table — never traces);
- ``Plan.apply()`` installs the global mesh and the hierarchical
  routing flag so ``DistributedOptimizer`` / ``shard_map_compat``
  pick the planned layout up;
- ``__graft_entry__.dryrun_multichip`` routes its mesh choices through
  here and, under ``HVD_PLAN=sweep``, sweeps planner-chosen meshes
  across workload shapes instead of the fixed 2x2x2.

Emitted specs stay on the FULL-manual shard_map path
(``Plan.shard_map`` makes every mesh axis manual via
``shard_map_compat``): the plan's per-leaf specs describe every axis,
so nothing is left for XLA to partition. (Partial-manual programs do
compile on the installed jax -- ``dryrun_multichip`` runs one on the
chip -- so this is a choice, not a workaround; ROADMAP D5.)
"""

from __future__ import annotations

import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from horovod_tpu.parallel import costmodel
from horovod_tpu.parallel.costmodel import (  # noqa: F401  (re-export)
    Candidate,
    PlanError,
    Topology,
    Workload,
)
from horovod_tpu.parallel.hierarchical import DCN_AXIS, ICI_AXIS
from horovod_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    make_mesh,
    set_global_mesh,
    shard_map_compat,
)
from horovod_tpu.utils.timeline import LAUNCH_LOG

__all__ = [
    "Plan", "PlanError", "Topology", "Workload", "plan",
    "workload_from_params",
]


def workload_from_params(params, *, batch: int, seq_len: int = 1,
                         d_model: Optional[int] = None,
                         n_layers: int = 1,
                         num_experts: int = 0,
                         pipeline_stages: int = 0,
                         dtype_bytes: Optional[int] = None,
                         live_layers: int = 0) -> Workload:
    """Build a :class:`Workload` from a real (or eval_shape'd) pytree.

    ``param_bytes`` sums every leaf; leaves whose leading dim equals
    ``num_experts`` are counted as expert weights (sharded over the
    ``expert`` axis instead of replicated, which is what makes expert
    parallelism pay off in the cost model). ``d_model`` defaults to
    the most common trailing dim of the >=2-D leaves, and
    ``dtype_bytes`` (the activation element width in the cost model)
    to the bytes-weighted dominant leaf itemsize — a bf16 model plans
    with 2-byte activations, not a hardcoded fp32 width. Override
    either when the pytree is not representative.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    total = 0
    expert_bytes = 0
    trailing: Dict[int, int] = {}
    bytes_by_itemsize: Dict[int, int] = {}
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        itemsize = int(jax.numpy.dtype(leaf.dtype).itemsize)
        nbytes = int(math.prod(shape)) * itemsize
        total += nbytes
        bytes_by_itemsize[itemsize] = \
            bytes_by_itemsize.get(itemsize, 0) + nbytes
        if num_experts and shape and shape[0] == num_experts:
            expert_bytes += nbytes
        if len(shape) >= 2:
            trailing[shape[-1]] = trailing.get(shape[-1], 0) + 1
    if d_model is None:
        d_model = max(trailing, key=lambda k: (trailing[k], k)) \
            if trailing else 1
    if dtype_bytes is None:
        dtype_bytes = max(bytes_by_itemsize,
                          key=lambda k: (bytes_by_itemsize[k], k)) \
            if bytes_by_itemsize else 4
    return Workload(
        param_bytes=total, batch=batch, seq_len=seq_len, d_model=d_model,
        n_layers=n_layers, dtype_bytes=int(dtype_bytes),
        num_experts=num_experts, expert_param_bytes=expert_bytes,
        pipeline_stages=pipeline_stages, live_layers=live_layers)


class Plan:
    """A composed layout: mesh axes + per-leaf specs + sync strategy.

    Immutable value object built by :func:`plan`; ``apply()`` is the
    only method with side effects (installs the global mesh and the
    hierarchical routing flag).
    """

    def __init__(self, *, mesh_axes: Dict[str, int],
                 data_axes: Tuple[str, ...],
                 grad_axes: Tuple[str, ...], sync: str,
                 workload: Workload,
                 topology: Topology, chosen: Candidate,
                 rejected: Sequence[Candidate]):
        self.mesh_axes = dict(mesh_axes)
        # Axes the BATCH dim is sharded over (data, or its dcn x ici
        # factorization on multi-slice topologies).
        self.data_axes = tuple(data_axes)
        # Axes gradients must be summed over — every token-sharding
        # axis, i.e. data plus seq when present. The expert axis is
        # deliberately excluded: expert weights are distinct per
        # expert, and averaging them across the expert axis would be
        # numerically wrong (expert-weight replicas live on the
        # data x seq grid only).
        self.grad_axes = tuple(grad_axes)
        self.sync = sync          # "none" | "psum" | "hierarchical"
        self.workload = workload
        self.topology = topology
        self.chosen = chosen
        self.rejected = list(rejected)

    # -- install ----------------------------------------------------------

    def apply(self, devices=None):
        """Build the mesh, install it process-wide, and arm the routing
        the plan's sync strategy needs. Returns the mesh.

        After ``apply()``, ``DistributedOptimizer(tx,
        axis=plan.data_axes)`` (or :meth:`optimizer`) syncs gradients
        exactly as planned: one grouped psum on a flat data
        axis, the ``grouped_hierarchical_allreduce`` ladder on a
        ``(data_dcn, data_ici)`` factorization.
        """
        with LAUNCH_LOG.span("plan/apply", axes=dict(self.mesh_axes)):
            mesh = make_mesh(self.mesh_axes, devices=devices)
            set_global_mesh(mesh)
        # apply() OWNS the routing toggle, in both directions: the
        # same flag a manual user sets (docs/configuration.md) arms
        # the (dcn, ici) ladder in collective_ops, and a later
        # non-hierarchical plan must disarm it — otherwise a re-plan
        # after e.g. an elastic resize to one slice would leave any
        # 2-tuple axis silently riding the ladder against the current
        # plan's intent.
        if self.sync == "hierarchical":
            os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
        else:
            os.environ.pop("HOROVOD_HIERARCHICAL_ALLREDUCE", None)
        return mesh

    def optimizer(self, inner, **kwargs):
        """Wrap an optax transformation with the planned gradient sync
        (``DistributedOptimizer`` over the plan's gradient axes)."""
        from horovod_tpu.jax import DistributedOptimizer

        axis = self.grad_axes if len(self.grad_axes) > 1 \
            else (self.grad_axes[0] if self.grad_axes else DATA_AXIS)
        return DistributedOptimizer(inner, axis=axis, **kwargs)

    def shard_map(self, fn, *, in_specs, out_specs, mesh=None,
                  check_vma: bool = False):
        """FULL-manual ``shard_map`` of ``fn`` over the planned mesh.

        Every mesh axis is manual (no ``axis_names`` subset): the
        plan's specs name every axis, so ``fn`` sees local shards only
        and issues the collectives itself.
        """
        mesh = mesh if mesh is not None else make_mesh(self.mesh_axes)
        return shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=check_vma)

    # -- specs ------------------------------------------------------------

    def batch_spec(self, ndim: int = 2, seq_dim: Optional[int] = 1):
        """PartitionSpec for a batch-leading input: data axes on dim 0,
        the ``seq`` axis on ``seq_dim`` when the plan has one."""
        from jax.sharding import PartitionSpec as P

        entries: List[object] = [None] * ndim
        if self.data_axes:
            entries[0] = self.data_axes if len(self.data_axes) > 1 \
                else self.data_axes[0]
        if seq_dim is not None and ndim > seq_dim \
                and self.mesh_axes.get(SEQ_AXIS, 1) > 1:
            entries[seq_dim] = SEQ_AXIS
        return P(*entries)

    def leaf_spec(self, shape: Sequence[int]):
        """Deterministic per-leaf PartitionSpec.

        Rules (documented in docs/planner.md, in precedence order):
        leaves with a leading expert dim shard dim 0 over ``expert``;
        with model parallelism, the LAST dim divisible by the model
        size is sharded over ``model`` (column-parallel by default,
        matching the flax ``with_partitioning`` idiom in
        models/transformer.py); everything else is replicated — data
        axes never appear on parameters (data parallelism replicates
        them).
        """
        from jax.sharding import PartitionSpec as P

        shape = tuple(int(x) for x in shape)
        entries: List[object] = [None] * len(shape)
        e = self.mesh_axes.get(EXPERT_AXIS, 1)
        m = self.mesh_axes.get(MODEL_AXIS, 1)
        if e > 1 and shape and shape[0] == self.workload.num_experts:
            entries[0] = EXPERT_AXIS
        if m > 1:
            for i in range(len(shape) - 1, -1, -1):
                if entries[i] is None and shape[i] % m == 0 \
                        and shape[i] >= m:
                    entries[i] = MODEL_AXIS
                    break
        while entries and entries[-1] is None:  # canonical: P() not
            entries.pop()                       # P(None, ...)
        return P(*entries)

    def leaf_specs(self, tree):
        """Map :meth:`leaf_spec` over a pytree of arrays/ShapeDtypes."""
        import jax

        return jax.tree_util.tree_map(
            lambda leaf: self.leaf_spec(getattr(leaf, "shape", ())), tree)

    # -- reporting (pure Python over the cost table; never traces) --------

    def summary(self) -> str:
        """One-line plan record for logs and the MULTICHIP dryrun tail."""
        top = next((c for c in self.rejected), None)
        rej = " top-rejected=%s (%s)" % (
            costmodel._compact(top.axes), top.reason) if top else ""
        return ("mesh=%r sync=%s step_comm=%.3f ms mem/chip=%.2f GB%s"
                % (self.mesh_axes, self.sync,
                   self.chosen.cost.seconds * 1e3,
                   self.chosen.cost.mem_bytes / 1e9, rej))

    def report(self) -> str:
        """Human-readable debug report: chosen mesh, per-axis
        rationale, and the scored cost table of rejected candidates."""
        w, t = self.workload, self.topology
        lines = [
            "hvd.plan report",
            "  workload: params=%.2f MB (expert %.2f MB) batch=%d "
            "seq=%d d_model=%d layers=%d experts=%d pipe_stages=%d"
            % (w.param_bytes / 1e6, w.expert_param_bytes / 1e6, w.batch,
               w.seq_len, w.d_model, w.n_layers, w.num_experts,
               w.pipeline_stages),
            "  topology: %d chips = %d ici x %d dcn | ici %.1f GB/s, "
            "dcn %.1f GB/s, %.1f GB/chip"
            % (t.chips, t.ici, t.dcn, t.ici_bw_gbps, t.dcn_bw_gbps,
               t.mem_per_chip_gb),
            "  chosen: %s" % self.summary(),
            "  per-axis rationale:",
        ]
        if self.chosen.cost.terms:
            for text, _ in self.chosen.cost.terms:
                lines.append("    - %s" % text)
        else:
            lines.append("    - no inter-chip communication needed "
                         "(single chip or no parallel axis > 1)")
        lines.append("  candidates (ranked; %d total):"
                     % (1 + len(self.rejected)))
        lines.append("    %-28s %12s %10s %10s %9s  %s"
                     % ("mesh", "step-comm", "ici MB", "dcn MB",
                        "mem GB", "verdict"))
        table = [(self.chosen, "CHOSEN")] + \
            [(c, "rejected: " + c.reason) for c in self.rejected]
        for cand, verdict in table:
            c = cand.cost
            lines.append(
                "    %-28s %9.3f ms %10.2f %10.2f %9.2f  %s"
                % (costmodel._compact(cand.axes), c.seconds * 1e3,
                   c.ici_bytes / 1e6, c.dcn_bytes / 1e6,
                   c.mem_bytes / 1e9, verdict))
        return "\n".join(lines)

    def to_json(self) -> Dict:
        """JSON-serializable plan record (journals, SCALING.json)."""
        return {
            "mesh_axes": dict(self.mesh_axes),
            "data_axes": list(self.data_axes),
            "grad_axes": list(self.grad_axes),
            "sync": self.sync,
            "step_comm_ms": round(self.chosen.cost.seconds * 1e3, 6),
            "mem_per_chip_gb": round(self.chosen.cost.mem_bytes / 1e9, 4),
            "chips": self.topology.chips,
            "ici": self.topology.ici,
            "dcn": self.topology.dcn,
            "rejected": [
                {"axes": {k: v for k, v in c.axes.items() if v > 1},
                 "reason": c.reason} for c in self.rejected[:4]],
        }

    def __repr__(self) -> str:
        return "Plan(%s)" % self.summary()


def plan(params=None, *, batch: Optional[int] = None, seq_len: int = 1,
         d_model: Optional[int] = None, n_layers: int = 1,
         num_experts: int = 0, pipeline_stages: int = 0,
         param_bytes: Optional[int] = None,
         expert_param_bytes: int = 0,
         dtype_bytes: Optional[int] = None,
         workload: Optional[Workload] = None,
         topology: Optional[Topology] = None,
         chips: Optional[int] = None, dcn: int = 1,
         require_axes: Optional[Dict[str, int]] = None,
         live_layers: int = 0) -> Plan:
    """Choose a composed parallel layout for a workload on a topology.

    Workload: pass a ``params`` pytree (real arrays or
    ``jax.eval_shape`` output), or ``param_bytes`` plus the shape
    dims, or a prebuilt :class:`Workload`. ``n_layers`` is the count
    of block APPLICATIONS a step, which prices the per-layer
    collectives and the activations: a looped model (ONE stack of L
    blocks applied T times with the same weights,
    ``TransformerConfig.passes``) passes ``L * T`` and plans like an
    untied model of that many blocks; ``live_layers`` says how many of
    them hold their activations at once (0 = all; a looped model that
    recomputes by pass holds one pass's, L). Topology: a
    :class:`Topology`, or ``chips=`` (+ ``dcn=`` for multi-slice);
    with neither, every visible jax device is used. ``require_axes``
    pins axes to exact sizes while the cost model assigns the rest.

    Returns a :class:`Plan`; raises :class:`PlanError` when no legal
    feasible layout exists.
    """
    with LAUNCH_LOG.span("plan") as span:
        if workload is None:
            if batch is None:
                raise ValueError("plan() needs batch= (or a prebuilt "
                                 "workload=)")
            if params is not None:
                workload = workload_from_params(
                    params, batch=batch, seq_len=seq_len, d_model=d_model,
                    n_layers=n_layers, num_experts=num_experts,
                    pipeline_stages=pipeline_stages,
                    dtype_bytes=dtype_bytes, live_layers=live_layers)
            else:
                workload = Workload(
                    param_bytes=int(param_bytes or 0), batch=batch,
                    seq_len=seq_len, d_model=d_model or 1,
                    n_layers=n_layers, num_experts=num_experts,
                    expert_param_bytes=int(expert_param_bytes),
                    dtype_bytes=int(dtype_bytes) if dtype_bytes else 4,
                    pipeline_stages=pipeline_stages,
                    live_layers=live_layers)
        if topology is None:
            if chips is None:
                import jax

                chips = jax.device_count()
            topology = Topology.make(chips, dcn=dcn)

        candidates = costmodel.enumerate_candidates(
            workload, topology, require_axes)
        chosen, rejected = costmodel.choose(candidates)
        made = _plan_from_candidate(chosen, rejected, workload, topology)
        span.update(chips=topology.chips, axes=dict(made.mesh_axes))
    return made


def _plan_from_candidate(chosen: Candidate, rejected: List[Candidate],
                         workload: Workload, topology: Topology) -> Plan:
    axes = chosen.axes
    d = axes[costmodel.DATA]
    s = axes[costmodel.SEQ]
    mesh_axes: Dict[str, int] = {}
    if topology.dcn > 1 and d > 1:
        # DCN outer, ICI inner — make_hierarchical_axes ordering, so
        # ici neighbors stay physically adjacent.
        mesh_axes[DCN_AXIS] = topology.dcn
        mesh_axes[ICI_AXIS] = d // topology.dcn
        data_axes: Tuple[str, ...] = (DCN_AXIS, ICI_AXIS)
    else:
        mesh_axes[DATA_AXIS] = d
        data_axes = (DATA_AXIS,)
    for name, logical in ((EXPERT_AXIS, costmodel.EXPERT),
                          (SEQ_AXIS, costmodel.SEQ),
                          (MODEL_AXIS, costmodel.MODEL),
                          (PIPE_AXIS, costmodel.PIPE)):
        if axes[logical] > 1:
            mesh_axes[name] = axes[logical]
    assert math.prod(mesh_axes.values()) == topology.chips
    # Gradients sum over every token-sharding axis: data (or its
    # dcn x ici pair) plus seq. The hierarchical ladder handles
    # exactly a (dcn, ici) pair, so a seq axis alongside a multi-slice
    # data axis falls back to the flat multi-axis psum — and the cost
    # model scores that case with the FLAT cross-slice formula
    # (costmodel.score mirrors this condition), so the ranking matches
    # what actually executes.
    grad_axes = data_axes + ((SEQ_AXIS,) if s > 1 else ())
    if d * s <= 1:
        sync = "none"
    elif topology.dcn > 1 and d > 1 and s == 1:
        sync = "hierarchical"
    else:
        sync = "psum"
    return Plan(
        mesh_axes=mesh_axes, data_axes=data_axes, grad_axes=grad_axes,
        sync=sync, workload=workload, topology=topology, chosen=chosen,
        rejected=rejected)
