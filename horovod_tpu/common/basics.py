"""Process topology and lifecycle for horovod_tpu.

This is the TPU-native analog of the reference's ``horovod/common/basics.py``
(ctypes wrapper over the C core, reference: horovod/common/basics.py:29-487).
Here the Python side owns topology bookkeeping; the native core
(``horovod_tpu.core``) is attached when world size > 1 to run the
coordinator/worker negotiation protocol and the CPU control-plane
collectives. The TPU data plane is XLA collectives over a
``jax.sharding.Mesh`` — see ``horovod_tpu.ops``.

Environment contract (set by the ``hvdrun`` launcher, mirroring the
reference's Gloo env contract, reference: horovod/runner/gloo_run.py:65-76):

- ``HOROVOD_RANK`` / ``HOROVOD_SIZE``: global rank / world size.
- ``HOROVOD_LOCAL_RANK`` / ``HOROVOD_LOCAL_SIZE``: rank / size on this host.
- ``HOROVOD_CROSS_RANK`` / ``HOROVOD_CROSS_SIZE``: rank / size across hosts
  (index of this host among hosts owning this local_rank).
- ``HOROVOD_RENDEZVOUS_ADDR`` / ``HOROVOD_RENDEZVOUS_PORT``: HTTP KV store
  run by the launcher, used by the native core for bootstrap.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Optional

from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.utils.timeline import LAUNCH_LOG

logger = logging.getLogger("horovod_tpu")


@dataclass
class Topology:
    rank: int = 0
    size: int = 1
    local_rank: int = 0
    local_size: int = 1
    cross_rank: int = 0
    cross_size: int = 1


@dataclass
class _Context:
    """Per-process singleton (analog of HorovodGlobalState,
    reference: horovod/common/global_state.h:39-126)."""

    initialized: bool = False
    # True once this process has EVER formed a multi-rank world; never
    # cleared. is_shared_world() stays conservatively True during the
    # shutdown->reinit window of an elastic reset, so per-rank
    # decisions gated on it (live-unsafe knob applies) cannot sneak
    # through mid-teardown.
    shared_high_water: bool = False
    topology: Topology = field(default_factory=Topology)
    # Native core handle (horovod_tpu.core.CoreSession) when size > 1.
    core: Optional[object] = None
    # Timeline state (horovod_tpu.utils.timeline.Timeline), lazily created.
    timeline: Optional[object] = None
    # /metrics HTTP server (runner.http_server.KVStoreServer), started
    # via start_metrics_server() or the HVD_METRICS_PORT env knob.
    metrics_server: Optional[object] = None
    # Bound port to re-serve after an elastic shutdown/init cycle: a
    # programmatically started server must survive resets the same way
    # the env-knob path does (scrapers keep targeting the same port).
    metrics_restart_port: Optional[int] = None
    lock: threading.RLock = field(default_factory=threading.RLock)


_ctx = _Context()


def _int_env(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return int(v)


def _first_int_env(names, default: int) -> int:
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            # Slurm counts can carry a repeat suffix ("4(x2)"): take the
            # leading integer.
            digits = ""
            for ch in v:
                if ch.isdigit():
                    digits += ch
                else:
                    break
            if digits:
                return int(digits)
    return default


def _topology_from_env() -> Topology:
    """Read the launcher environment. HOROVOD_* takes priority; under a
    bare ``mpirun`` (hvdrun --use-mpi) the standard MPI launcher vars
    (OpenMPI/PMI/Slurm) supply rank/size instead (the reference gets these
    from MPI_Comm_rank after MPI_Init; we read the launcher's env)."""
    # Launcher fallbacks are accepted only as rank+size *pairs* from the
    # same launcher: a plain `python train.py` inside an sbatch/salloc
    # allocation has SLURM_NTASKS but no per-task step vars, and must
    # stay a size-1 run rather than hang waiting for phantom peers —
    # and conversely a rank var must never be honored without its size
    # counterpart (rank 3 of size 1 silently trains standalone).
    size_vars, rank_vars = ["HOROVOD_SIZE"], ["HOROVOD_RANK"]
    lsize_vars, lrank_vars = ["HOROVOD_LOCAL_SIZE"], ["HOROVOD_LOCAL_RANK"]
    if ("OMPI_COMM_WORLD_RANK" in os.environ
            and "OMPI_COMM_WORLD_SIZE" in os.environ):
        size_vars.append("OMPI_COMM_WORLD_SIZE")
        rank_vars.append("OMPI_COMM_WORLD_RANK")
        lsize_vars.append("OMPI_COMM_WORLD_LOCAL_SIZE")
        lrank_vars.append("OMPI_COMM_WORLD_LOCAL_RANK")
    if "PMI_RANK" in os.environ and "PMI_SIZE" in os.environ:
        size_vars.append("PMI_SIZE")
        rank_vars.append("PMI_RANK")
        lsize_vars.append("MPI_LOCALNRANKS")
        lrank_vars.append("MPI_LOCALRANKID")
    if ("SLURM_PROCID" in os.environ
            and "SLURM_STEP_NUM_TASKS" in os.environ):
        size_vars.append("SLURM_STEP_NUM_TASKS")
        rank_vars.append("SLURM_PROCID")
        lsize_vars.append("SLURM_STEP_TASKS_PER_NODE")
        lrank_vars.append("SLURM_LOCALID")
    size = _first_int_env(size_vars, 1)
    rank = _first_int_env(rank_vars, 0)
    local_rank = _first_int_env(lrank_vars, 0)
    local_size = _first_int_env(lsize_vars, 1 if size == 1 else size)
    # Derive the cross (inter-node) coordinates when the launcher didn't
    # provide them: with homogeneous nodes rank = cross_rank*local_size +
    # local_rank.
    if ("HOROVOD_CROSS_RANK" in os.environ
            or "HOROVOD_CROSS_SIZE" in os.environ):
        cross_rank = _int_env("HOROVOD_CROSS_RANK", 0)
        cross_size = _int_env("HOROVOD_CROSS_SIZE", 1)
    elif local_size > 0 and size % local_size == 0:
        cross_rank = rank // local_size
        cross_size = size // local_size
    else:
        cross_rank, cross_size = 0, 1
    return Topology(
        rank=rank, size=size, local_rank=local_rank,
        local_size=local_size, cross_rank=cross_rank,
        cross_size=cross_size,
    )


def init(process_sets=None):
    """Initialize horovod_tpu.

    Reads the launcher environment, and when world size > 1 starts the
    native coordination core (background cycle thread + TCP control plane;
    analog of InitializeHorovodOnce, reference:
    horovod/common/operations.cc:791-843).

    Args:
        process_sets: optional list of ``ProcessSet`` objects to register at
            init time (analog of the reference's ``process_sets`` argument).
    """
    from horovod_tpu.utils.compile_cache import install_compile_listeners

    with _ctx.lock:
        if _ctx.initialized:
            return
        # The launch is recorded always (docs/timeline.md#launch):
        # this init and its parts as spans, and from here on every
        # program jax compiles or reads from its cache.
        LAUNCH_LOG.begin_launch()
        with LAUNCH_LOG.span("init"):
            install_compile_listeners()
            # analysis: blocking-ok(once-per-process bootstrap:
            # init() must be atomic under _ctx.lock — a second
            # thread calling init()/shutdown() mid-negotiation has
            # to wait for a fully built core either way, and the
            # rendezvous poll IS the init work)
            _start_world(process_sets)


def _start_world(process_sets):
    """``init()``'s part under ``_ctx.lock``."""
    # Env-knob registry: translate reference-named aliases
    # (HOROVOD_GLOO_*) and warn about set-but-meaningless knobs
    # (reference knob surface: horovod/common/common.h:107-139).
    from horovod_tpu.common import knobs

    knobs.apply_aliases()
    knobs.warn_rejected()
    # Unnamed-collective sequence numbers are per-world: reset so
    # elastic-reset survivors and fresh respawns start aligned.
    from horovod_tpu.ops import eager

    eager._reset_name_counters()
    _ctx.topology = _topology_from_env()
    if _ctx.topology.size > 1:
        from horovod_tpu.core import CoreSession

        # Elastic runs publish controller_port 0 (= negotiated):
        # the launcher's free_port() probes the wrong host — only
        # the rank-0 WORKER host knows what it can bind. Rank 0
        # picks a port there and reports it through the rendezvous
        # KV; everyone else polls it before dialing
        # (elastic/worker.negotiate_controller_port).
        if (os.environ.get("HOROVOD_CONTROLLER_PORT", "0") in ("", "0")
                and os.environ.get("HOROVOD_ELASTIC")
                and os.environ.get("HOROVOD_RENDEZVOUS_ADDR")):
            from horovod_tpu.elastic.worker import (
                negotiate_controller_port,
            )

            # Blocks under init()'s lock, by design (see its call).
            with LAUNCH_LOG.span("init/negotiate_port"):
                negotiate_controller_port(_ctx.topology.rank)
        with LAUNCH_LOG.span("init/core_start", size=_ctx.topology.size):
            _ctx.core = CoreSession.start(_ctx.topology)
    if _ctx.topology.size > 1:
        _ctx.shared_high_water = True
    _ctx.initialized = True
    timeline_path = os.environ.get("HOROVOD_TIMELINE")
    if timeline_path:
        # "{rank}" placeholder gives per-rank files on shared storage.
        timeline_path = timeline_path.replace(
            "{rank}", str(_ctx.topology.rank))
        mark = os.environ.get(
            "HOROVOD_TIMELINE_MARK_CYCLES", "") not in ("", "0")
        from horovod_tpu.utils.timeline import Timeline

        _set_timeline(Timeline(timeline_path, mark_cycles=mark))
        # The env-initiated timeline starts BOTH writers, exactly
        # like hvd.start_timeline (the native one carries the
        # per-tensor phase lanes and cycle marks).
        if _ctx.core is not None:
            _ctx.core.attach_timeline(_ctx.timeline)
            _ctx.core.start_core_timeline(
                timeline_path + ".core.json", mark_cycles=mark)
    if process_sets:
        from horovod_tpu.common import process_sets as ps_mod

        for ps in process_sets:
            ps_mod.add_process_set(ps)
    # Stall/health reporter: keeps hvd_seconds_since_last_collective
    # and the core's pending/stalled gauges fresh between scrapes
    # (docs/metrics.md). Registry and counters deliberately survive
    # shutdown/init cycles (elastic resets are themselves counted).
    from horovod_tpu.utils import metrics as metrics_mod

    metrics_mod.start_health_reporter()
    # Flight recorder (docs/flightrec.md): dump-on-SIGTERM so a
    # wedge-cull's SIGTERM->SIGKILL grace window leaves evidence
    # behind. Best-effort: init off the main thread (or
    # HVD_FLIGHTREC_SIGNAL=0 / HVD_FLIGHTREC=0) just skips it.
    from horovod_tpu.utils import flightrec as flightrec_mod

    flightrec_mod.install_signal_handler()
    port_env = os.environ.get("HVD_METRICS_PORT")
    if port_env not in (None, ""):
        with LAUNCH_LOG.span("init/metrics_server"):
            _try_start_metrics_server(
                port_env, "HVD_METRICS_PORT=%s" % port_env,
                offset_local_rank=True)
        _ctx.metrics_restart_port = None
    elif _ctx.metrics_restart_port is not None:
        # A server the user started programmatically before an
        # elastic reset: rebind the same (already rank-offset)
        # port so scrapers keep working across the new world. A
        # transient bind failure keeps the port remembered so the
        # NEXT reset retries instead of going dark for good.
        with LAUNCH_LOG.span("init/metrics_server"):
            if _try_start_metrics_server(
                    _ctx.metrics_restart_port,
                    "metrics server restart after reset") is not None:
                _ctx.metrics_restart_port = None
    atexit.register(shutdown)


def shutdown():
    """Shut down background machinery (idempotent)."""
    with _ctx.lock:
        if not _ctx.initialized:
            return
        if _ctx.core is not None:
            try:
                # Barrier first so no rank tears the TCP mesh down while a
                # peer is still mid-cycle (avoids spurious "broken pipe"
                # coordination errors on clean exits).
                from horovod_tpu.common.process_sets import (
                    global_process_set,
                )
                from horovod_tpu.ops import eager

                try:
                    # Backend call, not eager.barrier(): this barrier's
                    # failure is EXPECTED on staggered clean exits and
                    # must not count into hvd_collective_errors_total.
                    eager._backend().barrier(global_process_set)
                except Exception:  # analysis: allow-broad-except
                    pass  # peers may already be gone; close anyway
                _ctx.core.shutdown()
            finally:
                _ctx.core = None
        _set_timeline(None)
        # Preserve the bound port across the stop so an elastic
        # shutdown/init cycle re-serves on it (stop_metrics_server
        # clears it — an explicit user stop means stay stopped).
        restart_port = (_ctx.metrics_server.port
                        if _ctx.metrics_server is not None else None)
        stop_metrics_server()
        _ctx.metrics_restart_port = restart_port
        from horovod_tpu.utils import metrics as metrics_mod

        metrics_mod.stop_health_reporter()
        _ctx.initialized = False


def is_initialized() -> bool:
    return _ctx.initialized


def is_shared_world() -> bool:
    """True when this process is one rank of an initialized
    multi-rank world — the condition under which per-rank decisions
    that feed traced programs or collective sequences become SPMD
    hazards (docs/static_analysis.md#spmd). The online knob tuner reads
    it, at decision time rather than cached: elastic worlds grow and
    shrink across a process lifetime. During the shutdown->reinit window of
    an elastic reset (not initialized, but the process HAS been part
    of a multi-rank world) this answers conservatively True, so a
    concurrent thread cannot slip a per-rank mutation through
    mid-teardown. An initialized size-1 world after an elastic shrink
    answers False — the process really is alone."""
    if is_initialized():
        return size() > 1
    return _ctx.shared_high_water


def _check_initialized():
    if not _ctx.initialized:
        raise HorovodInternalError(
            "horovod_tpu has not been initialized; call horovod_tpu.init()."
        )


def rank() -> int:
    _check_initialized()
    return _ctx.topology.rank


def size() -> int:
    _check_initialized()
    return _ctx.topology.size


def local_rank() -> int:
    _check_initialized()
    return _ctx.topology.local_rank


def local_size() -> int:
    _check_initialized()
    return _ctx.topology.local_size


def cross_rank() -> int:
    _check_initialized()
    return _ctx.topology.cross_rank


def cross_size() -> int:
    _check_initialized()
    return _ctx.topology.cross_size


def is_homogeneous() -> bool:
    """True when every host runs the same number of processes."""
    _check_initialized()
    t = _ctx.topology
    return t.size == t.local_size * t.cross_size


# --- build/capability queries (reference: horovod/common/basics.py:250-330) ---

def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    # The native TCP control plane fills the role Gloo plays in the reference.
    return _ctx.core is not None


def gloo_built() -> bool:
    from horovod_tpu.core import core_built

    return core_built()


def check_extension(ext_base_name: str = "horovod_tpu",
                    *compat_args) -> None:
    """Fail fast when the native core cannot be used (reference:
    horovod/common/util.py check_extension, which raises ImportError
    when the framework extension was not compiled in; its extra
    ``ext_env_var``/``pkg_path`` arguments are accepted and ignored so
    reference call sites work verbatim). The core here builds lazily,
    so the check triggers that build: a fresh checkout with a working
    toolchain passes (compiling if needed); only a genuinely
    unbuildable core raises."""
    del compat_args
    try:
        from horovod_tpu.core.build import library_path

        library_path(build_if_missing=True)
    except Exception as e:  # compiler/source failure surfaces as the error
        raise ImportError(
            "%s native core unavailable (build failed: %s); "
            "multi-process collectives cannot run" % (ext_base_name, e)
        ) from e


def nccl_built() -> int:
    return 0


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def tpu_built() -> bool:
    """True when JAX reports at least one TPU device (or any XLA backend —
    the data plane is XLA collectives regardless of platform)."""
    return True


def core_session():
    """The native CoreSession, or None in single-process mode."""
    return _ctx.core


def _timeline():
    return _ctx.timeline


def _set_timeline(timeline):
    """Swap the process's ``Timeline`` under ``_ctx.lock`` (None: only
    close the one open). The launch's span log writes to whichever is
    open (utils/timeline.py ``SpanLog.attach``)."""
    old, _ctx.timeline = _ctx.timeline, timeline
    LAUNCH_LOG.attach(timeline)
    if old is not None:
        old.close()


def metrics_snapshot():
    """JSON-able snapshot of the process-wide metrics registry: native
    core counters (negotiation responses, cache hits, fusion), eager
    per-collective latency/bytes histograms, elastic reset/commit
    counters, data-pipeline throughput, and the stall/health gauges
    (``hvd_stalled_tensors``, ``hvd_seconds_since_last_collective``).
    Collectors (e.g. the native-counter bridge) run first, so the view
    is fresh. See docs/metrics.md for the catalog.

    The snapshot also carries ``hvd_recent_failures`` — an info-style
    entry (not a registry family) listing the last N abort/wedge
    reasons this process recorded (docs/flightrec.md), so "why did it
    degrade" is answerable from the same call dashboards already make.
    """
    from horovod_tpu.utils import flightrec, metrics

    snap = metrics.snapshot()
    snap["hvd_recent_failures"] = {
        "type": "info",
        "help": "Last abort/wedge/cull reasons recorded by the flight "
                "recorder (newest last; docs/flightrec.md).",
        "values": flightrec.recent_failures(),
    }
    return snap


def dump_flight_record(directory: Optional[str] = None) -> dict:
    """Dump both flight-recorder rings (Python planes + native core)
    as JSONL files into ``directory`` (default ``HVD_FLIGHTREC_DIR``
    or the cwd); returns ``{"python": path, "native": path}`` for the
    files written. Merge and diagnose per-rank dumps with
    ``python -m tools.trace <dir>`` (docs/flightrec.md). Callable at
    any time — the ring is always on — and automatically triggered on
    ``HorovodAbortedError`` and (when enabled) SIGTERM."""
    from horovod_tpu.utils import flightrec

    return flightrec.dump(directory, reason="hvd.dump_flight_record")


def start_metrics_server(port: int = 0) -> int:
    """Serve ``GET /metrics`` (Prometheus text format 0.0.4) and
    ``GET /metrics.json`` from this process; returns the bound port
    (``port=0`` picks an ephemeral one). Idempotent: a second call
    returns the already-running server's port. Set ``HVD_METRICS_PORT``
    to have ``hvd.init()`` do this automatically (each co-located
    worker serves on base + local_rank)."""
    from horovod_tpu.runner.http_server import KVStoreServer

    with _ctx.lock:
        if _ctx.metrics_server is not None:
            return _ctx.metrics_server.port
        # metrics_only: the scrape port must not double as a writable
        # KV store (operators open it to their Prometheus fleet).
        server = KVStoreServer(port=port, metrics_only=True)
        # On-demand flight-record dump of a LIVE job: GET it to write
        # this rank's python+native rings to HVD_FLIGHTREC_DIR and get
        # the paths plus the recent failure log back
        # (docs/flightrec.md). Read-only in KV terms, so it coexists
        # with metrics_only.
        server.register_get_route("/debug/flightrec", _flightrec_route)
        server.start()
        _ctx.metrics_server = server
        return server.port


def _flightrec_route():
    from horovod_tpu.runner.http_server import json_route_result
    from horovod_tpu.utils import flightrec

    dumped = flightrec.dump(reason="/debug/flightrec")
    status = 200 if (dumped or not flightrec.enabled()) else 500
    return json_route_result(status, {
        "enabled": flightrec.enabled(),
        "dumped": dumped,
        "recent_failures": flightrec.recent_failures(),
    })


def stop_metrics_server():
    """Stop the /metrics server started by ``start_metrics_server``
    (idempotent). An explicit stop also cancels any pending
    restart-after-reset (``shutdown()`` preserves it instead, so the
    server comes back with the next ``init()``)."""
    with _ctx.lock:
        server, _ctx.metrics_server = _ctx.metrics_server, None
        _ctx.metrics_restart_port = None
    if server is not None:
        try:
            server.stop()
        except Exception as e:
            # Best-effort: a half-dead server must not fail the caller's
            # teardown, but the reason is worth a breadcrumb.
            logger.debug("metrics server stop failed: %s", e)


def _try_start_metrics_server(base_port, source: str,
                              offset_local_rank: bool = False):
    """Best-effort server start shared by the ``HVD_METRICS_PORT`` init
    path, the restart-after-reset path, and ``MetricsCallback(port=)``:
    an observability knob must never take training down, so a malformed
    value or unbindable port logs a warning and continues. With
    ``offset_local_rank``, co-located workers serve on base +
    local_rank so one host's workers never collide (base 0 picks an
    ephemeral port). Returns the bound port or None."""
    try:
        port = int(base_port)
        if port != 0 and offset_local_rank and _ctx.initialized:
            port += _ctx.topology.local_rank
        return start_metrics_server(port)
    except (ValueError, OverflowError, OSError) as e:
        logger.warning(
            "%s: could not start the metrics server (%s); "
            "continuing without one", source, e)
        return None


def start_timeline(file_path: str, mark_cycles: bool = False):
    """Begin writing a Chrome-tracing timeline (analog of
    horovod_start_timeline, reference: horovod/common/operations.cc:1011-1041)."""
    _check_initialized()
    from horovod_tpu.utils.timeline import Timeline

    with _ctx.lock:
        _set_timeline(Timeline(file_path, mark_cycles=mark_cycles))
        if _ctx.core is not None:
            _ctx.core.attach_timeline(_ctx.timeline)
            # The native loop writes its own spans (negotiation, fused op
            # execution) beside the op-level Python timeline. Stop any
            # previous core writer first so a restart switches files.
            _ctx.core.stop_core_timeline()
            _ctx.core.start_core_timeline(file_path + ".core.json",
                                          mark_cycles=mark_cycles)


def stop_timeline():
    _check_initialized()
    with _ctx.lock:
        _set_timeline(None)
        if _ctx.core is not None:
            _ctx.core.attach_timeline(None)
            _ctx.core.stop_core_timeline()
