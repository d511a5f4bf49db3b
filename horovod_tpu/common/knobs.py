"""HOROVOD_* environment-knob registry.

The reference exposes ~40 ``HOROVOD_*`` environment variables
(reference: horovod/common/common.h:107-139 name constants,
horovod/common/utils/env_parser.cc parsing, horovod/common/operations.cc
:432-588 consumption at init). This registry accounts for every one of
them: each knob is either HONORED (consumed by this framework, with the
consuming module recorded), ALIASED (accepted under the reference name
and mapped onto this framework's equivalent), or REJECTED (meaningless
on TPU — the hardware/runtime it configures does not exist here — with
the reason recorded).

``apply_aliases()`` translates aliased names into their native
equivalents and ``warn_rejected()`` logs any rejected knob the user has
set, so a reference user migrating an environment gets an explicit
signal instead of a silently ignored variable. Both run during
``hvd.init()`` (common/basics.py).

The registry also carries this framework's native knobs (HVD_* and the
HOROVOD_* names with no reference analog). Completeness is machine-
checked: the env-knob contract checker (``python -m tools.analysis``,
docs/static_analysis.md) fails CI when any ``getenv``/``os.environ``
read of a HOROVOD_*/HVD_* name is neither registered here nor
explicitly allowlisted, or is missing from docs/configuration.md.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, NamedTuple, Optional

logger = logging.getLogger("horovod_tpu")

HONORED = "honored"
ALIASED = "aliased"
REJECTED = "rejected"


class Knob(NamedTuple):
    name: str
    status: str
    # HONORED: module that consumes it. ALIASED: the native name it maps
    # to. REJECTED: why it has no TPU meaning.
    detail: str


# Every knob named in reference common.h:107-139 plus the env_parser.cc
# extras, in reference order.
REGISTRY: Dict[str, Knob] = {k.name: k for k in [
    # --- logging / observability ---
    Knob("HOROVOD_LOG_LEVEL", HONORED,
         "core/src/common.cc CurrentLogLevel + python logging"),
    Knob("HOROVOD_LOG_TIMESTAMP", HONORED,
         "core/src/common.cc LogMessage timestamp prefix"),
    Knob("HOROVOD_LOG_HIDE_TIME", ALIASED,
         "HOROVOD_LOG_TIMESTAMP=0"),
    Knob("HOROVOD_TIMELINE", HONORED,
         "common/basics.py -> utils/timeline.py + native TimelineWriter"),
    Knob("HOROVOD_TIMELINE_MARK_CYCLES", HONORED,
         "native loop CYCLE_START marks on the trace's loop row "
         "(core/src/operations.cc; also via start_timeline's "
         "mark_cycles argument)"),
    Knob("HOROVOD_DISABLE_NVTX_RANGES", REJECTED,
         "NVTX is a CUDA profiler annotation library; TPU profiling "
         "goes through the timeline + XLA/jax.profiler instead"),
    # --- core coordination loop ---
    Knob("HOROVOD_FUSION_THRESHOLD", HONORED,
         "core/session.py + core/src/operations.cc (default 128 MB, "
         "reference operations.cc:488)"),
    Knob("HOROVOD_CYCLE_TIME", HONORED,
         "core/session.py + background loop cadence"),
    Knob("HOROVOD_CACHE_CAPACITY", HONORED,
         "core/src/controller.cc response cache"),
    Knob("HOROVOD_HIERARCHICAL_ALLREDUCE", HONORED,
         "core/src/controller.cc + parallel/hierarchical.py"),
    Knob("HOROVOD_HIERARCHICAL_ALLGATHER", HONORED,
         "parallel/hierarchical.py hierarchical_all_gather default"),
    Knob("HOROVOD_STALL_CHECK_DISABLE", HONORED,
         "core/src/controller.cc StallInspector"),
    Knob("HOROVOD_STALL_CHECK_TIME_SECONDS", HONORED,
         "core/src/controller.cc StallInspector warn threshold"),
    Knob("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", HONORED,
         "core/src/controller.cc StallInspector enforcement"),
    Knob("HOROVOD_ELASTIC", HONORED,
         "runner/elastic_run.py + elastic/worker.py"),
    Knob("HOROVOD_ELASTIC_TIMEOUT", HONORED,
         "runner/elastic_run.py re-scaling rendezvous budget "
         "(reference elastic/driver.py:81, default 600s)"),
    Knob("HOROVOD_COMM_TIMEOUT_SEC", HONORED,
         "core/src/comm.cc progress deadline on every blocking socket "
         "op (default 300; 0 = legacy infinite wait)"),
    Knob("HOROVOD_ELASTIC_MAX_FAILURES", HONORED,
         "elastic/worker.py capped-restart failure budget "
         "(consecutive HorovodInternalError recoveries; 0 = unlimited)"),
    Knob("HOROVOD_ELASTIC_BACKOFF_BASE", HONORED,
         "elastic worker+driver exponential backoff base seconds "
         "between consecutive failure resets (default 1.0)"),
    Knob("HOROVOD_ELASTIC_BACKOFF_MAX", HONORED,
         "elastic worker+driver backoff ceiling seconds (default 30)"),
    Knob("HOROVOD_ELASTIC_STABLE_SEC", HONORED,
         "elastic/worker.py: a world surviving this long resets the "
         "consecutive-failure budget (default 60); the driver also "
         "decays per-slot fail counts after this quiet stretch"),
    Knob("HOROVOD_ELASTIC_JOURNAL_DIR", HONORED,
         "runner/elastic_run.py: fsync'd JSONL journal of membership "
         "transitions (also hvdrun --journal-dir); a restarted driver "
         "replays it and resumes at rendezvous version N+1"),
    Knob("HOROVOD_WORKER_LIVENESS_SEC", HONORED,
         "runner/elastic_run.py: replace a worker slot whose "
         "heartbeats stop for this many seconds "
         "(SIGTERM->SIGKILL->reset); 0 = disabled. Also "
         "serve/router.py: cull a serving replica silent this long "
         "(serving default 30, re-admitted on rediscovery)"),
    Knob("HVD_HEARTBEAT_SEC", HONORED,
         "elastic/worker.py + serve/replica.py: liveness heartbeat "
         "PUT interval to the rendezvous/router KV (default 10; <=0 "
         "disables). Each sender starts at a random phase inside one "
         "interval so a reset's worth of workers never beats in "
         "lockstep (docs/fleet.md)"),
    Knob("HVD_KV_MAX_INFLIGHT", HONORED,
         "runner/http_server.py: max concurrent handler threads on "
         "the KV/HTTP servers; excess connections are shed with a "
         "typed 503 + Retry-After instead of spawning a thread storm "
         "(default 64 on the driver's rendezvous KV, 0 = unbounded "
         "on generic KV servers; docs/fleet.md)"),
    Knob("HVD_KV_RETRY_AFTER_SEC", HONORED,
         "runner/http_server.py: the Retry-After deferral a bounded "
         "KV server attaches to shed 503s — heartbeat clients sleep "
         "this long (plus jitter) before retrying (default 1.0)"),
    Knob("HVD_JOURNAL_SNAPSHOT_EVERY", HONORED,
         "runner/elastic_run.py + serve/router.py: fold the "
         "membership journal down to one snapshot record once the "
         "tail since the last snapshot exceeds this many records — "
         "bounded replay under churn (default 512; 0 disables "
         "compaction; docs/fleet.md)"),
    Knob("HOROVOD_DISABLE_GROUP_FUSION", HONORED,
         "core/src/controller.cc FuseResponses"),
    Knob("HOROVOD_DYNAMIC_PROCESS_SETS", HONORED,
         "common/process_sets.py (default ON here: dynamic sets have no "
         "extra cost without MPI communicator splitting)"),
    Knob("HOROVOD_THREAD_AFFINITY", HONORED,
         "core/src/operations.cc background-thread CPU pin"),
    # --- autotuner ---
    Knob("HOROVOD_AUTOTUNE", HONORED,
         "core/session.py (python manager) / =native (C++ manager)"),
    Knob("HOROVOD_AUTOTUNE_LOG", HONORED, "autotune CSV log path"),
    Knob("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", HONORED,
         "core/src/perf.cc sampling constants"),
    Knob("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", HONORED,
         "core/src/perf.cc sampling constants"),
    Knob("HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", HONORED,
         "core/src/perf.cc sampling constants"),
    Knob("HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", HONORED,
         "core/src/perf.cc GP noise"),
    # --- backend selection (reference compile/runtime backend matrix) ---
    Knob("HOROVOD_CONTROLLER", REJECTED,
         "the reference chooses MPI vs Gloo for the control plane; this "
         "framework has exactly one control plane (native TCP full mesh "
         "+ HTTP rendezvous), so there is nothing to select"),
    Knob("HOROVOD_CPU_OPERATIONS", REJECTED,
         "selects MPI/Gloo/oneCCL for CPU collectives in the reference; "
         "CPU collectives here are always the native TCP ring"),
    Knob("HOROVOD_MPI_THREADS_DISABLE", REJECTED,
         "MPI threading level — no MPI in the runtime"),
    Knob("HOROVOD_NUM_NCCL_STREAMS", REJECTED,
         "NCCL stream pool sizing — no NCCL; device collectives are XLA "
         "programs scheduled by the TPU runtime"),
    Knob("HOROVOD_CCL_CACHE", REJECTED, "oneCCL-specific cache knob"),
    Knob("HOROVOD_CCL_BGT_AFFINITY", REJECTED,
         "oneCCL background-thread affinity; use "
         "HOROVOD_THREAD_AFFINITY"),
    Knob("HOROVOD_DDL_OPTIONS", REJECTED, "IBM DDL backend options"),
    Knob("HOROVOD_ADASUM_MPI_CHUNK_SIZE", REJECTED,
         "chunking for MPI point-to-point Adasum; Adasum here is the "
         "native ring / in-graph reduction (parallel/adasum.py)"),
    Knob("HOROVOD_ENABLE_ASYNC_COMPLETION", REJECTED,
         "GPU event-polling completion mode; completion here is always "
         "asynchronous via the core callback trampoline"),
    Knob("HOROVOD_BATCH_D2D_MEMCOPIES", REJECTED,
         "batched CUDA D2D fusion-buffer copies; XLA fuses device "
         "copies at compile time"),
    Knob("HOROVOD_ENABLE_XLA_OPS", REJECTED,
         "opt-in XLA lowering for the reference's TF ops; collectives "
         "here are always XLA-native"),
    # --- gloo/bootstrap aliases (reference gloo_context.cc:150-230) ---
    Knob("HOROVOD_GLOO_RENDEZVOUS_ADDR", ALIASED,
         "HOROVOD_RENDEZVOUS_ADDR"),
    Knob("HOROVOD_GLOO_RENDEZVOUS_PORT", ALIASED,
         "HOROVOD_RENDEZVOUS_PORT"),
    Knob("HOROVOD_GLOO_IFACE", ALIASED, "HOROVOD_IFACE"),
    Knob("HOROVOD_GLOO_TIMEOUT_SECONDS", ALIASED,
         "HOROVOD_COMM_TIMEOUT_SEC"),
    Knob("HOROVOD_HOSTNAME", HONORED, "core/src/comm.cc advertise addr"),
    Knob("HOROVOD_RANK", HONORED, "common/basics.py topology"),
    Knob("HOROVOD_SIZE", HONORED, "common/basics.py topology"),
    Knob("HOROVOD_LOCAL_RANK", HONORED, "common/basics.py topology"),
    Knob("HOROVOD_LOCAL_SIZE", HONORED, "common/basics.py topology"),
    Knob("HOROVOD_CROSS_RANK", HONORED, "common/basics.py topology"),
    Knob("HOROVOD_CROSS_SIZE", HONORED, "common/basics.py topology"),
    # --- framework-native knobs (no reference analog) -----------------
    # Every entry below is enforced by the env-knob contract checker
    # (tools/analysis/check_knobs.py): a getenv/os.environ read of an
    # unregistered HOROVOD_*/HVD_* name anywhere in the tree fails CI.
    Knob("HOROVOD_CONTROLLER_ADDR", HONORED,
         "core/session.py: rank-0 coordination endpoint every rank "
         "connects to (the hvdrun launcher exports it; manual "
         "multi-process runs must set it)"),
    Knob("HOROVOD_CONTROLLER_PORT", HONORED,
         "core/session.py: coordination endpoint port (required; the "
         "hvdrun launcher picks and exports one)"),
    Knob("HOROVOD_RENDEZVOUS_ADDR", HONORED,
         "elastic/state.py + elastic/worker.py: elastic rendezvous "
         "HTTP endpoint (target of the HOROVOD_GLOO_RENDEZVOUS_ADDR "
         "alias)"),
    Knob("HOROVOD_RENDEZVOUS_PORT", HONORED,
         "elastic rendezvous HTTP port (alias target of "
         "HOROVOD_GLOO_RENDEZVOUS_PORT)"),
    Knob("HOROVOD_IFACE", HONORED,
         "runner/launch.py --nics export; bind-interface selection "
         "(alias target of HOROVOD_GLOO_IFACE)"),
    Knob("HOROVOD_TF_HOST_BRIDGE", HONORED,
         "tensorflow/ingraph.py: opt TF out of in-graph collectives "
         "and route through the host TCP ring"),
    Knob("HVD_METRICS_PORT", HONORED,
         "common/basics.py: serve GET /metrics from every worker at "
         "init (base port + local_rank; docs/metrics.md)"),
    Knob("HVD_METRICS_HEALTH_INTERVAL", HONORED,
         "utils/metrics.py: stall/health gauge refresh seconds "
         "(0 disables the reporter thread)"),
    Knob("HVD_CORE_SANITIZE", HONORED,
         "core/build.py: build/load a sanitizer-instrumented core "
         "(thread|address|undefined; docs/static_analysis.md)"),
    # Wire path (core/src/comm.cc + collectives.cc; docs/wire.md).
    Knob("HVD_RING_CHUNK_BYTES", HONORED,
         "core/src/comm.cc + collectives.cc: pipelined-ring sub-chunk "
         "size — reduce of sub-chunk k overlaps the transfer of k+1 "
         "(default 1 MiB; 0 = serial legacy schedule)"),
    Knob("HOROVOD_SOCKET_BUF_BYTES", HONORED,
         "core/src/comm.cc: explicit SO_SNDBUF/SO_RCVBUF on every data-"
         "plane socket (0/unset = kernel autotuned default)"),
    Knob("HVD_WIRE_SG", HONORED,
         "core/src/operations.cc: =0 restores the fusion-buffer "
         "pack/unpack path for fused allreduces instead of the "
         "scatter-gather ring over tensor memory"),
    Knob("HVD_WIRE_RECONNECT_SEC", HONORED,
         "core/src/comm.cc: in-place reconnect budget for a peer link "
         "that breaks with an RST-shaped error — redial/re-accept + "
         "epoch handshake + retransmit instead of a world teardown "
         "(default 30, clamped to HOROVOD_COMM_TIMEOUT_SEC so the "
         "typed-abort deadline never grows; 0 = legacy "
         "abort-on-break; docs/wire.md#reconnect)"),
    Knob("HVD_WIRE_RETRANSMIT_BUF_BYTES", HONORED,
         "core/src/comm.cc: per-peer retransmit ring over sent stream "
         "bytes — bounds how much in-flight loss a reconnect can "
         "replay; a larger gap falls back to abort-on-break, recorded "
         "(default 8 MiB; 0 disables buffering)"),
    Knob("HVD_WIRE_RETRANSMIT_TOTAL_BYTES", HONORED,
         "core/src/comm.cc: aggregate retransmit budget per rank — "
         "divided across the size-1 peer rings and clamping the "
         "per-peer window down when the division is smaller than "
         "HVD_WIRE_RETRANSMIT_BUF_BYTES (each clamped ring counts in "
         "hvd_wire_retx_rings_clamped_total). Default 512 MiB; 0 = "
         "no aggregate bound (docs/fleet.md)"),
    Knob("HVD_WIRE_CODEC", HONORED,
         "core/src/controller.cc + collectives.cc: wire codec for fp32 "
         "ring allreduce payloads — none | bf16 | fp16 | int8 (scaled, "
         "with error-feedback residuals). Staged through the "
         "coordinator broadcast so every rank flips in the same cycle; "
         "also read by parallel/costmodel.py as the planner's "
         "bytes-per-step discount (docs/wire.md#compression)"),
    # Inference serving (horovod_tpu/serve/; docs/serving.md).
    Knob("HVD_SERVE_MAX_BATCH", HONORED,
         "serve/batching.py: micro-batch size trigger — a batch fires "
         "as soon as this many rows are queued (default 8; also the "
         "largest bucketed batch shape)"),
    Knob("HVD_SERVE_BATCH_DEADLINE_MS", HONORED,
         "serve/batching.py: micro-batch deadline trigger — a batch "
         "fires when the oldest queued request has waited this long, "
         "even if not full (default 5 ms; 0 = no batching delay)"),
    Knob("HVD_SERVE_MIN_BUCKET", HONORED,
         "serve/batching.py: smallest bucketed batch shape; buckets "
         "double from here to HVD_SERVE_MAX_BATCH and bound XLA "
         "recompiles (default 4 — the smallest row-bitexact bucket "
         "for the repo models, see docs/serving.md)"),
    Knob("HVD_SERVE_PORT", HONORED,
         "serve/__main__.py: default router bind port for python -m "
         "horovod_tpu.serve (default 8000; --port overrides)"),
    Knob("HVD_SERVE_CKPT_POLL_SEC", HONORED,
         "serve/replica.py: poll Checkpointer.latest_step() this often "
         "and hot-swap newer committed steps into the live apply path "
         "(default 10; <=0 disables hot reload)"),
    Knob("HVD_SERVE_PROXY_TIMEOUT_SEC", HONORED,
         "serve/router.py + serve/replica.py: per-forward timeout for "
         "router->replica predict proxying and the replica's own "
         "batched-inference wait (default 30)"),
    # Online tuner (utils/online_tuner.py; docs/autotune.md).
    Knob("HVD_TUNE", HONORED,
         "utils/online_tuner.py: 1 = search the tunable-knob schema "
         "online (journal + A/B guardrail); cache = replay the "
         "journaled tuned state only, never search; 0/unset = off"),
    Knob("HVD_TUNE_WINDOW_SEC", HONORED,
         "utils/online_tuner.py: observation-window length in seconds "
         "for each objective measurement (default 30)"),
    Knob("HVD_TUNE_GUARD_PCT", HONORED,
         "utils/online_tuner.py: guardrail floor — a post-apply window "
         "regressing more than max(this %% of baseline, 2x the "
         "baseline sub-window noise) auto-reverts the move "
         "(default 5)"),
    Knob("HVD_TUNE_JOURNAL_DIR", HONORED,
         "utils/online_tuner.py: directory of the fsync'd JSONL "
         "decision journal (runner/journal.py primitives); a restarted "
         "job replays it to its tuned state instead of re-searching"),
    Knob("HVD_TUNE_FREEZE", HONORED,
         "utils/online_tuner.py: comma list of schema knob names "
         "(common/knobs.py TUNABLE) pinned at their current value — "
         "excluded from the search without disabling the tuner"),
    # Flight recorder (core/src/flightrec.cc + utils/flightrec.py;
    # docs/flightrec.md).
    Knob("HVD_FLIGHTREC", HONORED,
         "core/src/flightrec.cc + utils/flightrec.py: always-on event "
         "rings dumped on abort/SIGTERM/demand; 0 disables both"),
    Knob("HVD_FLIGHTREC_EVENTS", HONORED,
         "flight-recorder ring capacity in events (default 4096 "
         "native / 2048 python; clamped to [64, 1M])"),
    Knob("HVD_FLIGHTREC_DIR", HONORED,
         "directory flight-record dumps land in (default cwd; the "
         "elastic driver and serve fleet point workers at the journal "
         "dir so evidence survives the process, and launcher-spawned "
         "workers without an operator-chosen dir dump into a per-"
         "launcher temp dir instead of littering the cwd)"),
    Knob("HVD_FLIGHTREC_SIGNAL", HONORED,
         "utils/flightrec.py: 0 disables the SIGTERM dump handler "
         "(the wedge-cull SIGTERM->SIGKILL grace window is the dump "
         "window)"),
    # Sharding planner (parallel/planner.py + parallel/costmodel.py;
    # docs/planner.md).
    Knob("HVD_PLAN", HONORED,
         "__graft_entry__.dryrun_multichip planner mode: sweep = "
         "execute planner-chosen meshes across workload shapes "
         "instead of the fixed legs (docs/planner.md)"),
    Knob("HVD_PLAN_ICI_BW_GBPS", HONORED,
         "parallel/costmodel.py: ICI (intra-slice) bandwidth weight "
         "in GB/s for the planner's cost model (default 90)"),
    Knob("HVD_PLAN_DCN_BW_GBPS", HONORED,
         "parallel/costmodel.py: DCN (cross-slice) bandwidth weight "
         "in GB/s for the planner's cost model (default 6.25)"),
    Knob("HVD_PLAN_MEM_PER_CHIP_GB", HONORED,
         "parallel/costmodel.py: per-chip memory bound (GB) for the "
         "planner's memory-fit rejection (default 16)"),
    Knob("HVD_PLAN_GRAD_OVERLAP", HONORED,
         "parallel/costmodel.py: fraction of gradient-sync time the "
         "cost model counts as exposed (the rest hides under backprop "
         "via bucketing, docs/mfu.md; default 0.25, clamped to [0,1])"),
    # Fault injector (core/src/comm.cc; armed only on the matching
    # rank — see docs/configuration.md and common/fault_injection.py).
    Knob("HVD_FAULT_RANK", HONORED,
         "core/src/comm.cc: rank that self-sabotages (unset = off)"),
    Knob("HVD_FAULT_MODE", HONORED,
         "core/src/comm.cc: drop | stall | half_close | delay | "
         "reset (hard RST the self-healing wire reconnects from) | "
         "reconnect_storm (reset every K frames, bounded count)"),
    Knob("HVD_FAULT_PEER", HONORED,
         "core/src/comm.cc: half_close/reset target rank (-1 = all "
         "peers)"),
    Knob("HVD_FAULT_AFTER_FRAMES", HONORED,
         "core/src/comm.cc: arm after this many framed sends"),
    Knob("HVD_FAULT_DELAY_MS", HONORED,
         "core/src/comm.cc: per-frame sleep for delay mode"),
    Knob("HVD_FAULT_AFTER_SUBCHUNKS", HONORED,
         "core/src/comm.cc: reset mode fires after this many pipelined "
         "ring sub-chunk reductions — the RST lands mid-transfer, "
         "between sub-chunks, instead of at a frame boundary"),
    Knob("HVD_FAULT_EVERY_FRAMES", HONORED,
         "core/src/comm.cc: reconnect_storm period in frames "
         "(default 1)"),
    Knob("HVD_FAULT_COUNT", HONORED,
         "core/src/comm.cc: reconnect_storm bound — total resets fired "
         "(default 5)"),
    # Serving router breaker (serve/router.py; docs/serving.md).
    Knob("HVD_SERVE_BREAKER_THRESHOLD", HONORED,
         "serve/router.py: consecutive forward failures that trip a "
         "replica's breaker — it leaves round-robin rotation for a "
         "jittered cooldown window instead of eating live traffic "
         "(default 3; 0 disables the breaker)"),
    Knob("HVD_SERVE_BREAKER_COOLDOWN_SEC", HONORED,
         "serve/router.py: base cooldown for a tripped replica "
         "breaker, jittered +/-50% and doubled per consecutive trip "
         "(capped at 8x; default 5)"),
    # Fleet operations: drain / rolling upgrade / router failover
    # (serve/replica.py, serve/rollout.py, serve/standby.py;
    # docs/serving.md#fleet-operations-runbook).
    Knob("HVD_SERVE_DRAIN_GRACE_SEC", HONORED,
         "serve/replica.py + serve/server.py: how long a draining "
         "replica waits for its queued micro-batches before the "
         "goodbye beat and exit; Server.stop() waits this plus slack "
         "before killing stragglers (default 30)"),
    Knob("HVD_SERVE_ROLL_WAVE", HONORED,
         "serve/rollout.py: replicas upgraded per rolling-upgrade "
         "wave — the blast radius of a bad checkpoint (default 1)"),
    Knob("HVD_SERVE_ROLL_SETTLE_SEC", HONORED,
         "serve/rollout.py: per-wave health-gate window after "
         "re-admission — any new breaker charge inside it aborts and "
         "rolls the upgrade back (default 1.0)"),
    Knob("HVD_SERVE_LEASE_SEC", HONORED,
         "serve/router.py: how often the active router refreshes its "
         "leader lease next to the journal (default 1.0; <=0 disables "
         "the lease, and with it standby failover)"),
    Knob("HVD_SERVE_TAKEOVER_SEC", HONORED,
         "serve/standby.py: lease silence after which a hot standby "
         "takes over the service port and journal (default 3.0; keep "
         "well above HVD_SERVE_LEASE_SEC)"),
]}


# --- tunable-knob schema (the online tuner's search surface) -----------------
#
# Declarative contract between the performance-relevant knob surface
# and utils/online_tuner.py (docs/autotune.md): bounds, proposal
# granularity, and HOW a value reaches the running system. Three apply
# paths exist:
#
# - "native":  pushed into the live core through CoreSession
#              (set_params / set_wire_params) — takes effect within a
#              cycle, no restart, no retrace;
# - "env":     written to os.environ and read at next use — takes
#              effect at the next trace/connect/construction that
#              consults the knob;
# - "setter":  a callable the owning subsystem registers with the
#              tuner (e.g. MicroBatcher.set_tunables for the serving
#              micro-batch knobs).
#
# ``live_safe=False`` marks knobs whose LIVE per-rank mutation can
# lower rank-divergent XLA programs (trace-time reads: divergent
# meshes or wire codecs desync the collective sequence across
# ranks). The tuner only searches them when the
# process is alone in its world; they are still declared here so the
# schema is the single inventory of the tunable surface.


class TunableKnob(NamedTuple):
    name: str         # schema name (journal records, HVD_TUNE_FREEZE)
    lo: float         # search box, inclusive
    hi: float
    step: float       # proposal granularity: values snap to lo + k*step
    apply_path: str   # "native" | "env" | "setter"
    env: Optional[str]  # backing env knob (mirrored on apply when set)
    default: float    # the no-tuner value (docs/configuration.md)
    live_safe: bool   # safe to mutate per-rank mid-run (see above)
    detail: str


TUNABLE: Dict[str, TunableKnob] = {t.name: t for t in [
    TunableKnob("fusion_threshold_mb", 0.0, 64.0, 1.0, "native",
                "HOROVOD_FUSION_THRESHOLD", 128.0, True,
                "eager fusion-buffer threshold (MB; the env knob is "
                "bytes); staged through the coordinator broadcast so "
                "layouts stay rank-identical (core/session.set_params)"),
    TunableKnob("cycle_time_ms", 1.0, 100.0, 0.5, "native",
                "HOROVOD_CYCLE_TIME", 1.0, True,
                "background negotiation-loop cadence "
                "(core/session.set_params; applies locally)"),
    TunableKnob("ring_chunk_bytes", 0.0, float(16 << 20),
                float(64 << 10), "native", "HVD_RING_CHUNK_BYTES",
                float(1 << 20), True,
                "pipelined-ring sub-chunk size; atomic, read per ring "
                "step (core/session.set_wire_params; 0 = serial "
                "schedule). Local reduce scheduling only — divergence "
                "across ranks cannot desync the wire protocol"),
    TunableKnob("socket_buf_bytes", 0.0, float(16 << 20),
                float(64 << 10), "native", "HOROVOD_SOCKET_BUF_BYTES",
                0.0, True,
                "SO_SNDBUF/SO_RCVBUF on data-plane sockets; resizes "
                "live fds + pins an override for future connects "
                "(core/session.set_wire_params; 0 = kernel default "
                "for future sockets only)"),
    TunableKnob("serve_max_batch", 1.0, 64.0, 1.0, "setter",
                "HVD_SERVE_MAX_BATCH", 8.0, True,
                "serving micro-batch size trigger; tuned DOWN from the "
                "configured maximum only (buckets above it were never "
                "compiled) via MicroBatcher.set_tunables"),
    TunableKnob("serve_deadline_ms", 0.0, 50.0, 1.0, "setter",
                "HVD_SERVE_BATCH_DEADLINE_MS", 5.0, True,
                "serving micro-batch deadline trigger "
                "(MicroBatcher.set_tunables)"),
    TunableKnob("wire_codec", 0.0, 3.0, 1.0, "native",
                "HVD_WIRE_CODEC", 0.0, False,
                "wire codec id for fp32 ring payloads (0=none 1=bf16 "
                "2=fp16 3=int8; core/session.stage_wire_codec). NOT "
                "live-safe: lossy codecs change gradient numerics "
                "mid-run, so unsupervised search would fold codec "
                "noise into its objective — stage between training "
                "phases instead (docs/wire.md#compression)"),
    # Sharding-planner cost-model weights (parallel/costmodel.py,
    # docs/planner.md): searched OFFLINE only — plans are chosen at
    # setup time and per-rank divergence would pick divergent meshes,
    # the same hazard as a trace-time read. Autotune 2.0
    # fits them against measured step times (docs/autotune.md).
    TunableKnob("plan_ici_bw_gbps", 10.0, 1010.0, 10.0, "env",
                "HVD_PLAN_ICI_BW_GBPS", 90.0, False,
                "planner cost model: ICI bandwidth weight (GB/s); "
                "only the ICI:DCN ratio has to be right for the "
                "argmin to be right"),
    TunableKnob("plan_dcn_bw_gbps", 1.0, 101.0, 0.25, "env",
                "HVD_PLAN_DCN_BW_GBPS", 6.25, False,
                "planner cost model: DCN bandwidth weight (GB/s); "
                "lowering it pushes plans toward hierarchical "
                "factorizations that starve the slow links"),
    TunableKnob("plan_grad_overlap", 0.0, 1.0, 0.05, "env",
                "HVD_PLAN_GRAD_OVERLAP", 0.25, False,
                "planner cost model: exposed fraction of gradient-"
                "sync time (the rest overlaps backprop via bucketed "
                "issue, docs/mfu.md); 1.0 = no overlap credit"),
]}


def tunable_snap(knob: TunableKnob, value: float) -> float:
    """Clamp ``value`` into the knob's box and snap it to the step
    grid — every applied value is reproducible from (lo, step, k)."""
    value = min(max(float(value), knob.lo), knob.hi)
    if knob.step > 0:
        value = knob.lo + round((value - knob.lo) / knob.step) * knob.step
    return min(max(value, knob.lo), knob.hi)


def apply_aliases(env: Optional[Dict[str, str]] = None) -> None:
    """Copy reference-named aliases onto their native knobs (without
    overriding an explicitly set native value)."""
    env = os.environ if env is None else env
    for knob in REGISTRY.values():
        if knob.status != ALIASED or knob.name not in env:
            continue
        if "=" in knob.detail:  # fixed-value alias, e.g. X -> Y=0
            target, value = knob.detail.split("=", 1)
            env.setdefault(target, value)
        else:
            env.setdefault(knob.detail, env[knob.name])


def warn_rejected(env: Optional[Dict[str, str]] = None) -> list:
    """Log a warning for every set-but-rejected knob; returns the list
    of (name, reason) that fired (for tests)."""
    env = os.environ if env is None else env
    fired = []
    for knob in REGISTRY.values():
        if knob.status == REJECTED and env.get(knob.name):
            fired.append((knob.name, knob.detail))
            logger.warning(
                "%s is set but has no effect on TPU: %s",
                knob.name, knob.detail)
    return fired


def knob_table() -> str:
    """Human-readable registry dump (``python -m horovod_tpu.common.knobs``)."""
    rows = ["%-42s %-8s %s" % ("knob", "status", "detail"),
            "-" * 100]
    for knob in REGISTRY.values():
        rows.append("%-42s %-8s %s" % knob)
    return "\n".join(rows)


if __name__ == "__main__":  # pragma: no cover
    print(knob_table())
