"""hvdrun — the launcher CLI.

The ``horovodrun`` equivalent (reference: horovod/runner/launch.py:242-774):
parses hosts/np/tuning flags, maps CLI flags onto the core's environment
knobs (reference: runner/common/util/config_parser.py set_env_from_args),
computes slot assignments, starts the rendezvous KV server, and fans out
one worker process per slot (local subprocess or ssh), streaming output.

Usage::

    python -m horovod_tpu.runner -np 4 python train.py
    python -m horovod_tpu.runner -np 8 -H host1:4,host2:4 python train.py
    python -m horovod_tpu.runner -np 2 --min-np 2 --max-np 4 \
        --host-discovery-script ./discover.sh python train.py   # elastic
"""

from __future__ import annotations

import argparse
import os
import socket
import sys
import time
from typing import Dict, List, Optional

from horovod_tpu.runner.exec_util import SlotProcess, is_local
from horovod_tpu.runner.hosts import (
    HostInfo, get_host_assignments, parse_hostfile, parse_hosts,
)
from horovod_tpu.runner.http_server import RendezvousServer


def free_port() -> int:
    s = socket.socket()
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_flightrec_fallback_dir: Optional[str] = None


def flightrec_default_dir() -> str:
    """Where spawned workers auto-dump flight records when the
    operator didn't pin ``HVD_FLIGHTREC_DIR``: one temp dir per
    launcher process (memoized so every rank of a job dumps into the
    same place). Without this, an aborting worker drops
    ``flightrec.rank*.jsonl`` files into the LAUNCHING process's cwd —
    test- and bench-spawned fleets were littering the repo root."""
    global _flightrec_fallback_dir
    if _flightrec_fallback_dir is None:
        import tempfile

        _flightrec_fallback_dir = tempfile.mkdtemp(
            prefix="hvd_flightrec_")
    return _flightrec_fallback_dir


def _flightrec_env(env: Dict[str, str]) -> Dict[str, str]:
    """Add the flightrec dump-dir default to a worker env — unless the
    operator chose one (in the worker's extra env, or inherited: the
    spawn paths overlay ``env`` on ``os.environ``)."""
    if "HVD_FLIGHTREC_DIR" not in env \
            and "HVD_FLIGHTREC_DIR" not in os.environ:
        env["HVD_FLIGHTREC_DIR"] = flightrec_default_dir()
    return env


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    import horovod_tpu

    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu distributed job.")
    p.add_argument("-v", "--version", action="version",
                   version=horovod_tpu.__version__)
    p.add_argument("-np", "--num-proc", type=int, dest="np",
                   help="Total number of worker processes.")
    p.add_argument("-cb", "--check-build", action="store_true",
                   dest="check_build",
                   help="Print available frameworks/controllers/"
                        "operations and exit (reference: launch.py "
                        "--check-build).")
    p.add_argument("-H", "--hosts", dest="hosts",
                   help="Comma-separated host:slots list.")
    p.add_argument("-hostfile", "--hostfile", dest="hostfile",
                   help="Hostfile path (hostname slots=N per line).")
    p.add_argument("-p", "--ssh-port", type=int, dest="ssh_port")
    p.add_argument("-i", "--ssh-identity-file", dest="ssh_identity_file",
                   help="Private-key identity file passed to ssh for "
                        "remote slot fan-out.")
    p.add_argument("--start-timeout", type=int, default=120)
    p.add_argument("--disable-cache", action="store_true",
                   dest="disable_cache",
                   help="Disable the coordination response cache "
                        "(HOROVOD_CACHE_CAPACITY=0): every tensor "
                        "renegotiates every cycle.")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--output-filename", dest="output_filename",
                   help="Redirect worker output to this file.")
    p.add_argument("-prefix-timestamp", "--prefix-output-with-timestamp",
                   action="store_true", dest="prefix_output_with_timestamp",
                   help="Timestamp each forwarded worker output line.")
    # Elastic (reference: launch.py elastic args).
    p.add_argument("--min-np", type=int, dest="min_np")
    p.add_argument("--max-np", type=int, dest="max_np")
    p.add_argument("--host-discovery-script", dest="discovery_script")
    p.add_argument("--slots-per-host", type=int, dest="slots_per_host",
                   help="Elastic: slots per discovered host when the "
                        "discovery script does not specify them.")
    p.add_argument("--reset-limit", type=int, dest="reset_limit")
    p.add_argument("--elastic-timeout", type=int, dest="elastic_timeout",
                   default=None,
                   help="Timeout (s) for elastic re-initialisation after "
                        "re-scaling; default 600 or "
                        "HOROVOD_ELASTIC_TIMEOUT.")
    p.add_argument("--journal-dir", dest="journal_dir", default=None,
                   help="Elastic: directory for the driver's fsync'd "
                        "membership journal (or "
                        "HOROVOD_ELASTIC_JOURNAL_DIR). A restarted "
                        "driver replays it and resumes at the next "
                        "rendezvous version instead of losing the job.")
    # Core tuning knobs → env (reference: config_parser.py
    # set_env_from_args; flag names match launch.py:304-475).
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--hierarchical-allreduce", action="store_true",
                   default=None, dest="hierarchical_allreduce")
    p.add_argument("--no-hierarchical-allreduce", action="store_false",
                   dest="hierarchical_allreduce")
    p.add_argument("--hierarchical-allgather", action="store_true",
                   default=None, dest="hierarchical_allgather")
    p.add_argument("--no-hierarchical-allgather", action="store_false",
                   dest="hierarchical_allgather")
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true",
                   default=None, dest="timeline_mark_cycles")
    p.add_argument("--no-timeline-mark-cycles", action="store_false",
                   dest="timeline_mark_cycles")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--no-autotune", action="store_false", dest="autotune")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--autotune-warmup-samples", type=int, default=None)
    p.add_argument("--autotune-steps-per-sample", type=int, default=None)
    p.add_argument("--autotune-bayes-opt-max-samples", type=int,
                   default=None)
    p.add_argument("--autotune-gaussian-process-noise", type=float,
                   default=None)
    # Stall inspector (reference: launch.py:408-421).
    p.add_argument("--no-stall-check", action="store_true", default=None,
                   dest="no_stall_check")
    p.add_argument("--stall-check", action="store_false",
                   dest="no_stall_check")
    p.add_argument("--stall-check-warning-time-seconds", type=float,
                   default=None)
    p.add_argument("--stall-check-shutdown-time-seconds", type=float,
                   default=None)
    # Library / logging (reference: launch.py:423-476).
    p.add_argument("--thread-affinity", type=int, default=None,
                   help="Pin each worker's coordination thread to CPU "
                        "(base + local_rank).")
    p.add_argument("--log-level", default=None,
                   choices=["trace", "debug", "info", "warning", "error"])
    p.add_argument("--log-with-timestamp", action="store_true",
                   default=None, dest="log_with_timestamp")
    p.add_argument("--log-without-timestamp", action="store_false",
                   dest="log_with_timestamp")
    # Legacy spellings (reference: launch.py:468-475 deprecated pair).
    p.add_argument("--log-hide-timestamp", action="store_false",
                   dest="log_with_timestamp")
    p.add_argument("--no-log-hide-timestamp", action="store_true",
                   dest="log_with_timestamp")
    p.add_argument("--mpi-threads-disable", action="store_true",
                   default=None, dest="mpi_threads_disable",
                   help="Disable MPI threading support (mpirun mode "
                        "only; reference: launch.py:425-434).")
    p.add_argument("--no-mpi-threads-disable", action="store_false",
                   dest="mpi_threads_disable")
    p.add_argument("--num-nccl-streams", type=int, default=None,
                   dest="num_nccl_streams",
                   help="Accepted for reference CLI parity; NCCL stream "
                        "pools have no TPU equivalent (device "
                        "collectives are XLA programs) — see the knob "
                        "registry entry for HOROVOD_NUM_NCCL_STREAMS.")
    p.add_argument("--tcp", action="store_true", dest="tcp_flag",
                   help="Use only TCP for communication (always true "
                        "here: the control plane is the native TCP "
                        "mesh; accepted for reference CLI parity).")
    p.add_argument("--gloo-timeout-seconds", type=int, default=None,
                   dest="gloo_timeout_seconds",
                   help="Accepted for reference CLI parity; liveness "
                        "here is enforced by the stall inspector "
                        "(--stall-check-*).")
    p.add_argument("--binding-args", dest="binding_args", default=None,
                   help="Process binding arguments passed through to "
                        "jsrun (reference: launch.py:438-440).")
    # Controller selection (reference: launch.py run_controller
    # gloo/mpi/jsrun dispatch).
    p.add_argument("--use-gloo", "--gloo", action="store_true",
                   dest="use_gloo",
                   help="Force the built-in TCP (gloo-style) launcher.")
    p.add_argument("--use-mpi", "--mpi", action="store_true",
                   dest="use_mpi",
                   help="Launch through a single mpirun command.")
    p.add_argument("--use-jsrun", "--jsrun", action="store_true",
                   dest="use_jsrun",
                   help="Launch through LSF jsrun.")
    p.add_argument("--mpi-args", dest="mpi_args", default=None,
                   help="Extra arguments passed through to mpirun.")
    p.add_argument("--network-interfaces", "--network-interface",
                   dest="nics", default=None,
                   help="Comma-separated NIC allowlist for the data/"
                        "control plane.")
    p.add_argument("--platform", choices=["cpu", "tpu"], default="cpu",
                   help="JAX backend for the spawned workers. 'cpu' "
                        "(default) pins every worker to the CPU "
                        "backend. 'tpu' makes the TPU their backend: "
                        "several slots on one host get one chip each "
                        "(the local_rank-th), a lone slot owns all of "
                        "its host's chips.")
    p.add_argument("--config-file", dest="config_file", default=None,
                   help="YAML file whose keys mirror the long CLI flags "
                        "(reference: launch.py --config-file).")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Command to run on every slot.")
    args = p.parse_args(argv)
    if args.config_file:
        _apply_config_file(p, args)
    if not args.command and not args.check_build:
        p.error("no command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    return args


def _apply_config_file(parser: argparse.ArgumentParser, args) -> None:
    """Overlay YAML config onto args: CLI flags explicitly given win;
    unset flags take the file's value (reference: launch.py:293-297 +
    runner/common/util/config_parser.py). Keys use the long flag names
    with dashes or underscores."""
    import yaml

    with open(args.config_file) as f:
        cfg = yaml.safe_load(f) or {}
    if not isinstance(cfg, dict):
        raise ValueError("--config-file must contain a YAML mapping")
    defaults = parser.parse_args(["dummy"])  # all-default namespace
    for raw_key, value in cfg.items():
        key = raw_key.replace("-", "_")
        if key in ("command", "config_file"):
            continue
        if not hasattr(args, key):
            raise ValueError("unknown config-file key: %s" % raw_key)
        # Only fill in values the CLI left at default.
        if getattr(args, key) == getattr(defaults, key):
            setattr(args, key, value)


def _hosts_from_args(args) -> List[HostInfo]:
    if args.hosts:
        return parse_hosts(args.hosts)
    if args.hostfile:
        return parse_hostfile(args.hostfile)
    np_ = args.np or 1
    return [HostInfo("localhost", np_)]


def _tuning_env(args) -> Dict[str, str]:
    env = {}
    if args.fusion_threshold_mb is not None:
        env["HOROVOD_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024))
    if args.cycle_time_ms is not None:
        env["HOROVOD_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HOROVOD_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.hierarchical_allreduce is not None:
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = (
            "1" if args.hierarchical_allreduce else "0")
    if args.hierarchical_allgather is not None:
        env["HOROVOD_HIERARCHICAL_ALLGATHER"] = (
            "1" if args.hierarchical_allgather else "0")
    if args.timeline_filename:
        env["HOROVOD_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if args.autotune:
        env["HOROVOD_AUTOTUNE"] = "1"
        if args.autotune_log_file:
            env["HOROVOD_AUTOTUNE_LOG"] = args.autotune_log_file
        for attr, knob in (
                ("autotune_warmup_samples",
                 "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"),
                ("autotune_steps_per_sample",
                 "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"),
                ("autotune_bayes_opt_max_samples",
                 "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"),
                ("autotune_gaussian_process_noise",
                 "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE")):
            value = getattr(args, attr)
            if value is not None:
                env[knob] = str(value)
    if args.no_stall_check:
        env["HOROVOD_STALL_CHECK_DISABLE"] = "1"
    if args.stall_check_warning_time_seconds is not None:
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = str(
            args.stall_check_warning_time_seconds)
    if args.stall_check_shutdown_time_seconds is not None:
        env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = str(
            args.stall_check_shutdown_time_seconds)
    if args.thread_affinity is not None:
        env["HOROVOD_THREAD_AFFINITY"] = str(args.thread_affinity)
    if args.log_level:
        env["HOROVOD_LOG_LEVEL"] = args.log_level
    if args.log_with_timestamp is not None:
        env["HOROVOD_LOG_TIMESTAMP"] = (
            "1" if args.log_with_timestamp else "0")
    if args.disable_cache:
        env["HOROVOD_CACHE_CAPACITY"] = "0"
    if args.elastic_timeout is not None:
        env["HOROVOD_ELASTIC_TIMEOUT"] = str(args.elastic_timeout)
    if args.mpi_threads_disable is not None:
        env["HOROVOD_MPI_THREADS_DISABLE"] = (
            "1" if args.mpi_threads_disable else "0")
    if args.num_nccl_streams is not None:
        env["HOROVOD_NUM_NCCL_STREAMS"] = str(args.num_nccl_streams)
    if args.gloo_timeout_seconds is not None:
        env["HOROVOD_GLOO_TIMEOUT_SECONDS"] = str(
            args.gloo_timeout_seconds)
    return env


def worker_platform_env(platform: str = "cpu", local_rank: int = 0,
                        local_size: int = 1) -> Dict[str, str]:
    """Env entries that give a spawned worker its JAX backend.

    ``cpu`` (the default) pins the worker to the CPU backend.

    ``tpu`` makes the TPU the default backend: jax fails at start-up
    when a platform it was told to use cannot initialise, so a worker
    that cannot reach its chip does not train on the CPU instead.
    ``cpu`` stays listed behind it because ``io_callback``, the bridge
    that carries this launch shape's gradients into the native ring,
    places its operands on a local CPU device. A TPU chip belongs to
    one process at a time, so when a host runs several slots each one
    is shown exactly one chip, the ``local_rank``-th, as a 1x1x1
    topology of its own (libtpu lets several processes load when each
    claims a strict subset of the host's chips). A lone slot on a host
    keeps the inherited TPU environment and owns every chip.
    """
    if platform != "tpu":
        return {"JAX_PLATFORMS": "cpu"}
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    if local_size > 1:
        env.update({
            "TPU_VISIBLE_CHIPS": str(local_rank),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        })
    return env


def _shared_platform_env(args, assignments) -> Dict[str, str]:
    """The platform env of a launcher that starts every rank with ONE
    environment (mpirun, jsrun) and so cannot hand each slot of a host
    its own chip."""
    if args.platform == "tpu" and any(a.local_size > 1
                                      for a in assignments):
        raise ValueError(
            "--platform tpu with several slots on one host needs the "
            "built-in launcher: mpirun/jsrun give every rank the same "
            "environment, and each slot must be shown its own chip")
    return worker_platform_env(args.platform)


def slot_env(a, controller_addr: str, controller_port: int,
             rendezvous_addr: str, rendezvous_port: int,
             extra: Dict[str, str], platform: str = "cpu") -> Dict[str, str]:
    """Per-slot environment (reference: gloo_run.py:65-76)."""
    env = worker_platform_env(platform, a.local_rank, a.local_size)
    env.update({
        "HOROVOD_RANK": str(a.rank),
        "HOROVOD_SIZE": str(a.size),
        "HOROVOD_LOCAL_RANK": str(a.local_rank),
        "HOROVOD_LOCAL_SIZE": str(a.local_size),
        "HOROVOD_CROSS_RANK": str(a.cross_rank),
        "HOROVOD_CROSS_SIZE": str(a.cross_size),
        "HOROVOD_CONTROLLER_ADDR": controller_addr,
        "HOROVOD_CONTROLLER_PORT": str(controller_port),
        "HOROVOD_RENDEZVOUS_ADDR": rendezvous_addr,
        "HOROVOD_RENDEZVOUS_PORT": str(rendezvous_port),
        "HOROVOD_HOSTNAME": a.hostname,
        "PYTHONUNBUFFERED": "1",
    })
    pythonpath = os.pathsep.join(
        [os.getcwd()] + ([os.environ["PYTHONPATH"]]
                         if "PYTHONPATH" in os.environ else []))
    env["PYTHONPATH"] = pythonpath
    env.update(extra)
    return _flightrec_env(env)


def _run_static(args) -> int:
    hosts = _hosts_from_args(args)
    np_ = args.np or sum(h.slots for h in hosts)
    assignments = get_host_assignments(hosts, np_, np_)

    rendezvous = RendezvousServer()
    rendezvous_port = rendezvous.start()
    rendezvous.publish(assignments)

    # Rank 0's host runs the controller; workers dial it there.
    rank0_host = assignments[0].hostname
    controller_addr = "127.0.0.1" if is_local(rank0_host) else rank0_host
    launcher_default = (socket.gethostname()
                        if any(not is_local(a.hostname)
                               for a in assignments)
                        else "127.0.0.1")
    # --network-interfaces pins the rendezvous/controller endpoints (and
    # thus all control-plane traffic) to the named NICs.
    launcher_host = _launcher_addr(args.nics, launcher_default)
    if args.nics and is_local(rank0_host):
        controller_addr = launcher_host
    controller_port = free_port()

    extra = _tuning_env(args)
    if args.nics:
        extra["HOROVOD_IFACE"] = args.nics
    output_file = (open(args.output_filename, "w")
                   if args.output_filename else None)
    procs: List[SlotProcess] = []
    try:
        for a in assignments:
            env = slot_env(a, controller_addr, controller_port,
                           launcher_host, rendezvous_port, extra,
                           platform=args.platform)
            procs.append(SlotProcess(
                a.rank, args.command, env, hostname=a.hostname,
                ssh_port=args.ssh_port,
                ssh_identity_file=args.ssh_identity_file,
                output_file=output_file,
                prefix_timestamp=args.prefix_output_with_timestamp))
        # Wait; first failure kills the job (reference: gloo_run.py:259-271).
        exit_code = 0
        pending = set(range(len(procs)))
        while pending:
            for i in list(pending):
                rc = procs[i].poll()
                if rc is None:
                    continue
                pending.discard(i)
                if rc != 0:
                    exit_code = rc
                    sys.stderr.write(
                        "hvdrun: rank %d exited with code %d; terminating "
                        "remaining workers\n" % (procs[i].rank, rc))
                    for j in pending:
                        procs[j].terminate()
                    pending.clear()
                    break
            time.sleep(0.1)
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.terminate()
        return exit_code
    finally:
        if output_file:
            output_file.close()
        rendezvous.stop()


def _launcher_addr(nics: Optional[str], default: str) -> str:
    """Pick the launcher-side address workers should dial. With
    --network-interfaces, resolve an address on one of those NICs."""
    if not nics:
        return default
    from horovod_tpu.runner.network import local_addresses

    addrs = local_addresses()
    for nic in nics.split(","):
        if nic in addrs and addrs[nic]:
            return addrs[nic][0]
    raise ValueError(
        "--network-interfaces %r matched no local interface with an IPv4 "
        "address (have: %s)" % (nics, ", ".join(sorted(addrs))))


def _run_mpi(args) -> int:
    """Single-mpirun path (reference: launch.py run_controller mpi)."""
    from horovod_tpu.runner.mpi_run import run_mpi

    np_ = args.np or 1
    rendezvous = RendezvousServer()
    rendezvous_port = rendezvous.start()
    hosts = _hosts_from_args(args)
    assignments = get_host_assignments(hosts, np_, np_)
    rendezvous.publish(assignments)
    # Reconstruct the -H string from the parsed hosts so --hostfile works
    # identically to -H.
    hosts_str = ",".join("%s:%d" % (h.hostname, h.slots) for h in hosts) \
        if (args.hosts or args.hostfile) else None
    rank0_host = assignments[0].hostname
    all_local = all(is_local(h.hostname) for h in hosts)
    env = _tuning_env(args)
    env.update(_shared_platform_env(args, assignments))
    env.update({
        "HOROVOD_CONTROLLER_ADDR": ("127.0.0.1" if is_local(rank0_host)
                                    else rank0_host),
        "HOROVOD_CONTROLLER_PORT": str(free_port()),
        "HOROVOD_RENDEZVOUS_ADDR": _launcher_addr(
            args.nics,
            "127.0.0.1" if all_local else socket.gethostname()),
        "HOROVOD_RENDEZVOUS_PORT": str(rendezvous_port),
        "PYTHONUNBUFFERED": "1",
    })
    _flightrec_env(env)
    try:
        return run_mpi(np_, hosts_str, args.command, env,
                       nics=args.nics.split(",") if args.nics else None,
                       extra_mpi_args=args.mpi_args,
                       output_filename=args.output_filename)
    finally:
        rendezvous.stop()


def _run_jsrun(args) -> int:
    from horovod_tpu.runner.js_run import LSFUtils, js_run

    np_ = args.np or LSFUtils.get_num_processes()
    compute_hosts = LSFUtils.get_compute_hosts()
    num_hosts = max(len(compute_hosts), 1)
    if np_ % num_hosts != 0:
        # jsrun resource sets are uniform; a silent floor would launch
        # fewer workers than HOROVOD_SIZE and hang the first collective.
        raise ValueError(
            "-np %d does not divide evenly across %d LSF hosts; pick a "
            "multiple of the host count" % (np_, num_hosts))
    per_host = np_ // num_hosts
    hosts = ([HostInfo(h, per_host) for h in compute_hosts]
             or [HostInfo("localhost", np_)])
    rendezvous = RendezvousServer()
    rendezvous_port = rendezvous.start()
    assignments = get_host_assignments(hosts, np_, np_)
    rendezvous.publish(assignments)
    env = _tuning_env(args)
    env.update(_shared_platform_env(args, assignments))
    env.update({
        "HOROVOD_CONTROLLER_ADDR": assignments[0].hostname,
        "HOROVOD_CONTROLLER_PORT": str(free_port()),
        "HOROVOD_RENDEZVOUS_ADDR": _launcher_addr(args.nics,
                                                  socket.gethostname()),
        "HOROVOD_RENDEZVOUS_PORT": str(rendezvous_port),
        "PYTHONUNBUFFERED": "1",
    })
    _flightrec_env(env)
    try:
        return js_run(np_, args.command, env,
                      extra_args=args.binding_args)
    finally:
        rendezvous.stop()


def check_build(file=None) -> int:
    """Print the availability matrix (reference: launch.py
    --check-build prints frameworks / controllers / operations)."""
    import importlib.util
    import shutil

    import horovod_tpu

    file = file or sys.stdout

    def _have(mod):
        try:
            return importlib.util.find_spec(mod) is not None
        except (ImportError, ValueError):
            # ValueError: a stub in sys.modules with __spec__ = None.
            return False

    def _jsrun_available():
        try:
            from horovod_tpu.runner.js_run import is_jsrun_installed
            return is_jsrun_installed()
        except Exception:
            return False

    def _box(ok):
        return "[X]" if ok else "[ ]"

    # Report-only: do NOT trigger a build from a status command (the
    # reference's --check-build likewise reports what exists).
    try:
        from horovod_tpu.core.build import library_path
        native_built = library_path(build_if_missing=False) is not None
    except Exception:
        native_built = False
    lines = [
        "Horovod-TPU v%s:" % horovod_tpu.__version__,
        "",
        "Available Frameworks:",
        "    %s JAX" % _box(_have("jax")),
        "    %s TensorFlow" % _box(_have("tensorflow")),
        "    %s Keras" % _box(_have("keras")),
        "    %s PyTorch" % _box(_have("torch")),
        "    %s MXNet" % _box(_have("mxnet")),
        "",
        "Available Controllers:",
        "    %s TCP (native full mesh + HTTP rendezvous)" % _box(
            native_built),
        "    %s mpirun (process launch only)" % _box(
            shutil.which("mpirun") is not None),
        "    %s LSF jsrun" % _box(_jsrun_available()),
        "",
        "Available Tensor Operations:",
        "    %s XLA in-graph collectives (TPU/ICI)" % _box(_have("jax")),
        "    %s native CPU collectives" % _box(native_built),
        "    %s TF collective runtime" % _box(_have("tensorflow")),
    ]
    file.write("\n".join(lines) + "\n")
    return 0


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.check_build:
        return check_build()
    if sum([args.use_gloo, args.use_mpi, args.use_jsrun]) > 1:
        raise ValueError(
            "--use-gloo, --use-mpi and --use-jsrun are mutually exclusive")
    if args.discovery_script or args.min_np or args.max_np:
        from horovod_tpu.runner.elastic_run import run_elastic

        return run_elastic(args)
    if args.use_mpi:
        return _run_mpi(args)
    if args.use_jsrun:
        return _run_jsrun(args)
    return _run_static(args)


def main():
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
