"""Tier-2 chaos matrix: deadline-driven failure detection (ISSUE 3).

Acceptance contract under test: with ``HOROVOD_COMM_TIMEOUT_SEC`` set,
a peer that wedges (SIGSTOP — sockets open but silent), dies (kill -9),
or sabotages its connections (native fault injector: half-close, stall)
surfaces on every SURVIVING rank as the typed ``HorovodAbortedError``
within ~2x the deadline — never an infinite hang. One scenario also
runs under ThreadSanitizer to race-check the failure paths themselves.

Fast tier-1 stand-ins for the pure-Python pieces live in
tests/test_fault_tolerance.py.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from horovod_tpu.common.fault_injection import fault_env
from tests.test_native_core import _REPO, _ensure_tsan_core, _free_port, _launch

_WORKER = os.path.join(_REPO, "tests", "chaos_worker.py")

pytestmark = [pytest.mark.tier2, pytest.mark.slow]

DEADLINE = 3.0


def _spawn(np_, extra_env):
    """Async variant of test_native_core._launch: returns live Popen
    handles so scenarios can reap survivors before cleaning up a
    wedged victim."""
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r),
            "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_CROSS_RANK": "0",
            "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
            "HOROVOD_CONTROLLER_PORT": str(port),
            "HOROVOD_CYCLE_TIME": "1.0",
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, _WORKER], env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    return procs


def _run_chaos(np_, mode, extra_env=None, deadline=DEADLINE, timeout=150):
    """Run one scenario; returns (codes, outputs) keyed by rank. The
    victim (always the last rank) may be left wedged by design
    (sigstop/stall); it is reaped with SIGCONT+SIGKILL after the
    survivors are collected."""
    victim = np_ - 1
    env = {
        "CHAOS_MODE": mode,
        "CHAOS_VICTIM": str(victim),
        "CHAOS_EXPECT_WINDOW": str(2 * deadline),
        "HOROVOD_COMM_TIMEOUT_SEC": str(deadline),
    }
    env.update(extra_env or {})
    procs = _spawn(np_, env)
    victim_hangs = mode in ("sigstop", "stall")
    outputs, codes = {}, {}
    hard_deadline = time.time() + timeout
    try:
        for r, p in enumerate(procs):
            if r == victim and victim_hangs:
                continue
            out, _ = p.communicate(
                timeout=max(5.0, hard_deadline - time.time()))
            outputs[r], codes[r] = out, p.returncode
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    finally:
        vp = procs[victim]
        if vp.poll() is None:
            try:
                os.kill(vp.pid, signal.SIGCONT)  # a SIGSTOPped child
            except ProcessLookupError:
                pass
            vp.kill()
        if victim not in outputs:
            try:
                vout, _ = vp.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                vp.kill()
                vout = ""
            outputs[victim] = vout or ""
            codes[victim] = vp.returncode
    return codes, outputs


def _assert_survivors_typed(codes, outputs, survivors):
    for r in survivors:
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK typed error" in outputs[r], outputs[r]


def _counter(outputs, rank, name):
    for line in outputs[rank].splitlines():
        if line.startswith("COUNTERS"):
            for field in line.split()[1:]:
                k, v = field.split("=")
                if k == name:
                    return int(v)
    return 0


@pytest.mark.parametrize("np_", [2, 3])
def test_chaos_sigstop_typed_error(np_):
    """A SIGSTOPped peer mid-allreduce (open-but-silent sockets: no FIN,
    no RST) produces the typed error on every survivor within 2x the
    deadline — the headline acceptance criterion."""
    codes, outputs = _run_chaos(np_, "sigstop")
    survivors = range(np_ - 1)
    _assert_survivors_typed(codes, outputs, survivors)
    # Detection had to come from the progress deadline: at least one
    # survivor's poll timed out (the rest may fail via the cascade).
    assert sum(_counter(outputs, r, "timeouts") for r in survivors) >= 1, \
        "\n".join(outputs.values())


def test_chaos_kill9_abort_cascade():
    """kill -9 mid-collective: the closed socket drives the abort
    cascade and the typed error arrives well inside the window."""
    codes, outputs = _run_chaos(3, "kill9")
    _assert_survivors_typed(codes, outputs, (0, 1))
    assert codes[2] == -9, "victim should have died by SIGKILL:\n%s" \
        % outputs[2]


def test_chaos_half_close_injected():
    """Native fault injector: the victim half-closes its connections
    after 100 frames; every rank — victim included, its writes are
    dead — observes the typed error."""
    codes, outputs = _run_chaos(
        2, "half_close",
        extra_env=fault_env(1, "half_close", after_frames=100))
    _assert_survivors_typed(codes, outputs, (0, 1))


def test_chaos_drop_pipelined_ring():
    """Fault-injector compatibility with the pipelined wire path
    (docs/wire.md): a tiny HVD_RING_CHUNK_BYTES forces many sub-chunk
    callbacks per ring step, but HVD_FAULT_AFTER_FRAMES still counts
    ONE frame per vectored send / duplex transfer, however many
    sub-chunk callbacks fire inside it — the injected drop lands
    mid-pipeline (a 16 MB doom payload at 4 KB chunks is thousands of
    sub-chunks per ring step) and every rank, victim included, must
    observe the typed HorovodAbortedError, never a hang."""
    codes, outputs = _run_chaos(
        2, "half_close",
        extra_env=dict(fault_env(1, "drop", after_frames=100),
                       HVD_RING_CHUNK_BYTES="4096"))
    _assert_survivors_typed(codes, outputs, (0, 1))


def test_chaos_stall_pipelined_ring():
    """Same pipelined schedule, stall mode: the victim's background
    thread parks between sub-chunks and the survivor's progress
    deadline must fire through the chunked RawSendRecvV poll loop."""
    codes, outputs = _run_chaos(
        2, "stall",
        extra_env=dict(fault_env(1, "stall", after_frames=100),
                       HVD_RING_CHUNK_BYTES="4096"))
    _assert_survivors_typed(codes, outputs, (0,))
    assert _counter(outputs, 0, "timeouts") >= 1, outputs[0]


def test_chaos_stall_injected():
    """Native fault injector: the victim's background thread parks
    forever (comm-layer SIGSTOP analog); the survivor's deadline fires."""
    codes, outputs = _run_chaos(
        2, "stall", extra_env=fault_env(1, "stall", after_frames=100))
    _assert_survivors_typed(codes, outputs, (0,))
    assert _counter(outputs, 0, "timeouts") >= 1, outputs[0]


def test_chaos_reset_heals_in_place(tmp_path):
    """ISSUE 15 acceptance: np=3 pipelined-ring allreduce loop with a
    hard RST injected MID-TRANSFER (between pipelined sub-chunk
    reductions) heals in place — every step completes bit-identical to
    the fault-free run, hvd_comm_reconnects_total >= 1 on every rank,
    ZERO aborts, ZERO elastic resets (no restart machinery runs at
    all), and tools.trace reads the flight records as 'healed', not
    'wedged'."""
    victim = 2
    codes, outputs = _run_chaos(
        3, "reset_heal",
        extra_env=dict(fault_env(victim, "reset", after_subchunks=30),
                       HVD_RING_CHUNK_BYTES="262144",
                       HVD_FLIGHTREC_DIR=str(tmp_path),
                       # Big ring: the heal happens early and the loop
                       # keeps recording for seconds afterwards — the
                       # WIRE_* evidence must not wrap away before the
                       # end-of-run dump.
                       HVD_FLIGHTREC_EVENTS="65536"))
    for r in range(3):
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK healed" in outputs[r], outputs[r]
        assert "elastic_resets=0" in outputs[r], outputs[r]
    # Every rank healed at least one link (the victim healed two).
    heals = [int(outputs[r].split("reconnects=")[1].split()[0])
             for r in range(3)]
    assert all(h >= 1 for h in heals), heals
    assert heals[victim] >= 2, heals

    from tools import trace

    dumps = trace.load_dir(str(tmp_path))
    assert set(dumps) == {0, 1, 2}, sorted(dumps)
    trace.align(dumps)
    diag = trace.diagnose(dumps, np_hint=3)
    assert diag["verdict"] == "healed", diag
    assert diag["culprit_ranks"] == [], diag
    assert len(diag["wire_heals"]) >= 4, diag["wire_heals"]


def test_chaos_reconnect_storm_heals_repeatedly():
    """reconnect_storm: the link RSTs again and again (bounded count)
    while 16 MB rings are in flight — healing must be re-entrant, each
    resume exact, and the job still completes every step bit-identical."""
    codes, outputs = _run_chaos(
        2, "reset_heal",
        extra_env=dict(fault_env(1, "reconnect_storm", after_frames=200,
                                 every_frames=400, count=3),
                       HVD_RING_CHUNK_BYTES="262144"))
    for r in range(2):
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK healed" in outputs[r], outputs[r]
    heals = [int(outputs[r].split("reconnects=")[1].split()[0])
             for r in range(2)]
    assert all(h >= 2 for h in heals), heals


def test_chaos_reset_reconnect_disabled_legacy_abort():
    """HVD_WIRE_RECONNECT_SEC=0 regression-pins the escalation path:
    the SAME injection produces the legacy typed HorovodAbortedError on
    every rank within 2x HOROVOD_COMM_TIMEOUT_SEC — byte-compatible
    with the pre-reconnect failure story (elastic recovery takes over
    from here exactly as before)."""
    codes, outputs = _run_chaos(
        2, "reset_legacy",
        extra_env=dict(fault_env(1, "reset", after_frames=200),
                       HVD_WIRE_RECONNECT_SEC="0"))
    _assert_survivors_typed(codes, outputs, (0, 1))


@pytest.mark.parametrize("np_,mode", [(2, "sigstop"), (3, "stall")])
def test_chaos_forensics_names_culprit(tmp_path, np_, mode):
    """End-to-end forensics proof (docs/flightrec.md): a wedged rank —
    SIGSTOP at np=2, injected comm-layer stall at np=3 — leaves enough
    evidence in the survivors' flight-record dumps for
    ``python -m tools.trace`` to name the culprit rank AND the
    in-flight doom tensor. The victim itself dumps nothing (it cannot
    run); its absence plus the survivors' timeout/negotiation events
    is exactly the attribution the recorder exists for."""
    victim = np_ - 1
    extra = {"HVD_FLIGHTREC_DIR": str(tmp_path)}
    if mode == "stall":
        extra.update(fault_env(victim, "stall", after_frames=100))
    codes, outputs = _run_chaos(np_, mode, extra_env=extra)
    survivors = [r for r in range(np_) if r != victim]
    _assert_survivors_typed(codes, outputs, survivors)

    from tools import trace

    dumps = trace.load_dir(str(tmp_path))
    # Every survivor auto-dumped on the typed abort; the victim left
    # no dump (SIGSTOP/parked thread — no trigger could fire).
    assert set(survivors) <= set(dumps), (sorted(dumps), outputs)
    assert victim not in dumps, sorted(dumps)
    trace.align(dumps)
    diag = trace.diagnose(dumps, np_hint=np_)
    assert diag["culprit_ranks"] == [victim], (diag, outputs)
    # The in-flight tensor: the op the survivors died inside (failed/
    # unclosed RESP), a tensor some rank never submitted, or an eager
    # submit that never completed — whichever plane the wedge landed in.
    named = {f["name"] for f in diag["in_flight"]}
    named |= set(diag["stalled_tensors"])
    named |= {p["name"] for p in diag["pending_submits"]}
    assert any(n.startswith("doom") for n in named), (diag, outputs)
    # The CLI agrees (the operator-facing surface of the same verdict).
    import subprocess as sp

    cli = sp.run([sys.executable, "-m", "tools.trace", str(tmp_path),
                  "--np", str(np_)], cwd=_REPO, capture_output=True,
                 text=True, timeout=60)
    assert cli.returncode == 0, cli.stderr
    assert "CULPRIT rank(s): [%d]" % victim in cli.stdout, cli.stdout


def test_fault_injection_tsan_smoke():
    """One injected failure under ThreadSanitizer: the abort/timeout
    paths (poll deadline, cascade, status propagation) must be
    race-free. The sanitized core is built BEFORE the workers launch —
    forking make under a preloaded libtsan deadlocks — and the worker
    is jax-free (importing jax under TSAN takes minutes)."""
    import glob

    libtsan = None
    for pat in ("/usr/lib/x86_64-linux-gnu/libtsan.so.*",
                "/usr/lib/gcc/x86_64-linux-gnu/*/libtsan.so"):
        hits = sorted(glob.glob(pat))
        if hits:
            libtsan = hits[-1]
            break
    if libtsan is None:
        pytest.skip("libtsan not available")
    _ensure_tsan_core()
    report_prefix = os.path.join(
        _REPO, "horovod_tpu", "core", "build-thread", "chaos_tsan_report")
    for old in glob.glob(report_prefix + "*"):
        os.unlink(old)
    env = fault_env(1, "half_close", after_frames=50)
    env.update({
        "HVD_CORE_SANITIZE": "thread",
        "LD_PRELOAD": libtsan,
        "TSAN_OPTIONS": "report_thread_leaks=0 exitcode=66 "
                        "log_path=%s" % report_prefix,
        "HOROVOD_COMM_TIMEOUT_SEC": "10",
    })
    codes, outputs = _launch(
        2, os.path.join(_REPO, "tests", "chaos_tsan_worker.py"),
        extra_env=env, timeout=300)
    reports = glob.glob(report_prefix + "*")
    blobs = "".join(open(p).read() for p in reports)
    assert codes == [0, 0] and not reports, (
        "TSAN reports:\n%s\nworker output:\n%s"
        % (blobs[:4000], "\n".join(outputs)[-3000:]))
    assert sum("CHAOS_TSAN_OK" in o for o in outputs) == 2
