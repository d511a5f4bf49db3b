"""Multi-process tests of the native coordination core.

The TPU build's analog of the reference's ``test/parallel`` suite run
under ``mpirun -np 2`` (reference: Dockerfile.test.cpu:86): real
processes, real TCP collectives, no mocks (SURVEY.md §4 notes the
reference never fakes the communication backend).
"""

import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _ensure_tsan_core():
    """Build the TSAN-instrumented core BEFORE any libtsan-preloaded
    worker launches: forking the compiler from a preloaded process
    deadlocks silently (core/build.py refuses that combo for the same
    reason), so the build must happen here, preload-free."""
    env = dict(os.environ, HVD_CORE_SANITIZE="thread")
    env.pop("LD_PRELOAD", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "from horovod_tpu.core.build import library_path; "
         "library_path(build_if_missing=True)"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr


def _launch(np_, script, extra_env=None, timeout=180):
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
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            # Workers must not claim a real TPU.
            "JAX_PLATFORMS": "cpu",
        })
        if extra_env:
            env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outputs = []
    codes = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outputs.append(out)
        codes.append(p.returncode)
    return codes, outputs


@pytest.mark.parametrize("np_", [2, 3])
def test_native_collectives(np_):
    codes, outputs = _launch(
        np_, os.path.join(_REPO, "tests", "native_worker.py"))
    for r, (c, out) in enumerate(zip(codes, outputs)):
        assert c == 0, "rank %d failed:\n%s" % (r, out)


def test_dtype_op_matrix():
    """Exhaustive dtype x op collective matrix + shape-mismatch error
    (reference discipline: test/parallel/test_torch.py matrices)."""
    codes, outputs = _launch(2, os.path.join(_REPO, "tests",
                                             "dtype_matrix_worker.py"))
    assert codes == [0, 0], "\n".join(outputs)
    assert sum("DTYPE_MATRIX_OK" in o for o in outputs) == 2


def test_cache_eviction_under_tiny_capacity():
    """12 live names vs capacity 4: constant LRU eviction +
    renegotiation must stay exact and never wedge."""
    codes, outputs = _launch(
        2, os.path.join(_REPO, "tests", "cache_evict_worker.py"),
        extra_env={"HOROVOD_CACHE_CAPACITY": "4"})
    assert codes == [0, 0], "\n".join(outputs)
    assert sum("CACHE_EVICT_OK" in o for o in outputs) == 2


@pytest.mark.tier2
@pytest.mark.slow
def test_native_collectives_np8():
    """np=8 native control+data plane (VERDICT r2 #8): the same
    rank-generic matrix as np=2/3, at the widest world this host
    runs."""
    codes, outputs = _launch(
        8, os.path.join(_REPO, "tests", "native_worker.py"), timeout=300)
    assert codes == [0] * 8, "\n".join(outputs)
    assert sum("native worker rank %d OK" % k in "".join(outputs) for k in range(8)) == 8


@pytest.mark.tier2
def test_negotiation_scale_2k_tensors():
    """~2k uniquely named tensors through negotiation: bounded wall
    time cold, and the response-cache steady state no slower
    (quantifies the O(log n) LRU + fusion claims, VERDICT r2 #8)."""
    codes, outputs = _launch(
        2, os.path.join(_REPO, "tests", "negotiation_scale_worker.py"),
        timeout=240)
    assert codes == [0, 0], "\n".join(outputs)
    assert sum("NEGOTIATION_SCALE_OK" in o for o in outputs) == 2


@pytest.mark.tier2
def test_native_core_under_tsan():
    """np=2 collective matrix on a ThreadSanitizer-instrumented core:
    the background-thread/controller concurrency must produce ZERO race
    reports. The reference ships no sanitizer integration (SURVEY.md
    §5.2 — thread-safety by design only); this verifies it mechanically.
    """
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
        _REPO, "horovod_tpu", "core", "build-thread", "tsan_report")
    for old in glob.glob(report_prefix + "*"):
        os.unlink(old)
    codes, outputs = _launch(
        2, os.path.join(_REPO, "tests", "native_worker.py"),
        extra_env={
            "HVD_CORE_SANITIZE": "thread",
            "LD_PRELOAD": libtsan,
            # exitcode=66 turns any race report into a rank failure;
            # thread-leak checking off (python's own threads).
            "TSAN_OPTIONS": "report_thread_leaks=0 exitcode=66 "
                            "log_path=%s" % report_prefix,
        }, timeout=300)
    reports = glob.glob(report_prefix + "*")
    blobs = "".join(open(p).read() for p in reports)
    assert codes == [0, 0] and not reports, (
        "TSAN reports:\n%s\nworker output:\n%s"
        % (blobs[:4000], "\n".join(outputs)[-2000:]))


@pytest.mark.tier2
@pytest.mark.slow
def test_process_sets_np4():
    """Concurrent disjoint process sets at np=4 (reference:
    test_process_sets_static.py discipline)."""
    codes, outputs = _launch(
        4, os.path.join(_REPO, "tests", "process_sets_worker.py"))
    assert codes == [0, 0, 0, 0], "\n".join(outputs)
    assert sum("PROCESS_SETS_OK" in o for o in outputs) == 4
