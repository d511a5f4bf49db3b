"""On-demand build of the native core shared library.

Analog of the reference's CMake-driven extension build
(reference: CMakeLists.txt, setup.py:35-120), scoped to the coordination
core: a single `make` producing ``libhvdcore.so``, rebuilt when any
source is newer than the library. Guarded by an inter-process file lock so
concurrent ranks don't race the compiler.
"""

from __future__ import annotations

import fcntl
import os
import subprocess
from typing import Optional

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")


def _sanitize_mode() -> str:
    """``HVD_CORE_SANITIZE=thread`` builds/loads a TSAN-instrumented
    core — race detection for the background-thread/controller
    concurrency. Beyond the reference, which ships no sanitizer
    integration (SURVEY.md §5.2). Workers must ``LD_PRELOAD`` libtsan
    so the runtime initializes before the uninstrumented python binary
    loads the library."""
    return os.environ.get("HVD_CORE_SANITIZE", "").strip()


def _build_dir() -> str:
    mode = _sanitize_mode()
    suffix = "-" + mode if mode else ""
    return os.path.join(os.path.dirname(__file__), "build" + suffix)


def _lib_path() -> str:
    return os.path.join(_build_dir(), "libhvdcore.so")


def _needs_build() -> bool:
    lib = _lib_path()
    if not os.path.exists(lib):
        return True
    lib_mtime = os.path.getmtime(lib)
    for fn in os.listdir(_SRC_DIR):
        if fn.endswith((".cc", ".h", "Makefile")):
            if os.path.getmtime(os.path.join(_SRC_DIR, fn)) > lib_mtime:
                return True
    return False


def library_path(build_if_missing: bool = True) -> Optional[str]:
    """Path to libhvdcore.so, building it if needed. Returns None when the
    library is absent and ``build_if_missing`` is False."""
    if not _needs_build():
        return _lib_path()
    if not build_if_missing:
        return None
    preload = os.environ.get("LD_PRELOAD", "")
    loaded = [rt for rt in ("libtsan", "libasan", "libubsan")
              if rt in preload]
    if loaded:
        # Forking the compiler from a sanitizer-preloaded process is
        # unsafe: libtsan deadlocks outright, and the others inject
        # their runtime into every make/g++ child. Surfacing the rule
        # beats a hung CI lane: build first (make tsan/asan/ubsan),
        # then launch the instrumented workers.
        raise RuntimeError(
            "refusing to build the native core under an LD_PRELOADed "
            "%s; pre-build it without the preload first: "
            "make -C horovod_tpu/core/src tsan|asan|ubsan"
            % "/".join(loaded))
    build_dir = _build_dir()
    os.makedirs(build_dir, exist_ok=True)
    lock_path = os.path.join(build_dir, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if _needs_build():
                cmd = ["make", "-C", _SRC_DIR, "-j2",
                       "BUILDDIR=" + build_dir]
                if _sanitize_mode():
                    cmd.append("SANITIZE=" + _sanitize_mode())
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
        except FileNotFoundError as e:
            raise RuntimeError(
                "Failed to build horovod_tpu native core: it needs make "
                "and g++ on PATH (%s)" % e) from e
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                "Failed to build horovod_tpu native core:\n" + e.stderr
            ) from e
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return _lib_path()
