"""Shared np=2 worker launcher for the binding matrix/sweep tests."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def launch(worker, extra_env=None, timeout=300, np=2):
    """Spawn ``tests/<worker>`` under the runner, pinned to the CPU
    backend whatever the host has."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np),
         sys.executable, os.path.join(_REPO, "tests", worker)],
        cwd=_REPO, env=env, capture_output=True, text=True,
        timeout=timeout)
