"""The benchmark's own tests run on the CPU, from any directory:

    python -m pytest benchmark/tests -q

on eight virtual devices, as tier-1 runs them through ``tests/``
(``tests/conftest.py``): a test of two replicas needs two.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Must be set before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
