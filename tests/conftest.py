"""Test fixtures: run everything on an 8-device virtual CPU mesh.

This is the TPU build's "multi-node without a cluster" technique (SURVEY.md
§4): ``xla_force_host_platform_device_count`` gives N XLA devices in one
process so mesh/sharding/collective code paths compile and execute exactly
as they would across a pod, minus the physical interconnect.
"""

import os

# Must be set before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache, shared by this process AND every worker
# subprocess the tests spawn (they inherit os.environ): identical XLA
# programs (models, collectives, examples) compile once per machine
# instead of once per process. Measured: heavyweight compile tests run
# ~2x faster warm; the whole suite fits the CI budget.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import jax  # noqa: E402

from horovod_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

# The virtual devices above exist only on the cpu platform, so the suite
# selects it even on a host that has an accelerator and no JAX_PLATFORMS.
if os.environ.get("HVD_TPU_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd


@pytest.fixture
def mesh8():
    import jax
    from horovod_tpu.parallel import make_mesh, set_global_mesh

    assert jax.device_count() == 8, "expected 8 virtual devices"
    mesh = make_mesh({"data": 8})
    set_global_mesh(mesh)
    yield mesh
    set_global_mesh(None)
