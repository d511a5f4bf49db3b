"""Ray integration: placement bundle math, discovery adapters, gating."""

import pytest

import fake_ray

from horovod_tpu.ray.strategy import (
    ColocatedStrategy, PackStrategy, bundles_for, resources_per_bundle,
)
from horovod_tpu.ray.elastic import ElasticRayExecutor, StaticHostDiscovery
from horovod_tpu.runner.discovery import HostManager


@pytest.fixture
def ray_fake():
    fake_ray.install()
    yield
    fake_ray.uninstall()


def test_resources_per_bundle():
    assert resources_per_bundle(2, 0, 4) == {"CPU": 8}
    assert resources_per_bundle(1, 2, 4) == {"CPU": 4, "GPU": 8}


def test_bundles_colocated():
    bundles, strategy = bundles_for(8, workers_per_host=4,
                                    cpus_per_worker=2)
    assert strategy == "STRICT_SPREAD"
    assert bundles == [{"CPU": 8}, {"CPU": 8}]
    with pytest.raises(ValueError):
        bundles_for(7, workers_per_host=4)


def test_bundles_pack():
    bundles, strategy = bundles_for(3, None, cpus_per_worker=1,
                                    gpus_per_worker=1)
    assert strategy == "PACK"
    assert bundles == [{"CPU": 1, "GPU": 1}] * 3


def test_strategy_worker_counts():
    s = ColocatedStrategy(num_hosts=2, num_workers_per_host=4)
    assert s.num_workers == 8
    p = PackStrategy(num_workers=5)
    assert p.num_workers == 5


def test_static_discovery_feeds_host_manager():
    disc = StaticHostDiscovery({"hostB": 2, "hostA": 4})
    mgr = HostManager(disc)
    assert mgr.refresh() is True
    assert mgr.available_slot_keys() == [
        "hostA:0", "hostA:1", "hostA:2", "hostA:3",
        "hostB:0", "hostB:1"]
    mgr.blacklist_slot("hostA:2")
    assert "hostA:2" not in mgr.available_slot_keys()
    assert mgr.refresh() is False  # unchanged


class _RecordingDiscovery:
    """Host map as a schedule over discovery calls (the fake-cluster
    analog of the reference's discovery-script schedules,
    test/integration/elastic_common.py:42-66)."""

    def __init__(self, schedule):
        self.schedule = list(schedule)
        self.calls = 0

    def find_available_hosts_and_slots(self):
        hosts = self.schedule[min(self.calls, len(self.schedule) - 1)]
        self.calls += 1
        return dict(hosts)

    def find_available_hosts(self):
        from horovod_tpu.runner.hosts import HostInfo

        return [HostInfo(h, s) for h, s in sorted(
            self.find_available_hosts_and_slots().items())]


def _die_if_world_of_one():
    """Actor-side fn: simulate node loss at world size 1, succeed at 2."""
    import os

    if os.environ.get("HOROVOD_SIZE") == "1":
        os._exit(1)  # hard actor death, like a lost node
    return (int(os.environ["HOROVOD_RANK"]),
            int(os.environ["HOROVOD_SIZE"]))


def _always_die():
    import os

    os._exit(1)


def test_ray_elastic_grows_after_actor_loss(ray_fake):
    """Reference behavior (ray/elastic.py): an actor death tears the
    world down, discovery reports the (now larger) cluster, and the
    retry runs at the new size."""
    disc = _RecordingDiscovery([{"localhost": 1}, {"localhost": 2}])
    ex = ElasticRayExecutor(min_np=1, max_np=4, discovery=disc,
                            env_vars={"JAX_PLATFORMS": "cpu"})
    results = ex.run(_die_if_world_of_one)
    assert sorted(results) == [(0, 2), (1, 2)]
    assert disc.calls == 2  # one failed world + one grown world


def test_ray_elastic_reset_limit_bounds_retries(ray_fake):
    """Permanent failure: the executor retries exactly reset_limit
    times, re-discovering each attempt, then surfaces the actor error
    (reference: reset_limit semantics, registration.py:28-160)."""
    import ray

    disc = _RecordingDiscovery([{"localhost": 1}])
    ex = ElasticRayExecutor(min_np=1, discovery=disc, reset_limit=2,
                            env_vars={"JAX_PLATFORMS": "cpu"})
    with pytest.raises(ray.exceptions.RayActorError):
        ex.run(_always_die)
    assert disc.calls == 3  # initial attempt + 2 permitted resets


def test_ray_elastic_app_error_fails_fast(ray_fake):
    """An exception RAISED by the training fn is an application bug:
    no world reset, it propagates on the first attempt (reference:
    ray/elastic.py separates task errors from actor loss)."""
    import ray

    disc = _RecordingDiscovery([{"localhost": 2}])
    ex = ElasticRayExecutor(min_np=1, discovery=disc,
                            env_vars={"JAX_PLATFORMS": "cpu"})

    def boom():
        raise ValueError("bad hyperparameter")

    with pytest.raises(ray.exceptions.RayTaskError):
        ex.run(boom)
    assert disc.calls == 1


def test_elastic_executor_validates_min_np(monkeypatch):
    ex = ElasticRayExecutor(min_np=8,
                            discovery=StaticHostDiscovery({"h": 2}))
    # start() requires ray; run() with too few slots must raise before
    # touching ray actors.
    ex.discovery = StaticHostDiscovery({"h": 2})
    with pytest.raises((RuntimeError, ImportError)):
        ex.run(lambda: None)


def test_ray_executor_requires_ray():
    try:
        import ray  # noqa: F401

        pytest.skip("ray is installed; gating path not reachable")
    except ImportError:
        pass
    import horovod_tpu.ray as hvd_ray

    ex = hvd_ray.RayExecutor(num_workers=2)
    with pytest.raises(ImportError):
        ex.start()


def test_assign_topology_multi_host():
    from horovod_tpu.ray.utils import assign_topology

    # Actors interleaved across hosts A,B,A,B: ranks must pack by host.
    envs = assign_topology(["A", "B", "A", "B"])
    assert [e["HOROVOD_HOSTNAME"] for e in envs] == ["A", "A", "B", "B"]
    assert [e["HOROVOD_RANK"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["HOROVOD_LOCAL_RANK"] for e in envs] == ["0", "1", "0", "1"]
    assert all(e["HOROVOD_LOCAL_SIZE"] == "2" for e in envs)
    assert [e["HOROVOD_CROSS_RANK"] for e in envs] == ["0", "0", "1", "1"]
    assert all(e["HOROVOD_CROSS_SIZE"] == "2" for e in envs)
    # Uneven: 3 slots on A, 1 on B.
    envs = assign_topology(["A", "A", "B", "A"])
    by_rank = {int(e["HOROVOD_RANK"]): e for e in envs}
    assert by_rank[3]["HOROVOD_HOSTNAME"] == "B"
    # local_rank 2 exists only on A -> cross_size 1 for that slot.
    assert by_rank[2]["HOROVOD_CROSS_SIZE"] == "1"
