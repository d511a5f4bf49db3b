"""Controller selection, per-slot env construction, and the
programmatic ``run()`` API.

Reference pattern: test/single/test_run.py — run_controller selection
given the backend flags, gloo_run slot env construction, and
``horovod.run`` results ordering. Single-process with the launch
backends mocked; the one real np=2 cell is the programmatic run().
"""

import os

import pytest

from horovod_tpu.runner import launch
from horovod_tpu.runner.hosts import get_host_assignments, parse_hosts


def _select(monkeypatch, argv):
    """Run run_commandline with every backend mocked; return which one
    was chosen."""
    chosen = []

    monkeypatch.setattr(launch, "_run_static",
                        lambda a: chosen.append("static") or 0)
    monkeypatch.setattr(launch, "_run_mpi",
                        lambda a: chosen.append("mpi") or 0)
    monkeypatch.setattr(launch, "_run_jsrun",
                        lambda a: chosen.append("jsrun") or 0)
    import horovod_tpu.runner.elastic_run as elastic_run

    monkeypatch.setattr(elastic_run, "run_elastic",
                        lambda a: chosen.append("elastic") or 0)
    rc = launch.run_commandline(argv)
    assert rc == 0
    assert len(chosen) == 1, chosen
    return chosen[0]


@pytest.mark.parametrize("argv,expect", [
    (["-np", "2", "python", "x.py"], "static"),
    (["-np", "2", "--use-gloo", "python", "x.py"], "static"),
    (["-np", "2", "--use-mpi", "python", "x.py"], "mpi"),
    (["-np", "2", "--use-jsrun", "python", "x.py"], "jsrun"),
    (["-np", "2", "--min-np", "2", "--max-np", "4",
      "--host-discovery-script", "./d.sh", "python", "x.py"], "elastic"),
    # Elastic flags outrank an explicit backend choice (the elastic
    # driver owns worker placement; reference: launch.py elastic
    # branch precedes the gloo/mpi split).
    (["-np", "2", "--use-mpi", "--min-np", "2",
      "--host-discovery-script", "./d.sh", "python", "x.py"], "elastic"),
])
def test_controller_selection(monkeypatch, argv, expect):
    assert _select(monkeypatch, argv) == expect


def test_backend_flags_mutually_exclusive():
    with pytest.raises(ValueError):
        launch.run_commandline(
            ["-np", "2", "--use-gloo", "--use-mpi", "python", "x.py"])


def test_slot_env_two_host_topology():
    """gloo_run-equivalent slot env (reference: gloo_run.py:65-76):
    rank/local/cross coordinates for a 2x2 layout plus the rendezvous
    coordinates and the platform pin."""
    hosts = parse_hosts("h1:2,h2:2")
    assignments = get_host_assignments(hosts, min_np=4)
    by_rank = {a.rank: a for a in assignments}
    envs = {
        r: launch.slot_env(a, "1.2.3.4", 4321, "1.2.3.4", 9876,
                           extra={"X_EXTRA": "y"})
        for r, a in by_rank.items()
    }
    # Rank 2 is the first slot of the second host.
    e = envs[2]
    assert e["HOROVOD_RANK"] == "2"
    assert e["HOROVOD_SIZE"] == "4"
    assert e["HOROVOD_LOCAL_RANK"] == "0"
    assert e["HOROVOD_LOCAL_SIZE"] == "2"
    assert e["HOROVOD_CROSS_RANK"] == "1"   # second host
    assert e["HOROVOD_CROSS_SIZE"] == "2"
    assert e["HOROVOD_HOSTNAME"] == "h2"
    assert e["HOROVOD_CONTROLLER_ADDR"] == "1.2.3.4"
    assert e["HOROVOD_CONTROLLER_PORT"] == "4321"
    assert e["HOROVOD_RENDEZVOUS_PORT"] == "9876"
    assert e["X_EXTRA"] == "y"
    # The default platform pins every worker to the CPU backend and
    # hands out no chip.
    assert e["JAX_PLATFORMS"] == "cpu"
    assert not [k for k in e if k.startswith("TPU_")]
    # Workers inherit the launcher's cwd on sys.path.
    assert os.getcwd() in e["PYTHONPATH"].split(os.pathsep)
    # Local ranks differ within a host, ranks are globally unique.
    assert envs[0]["HOROVOD_LOCAL_RANK"] == "0"
    assert envs[1]["HOROVOD_LOCAL_RANK"] == "1"
    assert len({e["HOROVOD_RANK"] for e in envs.values()}) == 4


def test_tpu_slots_sharing_a_host_get_one_chip_each():
    """--platform tpu: a chip belongs to one process, so each slot of a
    shared host is shown its own chip (the local_rank-th) as a 1x1x1
    topology; a worker that cannot reach it fails instead of falling
    back to the CPU."""
    hosts = parse_hosts("h1:2,h2:2")
    envs = {
        a.rank: launch.slot_env(a, "1.2.3.4", 4321, "1.2.3.4", 9876,
                                extra={}, platform="tpu")
        for a in get_host_assignments(hosts, min_np=4)
    }
    assert [envs[r]["TPU_VISIBLE_CHIPS"] for r in range(4)] == \
        ["0", "1", "0", "1"]
    for e in envs.values():
        # cpu stays listed: io_callback needs a local CPU device.
        assert e["JAX_PLATFORMS"] == "tpu,cpu"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_lone_tpu_slot_owns_its_hosts_chips():
    """One slot per host: the worker keeps the inherited TPU
    environment and drives every chip of its host."""
    assert launch.worker_platform_env("tpu", local_rank=0, local_size=1) \
        == {"JAX_PLATFORMS": "tpu,cpu"}
    assert launch.worker_platform_env() == {"JAX_PLATFORMS": "cpu"}


def test_mpirun_cannot_hand_out_chips_per_slot():
    """mpirun/jsrun start every rank with one environment, so several
    TPU slots on a host are refused at launch with the reason."""
    with pytest.raises(ValueError, match="each slot must be shown its "
                       "own chip"):
        launch.run_commandline(["-np", "2", "--use-mpi", "--platform",
                                "tpu", "python", "x.py"])


def test_programmatic_run_results_ordering():
    """horovod_tpu.runner.run returns per-rank results in rank order
    (reference: horovod/runner/__init__.py horovod.run contract)."""
    import horovod_tpu.runner as runner

    # Closure, not a module-level function: cloudpickle must carry it
    # by value (the workers don't have tests/ on sys.path).
    def rank_payload(tag):
        import os

        return (int(os.environ["HOROVOD_RANK"]), tag)

    results = runner.run(rank_payload, args=("tag",), np=2)
    assert results == [(0, "tag"), (1, "tag")]
