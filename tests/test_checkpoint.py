"""TPU-native orbax checkpointing (utils/checkpoint.py)."""

import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkpointer_single_process(tmp_path):
    import jax.numpy as jnp

    from horovod_tpu.common import basics
    from horovod_tpu.utils.checkpoint import Checkpointer

    basics.init()
    ck = Checkpointer(str(tmp_path / "ckpt"), max_to_keep=2)
    ck.save(1, {"w": jnp.arange(4.0)})
    ck.save(2, {"w": jnp.arange(4.0) * 2})
    ck.save(3, {"w": jnp.arange(4.0) * 3})
    # max_to_keep=2 garbage-collects step 1.
    assert ck.all_steps() == [2, 3]
    out = ck.restore()
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0) * 3)
    out = ck.restore(step=2)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0) * 2)
    with pytest.raises(Exception):
        ck.restore(step=99)
    ck.close()


def test_checkpointer_restore_empty(tmp_path):
    from horovod_tpu.common import basics
    from horovod_tpu.utils.checkpoint import Checkpointer

    basics.init()
    ck = Checkpointer(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        ck.restore()
    ck.close()


def test_failed_save_still_runs_completion_barrier(tmp_path, monkeypatch):
    """A rank-0 write failure must not skip the completion barrier the
    other ranks are already blocked in — rank 0 sailing past it would
    desynchronize the world's collective sequence. The error surfaces
    only after the barrier."""
    from horovod_tpu.common import basics
    from horovod_tpu.utils.checkpoint import Checkpointer

    basics.init()
    ck = Checkpointer(str(tmp_path / "boom"))
    real_manager = ck._manager

    class _Boom:
        def save(self, *a, **k):
            raise IOError("disk full")

    events = []
    monkeypatch.setattr(ck, "_manager", _Boom())
    monkeypatch.setattr(ck, "_barrier",
                        lambda: events.append("barrier"))
    with pytest.raises(IOError, match="disk full"):
        ck.save(5, {"w": np.arange(2.0)})
    assert events == ["barrier"]
    real_manager.close()


def test_checkpointer_np2(tmp_path):
    """Rank-0 write + barrier + collective restore across 2 processes."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": "2",
            "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": "2",
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
            "HOROVOD_CONTROLLER_PORT": str(port),
            "JAX_PLATFORMS": "cpu",
            "HVD_TEST_CKPT_DIR": str(tmp_path / "shared"),
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable,
             os.path.join(_REPO, "tests", "ckpt_worker.py")],
            env=env, cwd=_REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out)
    assert [p.returncode for p in procs] == [0, 0], "\n".join(outs)
    assert sum("CKPT_OK" in o for o in outs) == 2
