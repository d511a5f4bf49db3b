"""Stall-inspector enforcement: no rank may hang on a dead/diverged peer.

Verdict-driven coverage (reference: horovod/common/stall_inspector.h:41-80
stall shutdown; stall_inspector.cc InvalidateStalledCachedTensors): one
rank misbehaves in (a) the negotiation phase — alive but never submits —
and (b) the execution phase — dies with a collective in flight; the
remaining ranks must error out within the stall window in both cases.
"""

import os

import pytest

from tests.test_native_core import _launch

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "stall_worker.py")


def test_stall_shutdown_negotiation_phase():
    """Rank 2 never submits; ranks 0-1 get an error within the stall
    shutdown window (enforcement, not just the 60s warning)."""
    codes, outputs = _launch(
        3, _WORKER,
        extra_env={
            "STALL_MODE": "negotiation",
            "STALL_EXPECT_WINDOW": "30",
            "STALL_SLEEP": "8",
            "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "2",
        },
        timeout=120)
    for r in (0, 1):
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK got error" in outputs[r], outputs[r]
    # The diverged rank's own late submit fails fast on the dead core.
    assert codes[2] == 0, "rank 2:\n%s" % outputs[2]


def test_stalled_cache_entry_invalidation():
    """A tensor already in the response cache stalls (one rank stops
    submitting it): the coordinated invalidation erases the entry,
    renegotiates through the slow path, and the stall shutdown fails the
    healthy ranks within the window."""
    codes, outputs = _launch(
        3, _WORKER,
        extra_env={
            "STALL_MODE": "cached",
            "STALL_EXPECT_WINDOW": "30",
            "STALL_SLEEP": "8",
            "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "2",
        },
        timeout=120)
    for r in (0, 1):
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK got error" in outputs[r], outputs[r]
    assert codes[2] == 0, "rank 2:\n%s" % outputs[2]


def test_abort_cascade_execution_phase():
    """Rank 2 dies with a 4 MB allreduce in flight; survivors error out
    promptly through the connection-abort cascade instead of blocking in
    the ring. A process that dies with unread bytes in its socket sends
    RST where an idle one sends FIN, and a reset link is first redialled
    for ``HVD_WIRE_RECONNECT_SEC`` (docs/wire.md#reconnect) before the
    typed abort: the window holds that budget, given here, and slack."""
    codes, outputs = _launch(
        3, _WORKER,
        extra_env={
            "STALL_MODE": "execution",
            "STALL_EXPECT_WINDOW": "30",
            "HVD_WIRE_RECONNECT_SEC": "5",
        },
        timeout=120)
    for r in (0, 1):
        assert codes[r] == 0, "rank %d:\n%s" % (r, outputs[r])
        assert "OK got error" in outputs[r], outputs[r]
    assert codes[2] == 19, "rank 2 should have hard-exited:\n%s" % outputs[2]
