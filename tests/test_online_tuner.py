"""Online tuner (Autotune 2.0) tier-1 units: injected clock +
synthetic metrics source — no threads, no sleeping, no jax.

The full loop under test (docs/autotune.md): observe windows ->
propose (BayesianOptimizer) -> apply through the schema's apply path
-> A/B guardrail (revert past the noise band) -> journal through
runner/journal.py -> a replayed process resumes the tuned state, a
stale-version journal is fenced off.
"""

import json
import os

import pytest

from horovod_tpu.common.knobs import TUNABLE, TunableKnob, tunable_snap
from horovod_tpu.runner.journal import DriverJournal
from horovod_tpu.serve.batching import MicroBatcher
from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils import online_tuner as ot

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every apply() mirrors the value into the backing env knob — exactly
# what a later test would then read back as its starting point. Scrub
# the mirrors (and the tuner's own knobs) around every test.
# The tests' own specimen of a live-unsafe, env-bound tunable (a
# trace-time read: 0 to 64 MiB in 1 MiB steps, default 4 MiB), put into
# the schema by the ``specimen`` fixture, so that no option's removal
# touches these tests.
SPECIMEN = TunableKnob("specimen_bytes", 0.0, float(64 << 20),
                       float(1 << 20), "env", "TUNER_TEST_SPECIMEN_BYTES",
                       float(4 << 20), False, "trace-time read")

_TUNER_ENVS = sorted({k.env for k in TUNABLE.values() if k.env} | {
    SPECIMEN.env, "HVD_TUNE", "HVD_TUNE_FREEZE", "HVD_TUNE_JOURNAL_DIR",
    "HVD_TUNE_WINDOW_SEC", "HVD_TUNE_GUARD_PCT"})


@pytest.fixture(autouse=True)
def _clean_tuner_env():
    saved = {n: os.environ.pop(n) for n in _TUNER_ENVS
             if n in os.environ}
    yield
    for n in _TUNER_ENVS:
        os.environ.pop(n, None)
    os.environ.update(saved)


@pytest.fixture
def specimen(monkeypatch):
    monkeypatch.setitem(TUNABLE, SPECIMEN.name, SPECIMEN)
    return SPECIMEN


class Sim:
    """Fake clock + synthetic objective: a monotone counter whose rate
    is a smooth function of the current knob values, integrated over
    fake time by ``wait`` — the tuner's injected clock/wait/objective
    triple."""

    def __init__(self, rate_fn):
        self.t = 0.0
        self.total = 0.0
        self.values = {}
        self._rate_fn = rate_fn

    def rate(self):
        return self._rate_fn(self.values)

    def wait(self, seconds):
        self.total += self.rate() * seconds
        self.t += seconds
        return False

    def clock(self):
        return self.t

    def objective(self):
        return self.total

    def binding(self, name):
        self.values.setdefault(name, TUNABLE[name].default)
        return ot.KnobBinding(
            TUNABLE[name],
            setter=lambda v, _n=name: self.values.__setitem__(_n, v))


def _peaked_rate(values):
    """Planted optimum: ring_chunk=4 MiB, socket_buf=2 MiB."""
    rc = values.get("ring_chunk_bytes", 0.0)
    sb = values.get("socket_buf_bytes", 0.0)
    return 1e6 * (1.0
                  - ((rc - (4 << 20)) / float(16 << 20)) ** 2
                  - ((sb - (2 << 20)) / float(16 << 20)) ** 2)


def _make_tuner(sim, names, journal_path=None, **kw):
    kw.setdefault("window_sec", 1.0)
    kw.setdefault("guard_pct", 5.0)
    kw.setdefault("max_samples", 12)
    return ot.OnlineTuner([sim.binding(n) for n in names], sim.objective,
                          journal_path=journal_path, clock=sim.clock,
                          wait=sim.wait, **kw)


def _drive(tuner):
    records = []
    while True:
        rec = tuner.step()
        if rec is None:
            return records
        records.append(rec)


# --- schema -----------------------------------------------------------------


def test_schema_covers_required_surface():
    """ISSUE 11 floor: the schema must declare at least the PR 6-8
    knob surface plus the reference pair."""
    required = {"fusion_threshold_mb", "cycle_time_ms",
                "ring_chunk_bytes", "socket_buf_bytes",
                "serve_max_batch", "serve_deadline_ms"}
    assert required <= set(TUNABLE)
    for knob in TUNABLE.values():
        assert knob.lo <= knob.hi
        assert knob.apply_path in ("native", "env", "setter")


def test_schema_trace_time_knobs_are_not_live_safe():
    """Trace-time reads lower rank-divergent programs: the schema must
    say so, and the default training set must exclude them."""
    assert not TUNABLE["plan_grad_overlap"].live_safe
    for name in ot.TRAINING_KNOBS:
        assert TUNABLE[name].live_safe


def test_tunable_snap_clamps_and_grids():
    k = TUNABLE["ring_chunk_bytes"]
    assert tunable_snap(k, -5.0) == k.lo
    assert tunable_snap(k, 1e12) == k.hi
    v = tunable_snap(k, (1 << 20) + 1000.0)
    assert (v - k.lo) % k.step == 0


def test_env_mirror_and_fusion_byte_convention(monkeypatch):
    monkeypatch.delenv("HVD_RING_CHUNK_BYTES", raising=False)
    b = ot.KnobBinding(TUNABLE["ring_chunk_bytes"],
                       setter=lambda v: None)
    b.apply(2 << 20)
    assert os.environ["HVD_RING_CHUNK_BYTES"] == str(2 << 20)
    # The 0-MB fusion endpoint means "unfused", spelled as a 1-byte
    # threshold downstream (<=0 is "no update") — same convention as
    # utils/autotune._apply.
    fb = ot.KnobBinding(TUNABLE["fusion_threshold_mb"],
                        setter=lambda v: None)
    fb.apply(0.0)
    assert os.environ["HOROVOD_FUSION_THRESHOLD"] == "1"
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
    monkeypatch.delenv("HVD_RING_CHUNK_BYTES", raising=False)


def test_frozen_knob_names_ignores_unknown(monkeypatch):
    monkeypatch.setenv("HVD_TUNE_FREEZE",
                       "ring_chunk_bytes, no_such_knob ,")
    assert ot.frozen_knob_names() == ["ring_chunk_bytes"]


def test_tune_mode_parsing(monkeypatch):
    for raw, want in [("", ""), ("0", ""), ("off", ""), ("false", ""),
                      ("1", "1"), ("yes", "1"), ("cache", "cache"),
                      ("CACHE", "cache")]:
        monkeypatch.setenv("HVD_TUNE", raw)
        assert ot.tune_mode() == want, raw


# --- the loop ---------------------------------------------------------------


def test_convergence_on_planted_optimum(tmp_path):
    """(a) With a smooth synthetic objective peaked inside the box,
    the search lands within one step-grid neighborhood of the planted
    optimum within max_samples windows and freezes there."""
    sim = Sim(_peaked_rate)
    tuner = _make_tuner(sim, ["ring_chunk_bytes", "socket_buf_bytes"],
                        journal_path=str(tmp_path / "j.jsonl"))
    tuner.start  # not started: tests drive step() directly
    tuner._attach_journal()
    tuner.replay()
    records = _drive(tuner)
    state = tuner.state()
    assert state["frozen"]
    assert state["samples"] == 12
    # Within 1 MiB of the 4 MiB / 2 MiB planted peak — far tighter
    # than the 16 MiB box, i.e. the search genuinely localized it.
    assert abs(state["values"]["ring_chunk_bytes"] - (4 << 20)) <= (1 << 20)
    assert abs(state["values"]["socket_buf_bytes"] - (2 << 20)) <= (1 << 20)
    assert any(r["type"] == "tune_freeze" for r in records)
    # The sim actually RAN at the applied values (setter apply path).
    assert sim.values["ring_chunk_bytes"] == \
        state["values"]["ring_chunk_bytes"]


def test_guardrail_reverts_injected_regression(tmp_path):
    """(b) An objective that collapses whenever the knob leaves its
    default makes every proposed move regress: the guardrail must
    revert each one and the knob must end exactly where it started."""
    default = TUNABLE["ring_chunk_bytes"].default

    def cliff(values):
        return 1e6 if values.get("ring_chunk_bytes") == default else 1e3

    sim = Sim(cliff)
    tuner = _make_tuner(sim, ["ring_chunk_bytes"],
                        journal_path=str(tmp_path / "j.jsonl"),
                        max_samples=6)
    tuner._attach_journal()
    tuner.replay()
    records = _drive(tuner)
    reverts = [r for r in records if r["type"] == "tune_revert"]
    assert reverts, "no move was ever reverted"
    for r in reverts:
        # The revert restored the incumbent and recorded the loss.
        assert r["values"]["ring_chunk_bytes"] == default
        assert r["applied"]["ring_chunk_bytes"] != default
        assert r["objective"] < r["threshold"]
    # Freeze lands back on the default — the only good point seen.
    assert tuner.state()["values"]["ring_chunk_bytes"] == default
    assert sim.values["ring_chunk_bytes"] == default


def test_idle_objective_never_searches(tmp_path):
    """A zero objective (no traffic yet, counter not wired) must not
    trigger moves: with o0 = 0 the guard is trivially passable and the
    'search' would be a random walk. The tuner keeps measuring and
    journals nothing."""
    sim = Sim(lambda values: 0.0)
    jp = str(tmp_path / "j.jsonl")
    tuner = _make_tuner(sim, ["ring_chunk_bytes"], journal_path=jp,
                        max_samples=4)
    tuner._attach_journal()
    tuner.replay()
    for _ in range(3):
        rec = tuner.step()
        assert rec["type"] == "tune_idle"
    # Consecutive idle windows coalesce into ONE trajectory record
    # (unbounded growth guard for long-idle replicas).
    idles = [r for r in tuner.trajectory() if r["type"] == "tune_idle"]
    assert len(idles) == 1 and idles[0]["windows"] == 3
    assert tuner.state()["samples"] == 0
    assert not tuner.state()["frozen"]
    assert sim.values["ring_chunk_bytes"] == \
        TUNABLE["ring_chunk_bytes"].default
    types = {json.loads(l)["type"] for l in open(jp)}
    assert types == {"tune_meta"}  # idle windows are not journaled


def test_guard_band_absorbs_noise_within_pct(tmp_path):
    """A post-apply rate inside the guard band (smaller than
    HVD_TUNE_GUARD_PCT) is NOT a revert — the band exists so
    measurement jitter does not thrash knobs."""
    state = {"phase": 0}

    def wobble(values):
        # 2% down after any move: inside the 5% band.
        return 1e6 * (0.98 if values.get("ring_chunk_bytes")
                      != TUNABLE["ring_chunk_bytes"].default else 1.0)

    sim = Sim(wobble)
    tuner = _make_tuner(sim, ["ring_chunk_bytes"], max_samples=4,
                        guard_pct=5.0)
    records = _drive(tuner)
    assert state["phase"] == 0  # unused; silences lint
    assert not any(r["type"] == "tune_revert" for r in records), records


# --- journal + replay -------------------------------------------------------


def test_journal_records_go_through_driver_journal(tmp_path):
    """The decision log is a DriverJournal product: fsync'd JSONL, one
    record per line, meta first — and replayable by the tuner's fold."""
    sim = Sim(_peaked_rate)
    jp = str(tmp_path / "tuner_journal.test.jsonl")
    tuner = _make_tuner(sim, ["ring_chunk_bytes"], journal_path=jp,
                        max_samples=4)
    tuner._attach_journal()
    tuner.replay()
    _drive(tuner)
    lines = [json.loads(l) for l in open(jp)]
    assert lines[0]["type"] == "tune_meta"
    assert lines[0]["tuner_version"] == ot.TUNER_VERSION
    types = {l["type"] for l in lines}
    assert "tune_apply" in types
    assert "tune_freeze" in types
    # Every apply is journaled BEFORE its guard verdict record.
    for i, rec in enumerate(lines):
        if rec["type"] in ("tune_accept", "tune_revert") \
                and not rec.get("noop"):
            prior = [l["type"] for l in lines[:i]]
            assert "tune_apply" in prior


def test_replay_resumes_tuned_state_without_research(tmp_path):
    """(c) A restarted process folds the journal and adopts the tuned
    values + frozen flag + warm samples instead of re-searching."""
    sim = Sim(_peaked_rate)
    jp = str(tmp_path / "j.jsonl")
    first = _make_tuner(sim, ["ring_chunk_bytes", "socket_buf_bytes"],
                        journal_path=jp)
    first._attach_journal()
    first.replay()
    _drive(first)
    tuned = first.state()["values"]
    before = _metrics.value("hvd_tune_replays_total") or 0.0

    sim2 = Sim(_peaked_rate)
    second = _make_tuner(sim2, ["ring_chunk_bytes", "socket_buf_bytes"],
                         journal_path=jp)
    assert second.replay() is True
    st = second.state()
    assert st["values"] == tuned
    assert st["frozen"]
    assert st["samples"] == 12  # warm optimizer, no cold re-search
    # The replayed values were pushed through the apply path.
    assert sim2.values["ring_chunk_bytes"] == tuned["ring_chunk_bytes"]
    assert (_metrics.value("hvd_tune_replays_total") or 0.0) > before
    # step() on a frozen replayed tuner is a no-op.
    assert second.step() is None


def test_replay_survives_restart_meta_and_torn_tail(tmp_path):
    """A second incarnation's meta record must not discard the fold so
    far, and a torn trailing line ends the fold at the last complete
    record (DriverJournal discipline)."""
    sim = Sim(_peaked_rate)
    jp = str(tmp_path / "j.jsonl")
    t1 = _make_tuner(sim, ["ring_chunk_bytes"], journal_path=jp,
                     max_samples=4)
    t1._attach_journal()
    t1.replay()
    _drive(t1)
    tuned = t1.state()["values"]
    # Simulate the restart appending its own (matching) meta, then a
    # torn tail from a crash mid-append.
    fence = t1.fence
    j = DriverJournal(jp)
    j.append({"type": "tune_meta", "tuner_version": ot.TUNER_VERSION,
              "fence": fence})
    j.close()
    with open(jp, "a") as fh:  # analysis: allow-append — test seeds a torn tail
        fh.write('{"type": "tune_accept", "values": {"ring_chunk_')
    rep = ot.replay_journal(jp, fence)
    assert rep is not None
    assert rep.values == tuned
    assert rep.frozen


def test_stale_version_journal_is_fenced(tmp_path):
    """(c') A journal stamped by a different tuner version or a
    different knob schema must be ignored — cold start, no adoption."""
    sim = Sim(_peaked_rate)
    jp = str(tmp_path / "j.jsonl")
    t1 = _make_tuner(sim, ["ring_chunk_bytes"], journal_path=jp,
                     max_samples=4)
    t1._attach_journal()
    t1.replay()
    _drive(t1)
    t1.stop()

    # Fence 1: version bump.
    raw = open(jp).read().splitlines()
    meta = json.loads(raw[0])
    meta["tuner_version"] = ot.TUNER_VERSION + 1
    with open(jp, "w") as fh:
        fh.write("\n".join([json.dumps(meta)] + raw[1:]) + "\n")
    sim2 = Sim(_peaked_rate)
    t2 = _make_tuner(sim2, ["ring_chunk_bytes"], journal_path=jp,
                     max_samples=4)
    assert t2.replay() is False
    assert t2.state()["samples"] == 0
    assert not t2.state()["frozen"]

    # Fence 2: same version, different schema (knob set changed).
    meta["tuner_version"] = ot.TUNER_VERSION
    with open(jp, "w") as fh:
        fh.write("\n".join([json.dumps(meta)] + raw[1:]) + "\n")
    t3 = _make_tuner(sim2, ["ring_chunk_bytes", "socket_buf_bytes"],
                     journal_path=jp, max_samples=4)
    assert t3.replay() is False


def test_cache_mode_replays_without_searching(tmp_path, monkeypatch):
    """HVD_TUNE=cache: start_online_tuner adopts the journaled state
    and never starts the search thread."""
    # The journal must be written with the SAME schema the cache-mode
    # tuner will resume with (the full training knob set) — a 2-knob
    # journal would be version-FENCED by the 4-knob resume, correctly.
    sim = Sim(_peaked_rate)
    jp = str(tmp_path / "tuner_journal.rank0.jsonl")
    t1 = _make_tuner(sim, list(ot.TRAINING_KNOBS),
                     journal_path=jp, max_samples=4)
    t1._attach_journal()
    t1.replay()
    _drive(t1)
    tuned = t1.state()["values"]
    t1.stop()

    monkeypatch.setenv("HVD_TUNE", "cache")
    monkeypatch.setenv("HVD_TUNE_JOURNAL_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_RANK", "0")
    monkeypatch.delenv("HVD_TUNE_FREEZE", raising=False)
    ot.stop_online_tuner()
    try:
        tuner = ot.start_online_tuner(role="training")
        assert tuner is not None
        assert tuner._thread is None  # cache mode: no search thread
        st = tuner.state()
        for name in ("ring_chunk_bytes", "socket_buf_bytes"):
            assert st["values"][name] == tuned[name]
        # The env mirror carries the tuned state to the next bootstrap.
        assert os.environ["HVD_RING_CHUNK_BYTES"] == \
            str(int(tuned["ring_chunk_bytes"]))
        # start() attaches the journal BEFORE replaying, so the
        # adoption is journaled: post-mortem forensics can count
        # resumed incarnations from the file alone.
        jtypes = [json.loads(l)["type"] for l in open(jp)]
        assert "tune_replay" in jtypes
    finally:
        ot.stop_online_tuner()
        for env in ("HVD_RING_CHUNK_BYTES", "HOROVOD_SOCKET_BUF_BYTES",
                    "HOROVOD_FUSION_THRESHOLD", "HOROVOD_CYCLE_TIME"):
            monkeypatch.delenv(env, raising=False)


def test_start_online_tuner_off_and_all_frozen(monkeypatch):
    monkeypatch.delenv("HVD_TUNE", raising=False)
    ot.stop_online_tuner()
    assert ot.start_online_tuner() is None
    monkeypatch.setenv("HVD_TUNE", "1")
    monkeypatch.setenv("HVD_TUNE_FREEZE", ",".join(ot.TRAINING_KNOBS))
    assert ot.start_online_tuner(role="training") is None
    ot.stop_online_tuner()


def test_live_unsafe_knobs_dropped_in_multi_rank_world(monkeypatch, specimen):
    """Runtime half of the spmd live_safe contract (the static half is
    tools/analysis/check_spmd.py): if the composed knob set ever grows
    a live_safe=False entry — a trace-time read whose per-rank search
    lowers divergent XLA programs — a tuner starting inside a shared
    world must drop the knob (and keep the rest), not search it."""
    from horovod_tpu.common import basics

    monkeypatch.setenv("HVD_TUNE", "cache")  # no search thread needed
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    monkeypatch.setattr(ot, "TRAINING_KNOBS",
                        ("ring_chunk_bytes", "specimen_bytes"))
    ot.stop_online_tuner()
    try:
        tuner = ot.start_online_tuner(role="training")
        assert tuner is not None
        searched = {b.name for b in tuner.bindings}
        assert searched == {"ring_chunk_bytes"}, searched
    finally:
        ot.stop_online_tuner()
    # Alone in its world the same set stays searchable (single-process
    # flash-tile tuning is legitimate — docs/autotune.md).
    monkeypatch.setattr(basics, "size", lambda: 1)
    try:
        tuner = ot.start_online_tuner(role="training")
        assert {b.name for b in tuner.bindings} == \
            {"ring_chunk_bytes", "specimen_bytes"}
    finally:
        ot.stop_online_tuner()


# --- metrics ----------------------------------------------------------------


def test_tuner_metrics_families_move(tmp_path):
    w0 = _metrics.value("hvd_tune_windows_total") or 0.0
    sim = Sim(_peaked_rate)
    tuner = _make_tuner(sim, ["ring_chunk_bytes"], max_samples=3)
    _drive(tuner)
    assert (_metrics.value("hvd_tune_windows_total") or 0.0) > w0
    assert _metrics.value("hvd_tune_frozen") == 1.0
    assert _metrics.value("hvd_tune_objective") > 0


# --- serve batcher setter path ----------------------------------------------


def test_batcher_set_tunables_clamps_to_hard_max():
    calls = []
    b = MicroBatcher(lambda rows: rows, max_batch=8, deadline_ms=5,
                     min_bucket=4, name="tune-test")
    try:
        b.set_tunables(max_batch=64, deadline_ms=-3)
        assert b.max_batch == 8      # never above the compiled ceiling
        assert b.deadline_s == 0.0   # deadline floors at 0
        b.set_tunables(max_batch=0)
        assert b.max_batch == 1
        b.set_tunables(max_batch=3, deadline_ms=2.5)
        assert b.max_batch == 3
        assert b.deadline_s == 0.0025
        assert calls == []
    finally:
        b.stop()


def test_batcher_tuned_down_still_drains_large_requests():
    """A request legal under the configured ceiling must still be
    served after the tuner lowers the fire trigger below its row
    count (the drain loop takes at least one request)."""
    import numpy as np

    b = MicroBatcher(lambda rows: rows * 2, max_batch=8, deadline_ms=1,
                     min_bucket=4, name="tune-drain")
    try:
        b.set_tunables(max_batch=2)
        fut = b.submit(np.ones((5, 3), np.float32))
        out = fut.result(timeout=10)
        assert out.shape == (5, 3)
        assert float(out[0, 0]) == 2.0
    finally:
        b.stop()


def test_replica_serve_knob_schema_matches_batcher_contract():
    """The serve schema's box stays inside what set_tunables accepts."""
    k = TUNABLE["serve_max_batch"]
    assert k.lo >= 1
    assert TUNABLE["serve_deadline_ms"].lo >= 0


def test_full_loop_propose_apply_revert_journal_replay(tmp_path):
    """ISSUE 11 acceptance, one test: propose -> apply -> guardrail-
    revert on regression -> journal -> a replayed process resumes the
    tuned state without re-searching. The objective is a plateau with
    a cliff: moves inside the plateau are accepted (within the guard
    band), moves over the cliff regress hard and must revert."""

    def plateau_cliff(values):
        rc = values.get("ring_chunk_bytes", 0.0)
        return 1e6 if rc <= (8 << 20) else 1e4

    sim = Sim(plateau_cliff)
    jp = str(tmp_path / "j.jsonl")
    tuner = _make_tuner(sim, ["ring_chunk_bytes"], journal_path=jp,
                        max_samples=10)
    tuner._attach_journal()
    tuner.replay()
    records = _drive(tuner)
    types = [r["type"] for r in records]
    assert "tune_accept" in types, types     # propose -> apply -> keep
    assert "tune_revert" in types, types     # guardrail fired
    assert types[-1] == "tune_freeze"
    tuned = tuner.state()["values"]
    assert tuned["ring_chunk_bytes"] <= (8 << 20)  # froze on plateau
    tuner.stop()
    # Journal carries the full decision stream...
    jtypes = {json.loads(l)["type"] for l in open(jp)}
    assert {"tune_meta", "tune_apply", "tune_accept", "tune_revert",
            "tune_freeze"} <= jtypes
    # ...and a restarted process resumes tuned, frozen, search-free.
    sim2 = Sim(plateau_cliff)
    restarted = _make_tuner(sim2, ["ring_chunk_bytes"], journal_path=jp,
                            max_samples=10)
    assert restarted.replay() is True
    assert restarted.state()["values"] == tuned
    assert restarted.step() is None          # no re-search
    assert sim2.values["ring_chunk_bytes"] == tuned["ring_chunk_bytes"]


# --- end-to-end: live knob moves under real np=2 traffic --------------------


@pytest.mark.tier2
@pytest.mark.slow
def test_tuner_moves_ring_chunk_live_np2(tmp_path):
    """ISSUE 11 acceptance: an np=2 job with HVD_TUNE=1 has the tuner
    move HVD_RING_CHUNK_BYTES (native set_wire_params on the LIVE
    core) under real allreduce traffic with per-step bit-correctness
    asserted and decisions journaled — no correctness or typed-abort
    failure. Assertions live in tuner_worker.py."""
    import subprocess
    import sys

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               HVD_TUNE="1",
               HVD_TUNE_WINDOW_SEC="1",
               HVD_TUNE_GUARD_PCT="50",  # loopback noise: keep moves
               HVD_TUNE_JOURNAL_DIR=str(tmp_path),
               HVD_TUNE_FREEZE="fusion_threshold_mb,cycle_time_ms")
    procs = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join(_REPO, "tests",
                                      "tuner_worker.py")],
        capture_output=True, text=True, timeout=240, env=env)
    assert procs.returncode == 0, procs.stdout + procs.stderr
    assert procs.stdout.count("TUNER_E2E_OK") == 2, procs.stdout


def test_live_unsafe_apply_refused_after_world_grows(monkeypatch, specimen):
    """Review fix: the start-time live_safe filter samples world size
    once, but an ELASTIC world can grow after the tuner thread is
    running (size 1 at start, peers join via reinit). The apply path
    itself must refuse to mutate a live_safe=False knob the moment
    the world is shared — per-rank mutation of a trace-time knob
    lowers divergent XLA programs."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.knobs import TUNABLE

    monkeypatch.delenv("TUNER_TEST_SPECIMEN_BYTES", raising=False)
    b = ot.KnobBinding(TUNABLE["specimen_bytes"])
    # Alone in its world: the apply lands and mirrors to env.
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 1)
    applied = b.apply(float(8 << 20))
    assert applied == float(8 << 20)
    assert os.environ["TUNER_TEST_SPECIMEN_BYTES"] == str(8 << 20)
    # World grew: the apply is refused, env mirror untouched, and the
    # returned value reports the LIVE state so tuner bookkeeping
    # stays coherent.
    monkeypatch.setattr(basics, "size", lambda: 2)
    refused = b.apply(float(16 << 20))
    assert refused == float(8 << 20)
    assert os.environ["TUNER_TEST_SPECIMEN_BYTES"] == str(8 << 20)
    # The guardrail's REVERT is exempt (restore=True): blocking it
    # would strand the knob at the mid-search value the guard just
    # rejected. In the shared world it lands the LAUNCH anchor —
    # here "unset", so the mirror is deleted and the schema default
    # (what an absent mirror means) is reported.
    restored = b.apply(float(4 << 20), restore=True)
    assert restored == float(4 << 20)  # launch anchor == default
    assert "TUNER_TEST_SPECIMEN_BYTES" not in os.environ
    # live_safe=True knobs are untouched by the gate.
    monkeypatch.delenv("HVD_RING_CHUNK_BYTES", raising=False)
    safe = ot.KnobBinding(TUNABLE["ring_chunk_bytes"])
    assert safe.apply(float(2 << 20)) == float(2 << 20)
    monkeypatch.delenv("TUNER_TEST_SPECIMEN_BYTES", raising=False)
    monkeypatch.delenv("HVD_RING_CHUNK_BYTES", raising=False)


def test_live_unsafe_apply_gate_is_atomic_with_the_write(
        monkeypatch, specimen):
    """Review fix (TOCTOU): the live_safe gate check and the env
    write run as one atomic unit under ot._apply_lock — the same lock
    every restore takes. A search-thread apply that raced an elastic
    reinit could otherwise pass the gate at size 1, stall, and land
    its stale write AFTER on_world_change's uniform restore. Pinned
    by holding the lock (the restore-in-progress stand-in), growing
    the world, and proving the blocked apply re-checks the gate when
    it finally acquires — refusing instead of clobbering."""
    import threading

    from horovod_tpu.common import basics
    from horovod_tpu.common.knobs import TUNABLE

    monkeypatch.delenv("TUNER_TEST_SPECIMEN_BYTES", raising=False)
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    size = {"v": 1}
    monkeypatch.setattr(basics, "size", lambda: size["v"])
    b = ot.KnobBinding(TUNABLE["specimen_bytes"])

    results = []
    t = threading.Thread(
        target=lambda: results.append(b.apply(float(16 << 20))))
    with ot._apply_lock:
        t.start()
        t.join(timeout=0.5)
        assert t.is_alive(), "apply must serialize on _apply_lock"
        size["v"] = 2  # the world grows while the apply is parked
    t.join(timeout=5)
    assert not t.is_alive()
    # The parked apply re-read the gate under the lock and refused:
    # no env write, live (default) value returned.
    assert "TUNER_TEST_SPECIMEN_BYTES" not in os.environ
    assert results == [TUNABLE["specimen_bytes"].default]


def test_shared_world_revert_clamps_to_launch_anchor(monkeypatch, specimen):
    """Review fix (revert-side TOCTOU): restore=True bypasses the
    live_safe gate, and the revert TARGET (the incumbent) is computed
    outside _apply_lock — so a guardrail revert racing an elastic
    reinit could land a stale per-rank incumbent chosen at size 1
    AFTER on_world_change's uniform restore. _apply_locked now
    re-derives the target under the lock: a shared-world restore of a
    live-unsafe knob always lands the LAUNCH anchor, whatever stale
    value the caller computed."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.knobs import TUNABLE

    monkeypatch.setenv("TUNER_TEST_SPECIMEN_BYTES", str(6 << 20))
    b = ot.KnobBinding(TUNABLE["specimen_bytes"])  # launch = 6 MiB
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    size = {"v": 1}
    monkeypatch.setattr(basics, "size", lambda: size["v"])
    # Alone: a mid-search apply lands (the stale incumbent-to-be).
    assert b.apply(float(16 << 20)) == float(16 << 20)
    # World grows; a revert still carrying the 16 MiB incumbent must
    # land the launch anchor instead.
    size["v"] = 2
    assert b.apply(float(16 << 20), restore=True) == float(6 << 20)
    assert os.environ["TUNER_TEST_SPECIMEN_BYTES"] == str(6 << 20)
    # Alone again (shrunk world): restores keep the caller's target —
    # the incumbent revert is the correct single-process behavior.
    size["v"] = 1
    assert b.apply(float(8 << 20), restore=True) == float(8 << 20)


def test_live_unsafe_binding_pruned_when_world_grows(monkeypatch, specimen):
    """Review fix: when an elastic world grows mid-search, a
    live_safe=False binding must be dropped from the searched set
    ONCE (optimizer box rebuilt over the survivors, measured samples
    re-fed) instead of proposing dead moves + warning every window
    for the life of the process."""
    from horovod_tpu.common import basics

    sim = Sim(lambda v: 100.0)
    tuner = _make_tuner(sim, ["ring_chunk_bytes", "specimen_bytes"],
                        max_samples=3)
    # Alone in its world: both knobs searched.
    assert {b.name for b in tuner.bindings} == \
        {"ring_chunk_bytes", "specimen_bytes"}
    rec = tuner.step()
    assert rec is not None
    # The world grows: the next round prunes to the safe survivor and
    # the search carries on over it alone.
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    rec = tuner.step()
    assert {b.name for b in tuner.bindings} == {"ring_chunk_bytes"}
    assert rec is not None
    # The prune restored the dropped knob to its START-TIME value,
    # KEPT it visible in state() (bench JSON reports what is live),
    # and journaled the decision.
    assert sim.values["specimen_bytes"] == \
        TUNABLE["specimen_bytes"].default
    assert tuner.state()["values"]["specimen_bytes"] == \
        TUNABLE["specimen_bytes"].default
    assert any(r["type"] == "tune_prune" and
               r["dropped"] == ["specimen_bytes"]
               for r in tuner.trajectory())
    # With ONLY unsafe knobs, the prune freezes the search outright —
    # at the restored values, with a journaled freeze record.
    sim2 = Sim(lambda v: 100.0)
    t2 = _make_tuner(sim2, ["specimen_bytes"], max_samples=3)
    assert t2.step() is None and t2.state()["frozen"]
    [frz] = [r for r in t2.trajectory() if r["type"] == "tune_freeze"]
    assert frz["pruned"] == ["specimen_bytes"]
    assert t2.state()["values"] == frz["values"]


def test_pruned_knob_restores_job_env_value_not_schema_default(
        monkeypatch, specimen):
    """Review fix: a fleet launched with an explicit env value for a
    live-unsafe knob must be restored to THAT value on prune — fresh
    elastic peers inherit the job env, so the launch value (not the
    schema default) is the rank-uniform anchor."""
    from horovod_tpu.common import basics

    monkeypatch.setenv("TUNER_TEST_SPECIMEN_BYTES", str(8 << 20))
    sim = Sim(lambda v: 100.0)
    tuner = _make_tuner(sim, ["ring_chunk_bytes", "specimen_bytes"],
                        max_samples=3)
    assert tuner.state()["values"]["specimen_bytes"] == \
        float(8 << 20)
    # A mid-search move lands while the process is alone in its world.
    [b] = [b for b in tuner.bindings if b.name == "specimen_bytes"]
    b.apply(float(16 << 20))
    assert os.environ["TUNER_TEST_SPECIMEN_BYTES"] == str(16 << 20)
    # The world grows: prune restores the LAUNCH value, not 4 MiB.
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    assert tuner.step() is not None
    assert os.environ["TUNER_TEST_SPECIMEN_BYTES"] == str(8 << 20)


def test_journal_replays_across_live_safe_recomposition(
        tmp_path, monkeypatch, specimen):
    """Review fix: a journal written by the full composed knob set
    (size-1 world) must replay after a restart whose live_safe drop
    narrowed the SEARCHED set — the fence hashes the composition, not
    the post-filter survivors, so tuned live-safe values are not
    silently discarded on an elastic re-bootstrap."""
    from horovod_tpu.common.knobs import TUNABLE as _T

    jp = str(tmp_path / "tuner_journal.jsonl")
    sim = Sim(lambda v: 100.0)
    both = ["ring_chunk_bytes", "specimen_bytes"]
    t1 = _make_tuner(sim, both, journal_path=jp, max_samples=2)
    t1._attach_journal()
    t1.replay()
    _drive(t1)
    tuned = t1.state()["values"]["ring_chunk_bytes"]
    t1.stop()
    # Restart composes the same schema but searches only the safe
    # survivor (what start_online_tuner does in a multi-rank world).
    sim2 = Sim(lambda v: 100.0)
    t2 = ot.OnlineTuner([sim2.binding("ring_chunk_bytes")],
                        sim2.objective, journal_path=jp,
                        clock=sim2.clock, wait=sim2.wait,
                        window_sec=1.0, max_samples=2,
                        fence_knobs=[_T[n] for n in both])
    t2._attach_journal()
    assert t2.replay() is True
    assert t2.state()["values"]["ring_chunk_bytes"] == tuned
    assert t2.state()["frozen"]
    t2.stop()


def test_frozen_live_unsafe_value_restored_on_world_change(
        monkeypatch, specimen):
    """Review fix: freeze is the terminal state of every search and
    exits the tuner thread, so a live-unsafe value frozen while the
    process was alone would outlive any in-loop protection. The
    elastic worker calls on_world_change() after each reinit; it must
    restore the launch value even on a frozen tuner."""
    from horovod_tpu.common import basics

    monkeypatch.delenv("TUNER_TEST_SPECIMEN_BYTES", raising=False)
    # Rate rewards bigger values, so the size-1 search freezes at a
    # NON-default value.
    sim = Sim(lambda v: 1.0 + v.get("specimen_bytes", 0.0))
    tuner = _make_tuner(sim, ["specimen_bytes"], max_samples=3)
    _drive(tuner)
    assert tuner.state()["frozen"]
    frozen_val = sim.values["specimen_bytes"]
    assert frozen_val != TUNABLE["specimen_bytes"].default
    # The world grows; the elastic worker's reinit hook fires.
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    monkeypatch.setattr(ot, "_global_tuner", tuner)
    ot.on_world_change()
    assert sim.values["specimen_bytes"] == \
        TUNABLE["specimen_bytes"].default
    assert tuner.state()["values"]["specimen_bytes"] == \
        TUNABLE["specimen_bytes"].default
    # Recorded as a prune (the search was already frozen), and a
    # second world change is a no-op.
    assert any(r["type"] == "tune_prune" for r in tuner.trajectory())
    n = len(tuner.trajectory())
    ot.on_world_change()
    assert len(tuner.trajectory()) == n
    monkeypatch.setattr(ot, "_global_tuner", None)
    assert ot.on_world_change() is None  # no tuner: no-op


def test_live_search_world_change_restores_values_inline(
        monkeypatch, specimen):
    """Review fix: with the search thread LIVE, on_world_change must
    restore live-unsafe VALUES immediately (the worker retraces right
    after the reset) while leaving bindings/_bo to the loop's own
    round-top prune — a cross-thread structural swap could misalign a
    concurrently built proposal."""
    from horovod_tpu.common import basics

    monkeypatch.delenv("TUNER_TEST_SPECIMEN_BYTES", raising=False)
    sim = Sim(lambda v: 100.0)
    tuner = _make_tuner(sim, ["ring_chunk_bytes", "specimen_bytes"],
                        max_samples=3)
    [b] = [b for b in tuner.bindings if b.name == "specimen_bytes"]
    b.apply(float(16 << 20))  # legal mid-search move while alone

    class _FakeThread:
        @staticmethod
        def is_alive():
            return True

    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    monkeypatch.setattr(ot, "_global_tuner", tuner)
    tuner._thread = _FakeThread()
    ot.on_world_change()
    # Values restored to launch state NOW...
    assert sim.values["specimen_bytes"] == \
        TUNABLE["specimen_bytes"].default
    assert any(r["type"] == "tune_restore" for r in tuner.trajectory())
    # ...but the structural drop is left to the search thread.
    assert {b.name for b in tuner.bindings} == \
        {"ring_chunk_bytes", "specimen_bytes"}
    tuner._thread = None
    monkeypatch.setattr(ot, "_global_tuner", None)


def test_shared_world_restore_deletes_env_mirror_unset_at_launch(
        monkeypatch):
    """Review fix: the env mirror must restore launch PRESENCE, not
    just the launch value — peers that inherit the job env have the
    variable unset, so a shared-world restore that wrote the default
    back (instead of deleting a mirror the job never set) would leave
    this rank alone with a trace-time knob in its environment."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.knobs import TUNABLE

    monkeypatch.delenv("HVD_PLAN_GRAD_OVERLAP", raising=False)
    b = ot.KnobBinding(TUNABLE["plan_grad_overlap"])  # launch: UNSET
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    size = {"v": 1}
    monkeypatch.setattr(basics, "size", lambda: size["v"])
    # Alone: a search apply lands and mirrors to env.
    assert b.apply(0.5) == 0.5
    assert os.environ["HVD_PLAN_GRAD_OVERLAP"] == "0.5"
    # World grows: the uniform restore DELETES the mirror (launch
    # state was absent) and reports the launch value.
    size["v"] = 2
    assert b.apply(0.5, restore=True) == \
        TUNABLE["plan_grad_overlap"].default
    assert "HVD_PLAN_GRAD_OVERLAP" not in os.environ
    # A mirror the job DID set at launch is written back, not deleted
    # (test_shared_world_revert_clamps_to_launch_anchor pins the
    # value side).
    monkeypatch.setenv("HVD_PLAN_DCN_BW_GBPS", "25")
    bk = ot.KnobBinding(TUNABLE["plan_dcn_bw_gbps"])
    assert bk.apply(50.0, restore=True) == 25.0
    assert os.environ["HVD_PLAN_DCN_BW_GBPS"] == "25"
