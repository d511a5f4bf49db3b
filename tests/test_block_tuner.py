"""Flash-attention block autotuner (ops/block_tuner.py).

Cache journal round-trips (the PR 5 append-fsync discipline: torn
tails tolerated, concurrent appends interleave whole records, last
record per key wins), winner selection with an injected timer, and one
real CPU-interpreter sweep proving the tuner picks a non-default
winner for a small shape (docs/mfu.md).
"""

import json
import os

import numpy as np
import pytest

from horovod_tpu.ops import block_tuner


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "flash_blocks.jsonl")
    monkeypatch.setenv("HVD_FLASH_TUNE_CACHE", path)
    # Reset the process-local fold so tests never see each other.
    block_tuner._mem_cache = {}
    block_tuner._mem_cache_path = None
    yield path


def _rec(key, bq, bk, **extra):
    rec = {"version": block_tuner.CACHE_VERSION, "key": key,
           "block_q": bq, "block_k": bk}
    rec.update(extra)
    return rec


class TestCacheJournal:
    def test_round_trip(self, _isolated_cache):
        block_tuner.append_record(_rec("k1", 128, 256))
        block_tuner.append_record(_rec("k2", 64, 64))
        cache = block_tuner.load_cache(_isolated_cache)
        assert cache["k1"]["block_q"] == 128
        assert cache["k2"] == _rec("k2", 64, 64)

    def test_last_record_wins(self, _isolated_cache):
        block_tuner.append_record(_rec("k", 128, 128))
        block_tuner.append_record(_rec("k", 512, 256))
        assert block_tuner.load_cache(_isolated_cache)["k"]["block_q"] == 512

    def test_torn_tail_tolerated(self, _isolated_cache):
        block_tuner.append_record(_rec("good", 64, 64))
        with open(_isolated_cache, "a") as fh:
            fh.write('{"version": 1, "key": "torn", "blo')  # crash mid-append
        cache = block_tuner.load_cache(_isolated_cache)
        assert "good" in cache and "torn" not in cache
        # Appending after the torn tail still yields parseable records
        # for every LATER line (the torn line only loses itself).
        block_tuner.append_record(_rec("after", 32, 32))
        cache = block_tuner.load_cache(_isolated_cache)
        assert "after" in cache

    def test_garbage_and_wrong_version_skipped(self, _isolated_cache):
        with open(_isolated_cache, "w") as fh:
            fh.write("not json at all\n")
            fh.write(json.dumps({"version": 999, "key": "v", "block_q": 1,
                                 "block_k": 1}) + "\n")
            fh.write(json.dumps({"key": "missing-fields"}) + "\n")
        assert block_tuner.load_cache(_isolated_cache) == {}

    def test_missing_file_is_empty_cache(self, tmp_path):
        assert block_tuner.load_cache(str(tmp_path / "nope.jsonl")) == {}

    def test_interleaved_appends_from_two_writers(self, _isolated_cache):
        # Two processes' interleaved whole-line appends: all survive.
        for i in range(10):
            block_tuner.append_record(_rec("w1.%d" % i, 64, 64))
            block_tuner.append_record(_rec("w2.%d" % i, 128, 128))
        cache = block_tuner.load_cache(_isolated_cache)
        assert len(cache) == 20


class TestShapeKey:
    def test_key_fields(self):
        key = block_tuner.shape_key(2048, 2048, 64, "bfloat16", True,
                                    "tpu v5e")
        assert key == "q2048.kv2048.d64.bfloat16.causal.tpu_v5e"
        assert block_tuner.shape_key(64, 128, 8, "float32", False, "cpu") \
            == "q64.kv128.d8.float32.full.cpu"

    def test_candidate_pairs_clamped_and_deduped(self, monkeypatch):
        monkeypatch.delenv("HVD_FLASH_TUNE_CANDIDATES", raising=False)
        pairs = block_tuner.candidate_pairs(64, 64, (128, 256, 512))
        assert pairs == [(64, 64)]
        pairs = block_tuner.candidate_pairs(200, 100, (64, 256))
        assert pairs == [(64, 64), (64, 100), (200, 64), (200, 100)]

    def test_candidates_env(self, monkeypatch):
        monkeypatch.setenv("HVD_FLASH_TUNE_CANDIDATES", "16,32")
        assert block_tuner.candidate_pairs(1024, 1024) == [
            (16, 16), (16, 32), (32, 16), (32, 32)]


class TestTune:
    def test_injected_timer_picks_fastest_and_journals(
            self, _isolated_cache, monkeypatch):
        times = {(32, 32): 3.0, (32, 64): 1.0, (64, 32): 2.0,
                 (64, 64): 4.0}
        bq, bk = block_tuner.tune(
            64, 64, 8, "float32", True, candidates=(32, 64),
            time_fn=lambda q, k: times[(q, k)])
        assert (bq, bk) == (32, 64)
        cache = block_tuner.load_cache(_isolated_cache)
        (rec,) = cache.values()
        assert (rec["block_q"], rec["block_k"]) == (32, 64)
        assert rec["trials"] == 4

    def test_failing_candidates_are_skipped(self, _isolated_cache):
        def time_fn(q, k):
            if (q, k) != (32, 32):
                raise RuntimeError("VMEM overflow")
            return 1.0

        assert block_tuner.tune(64, 64, 8, "float32", True,
                                candidates=(32, 64),
                                time_fn=time_fn) == (32, 32)

    def test_all_candidates_failing_raises(self, _isolated_cache):
        def time_fn(q, k):
            raise RuntimeError("no")

        with pytest.raises(RuntimeError, match="every candidate"):
            block_tuner.tune(64, 64, 8, "float32", True,
                             candidates=(32,), time_fn=time_fn)

    def test_trials_counter(self, _isolated_cache):
        from horovod_tpu.utils import metrics

        before = metrics.REGISTRY.snapshot().get(
            "hvd_flash_tuner_trials_total", {}).get("values", [])
        before = before[0]["value"] if before else 0
        block_tuner.tune(64, 64, 8, "float32", True, candidates=(32, 64),
                         time_fn=lambda q, k: 1.0)
        after = metrics.REGISTRY.snapshot()[
            "hvd_flash_tuner_trials_total"]["values"][0]["value"]
        assert after - before == 4


class TestBestBlocks:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("HVD_FLASH_TUNE", raising=False)
        assert block_tuner.best_blocks(64, 64, 8, "float32", True) is None

    def test_cache_mode_never_measures(self, _isolated_cache, monkeypatch):
        monkeypatch.setenv("HVD_FLASH_TUNE", "cache")
        # Miss: returns None without running a sweep.
        assert block_tuner.best_blocks(64, 64, 8, "float32", True) is None
        # Hit: returns the journaled winner.
        key = block_tuner.shape_key(64, 64, 8, "float32", True,
                                    block_tuner._device_kind())
        block_tuner.append_record(_rec(key, 32, 16))
        block_tuner._mem_cache_path = None  # force re-fold
        assert block_tuner.best_blocks(64, 64, 8, "float32", True) \
            == (32, 16)


def test_cpu_interpreter_sweep_selects_non_default_winner(
        _isolated_cache, monkeypatch):
    """The acceptance sweep: a real interpret-mode fwd+bwd timing run
    on a small shape must pick SOME winner from the clamped candidate
    grid — necessarily non-default (256/512 is not in the grid at
    seq 64) — and flash_attention must consume it via HVD_FLASH_TUNE."""
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_attention import flash_attention

    monkeypatch.setenv("HVD_FLASH_TUNE", "1")
    monkeypatch.setenv("HVD_FLASH_TUNE_CANDIDATES", "32,64")
    monkeypatch.setenv("HVD_FLASH_TUNE_ITERS", "1")
    monkeypatch.delenv("HVD_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("HVD_FLASH_BLOCK_K", raising=False)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 64, 1, 8), jnp.float32)
    out = flash_attention(q, q, q, causal=True)  # tunes on first call
    assert out.shape == q.shape

    cache = block_tuner.load_cache()
    (rec,) = cache.values()
    winner = (rec["block_q"], rec["block_k"])
    assert winner != (256, 512)
    assert set(winner) <= {32, 64}
    # Second call is a pure cache hit: the trial counter must not move.
    from horovod_tpu.utils import metrics

    trials = metrics.REGISTRY.snapshot()[
        "hvd_flash_tuner_trials_total"]["values"][0]["value"]
    flash_attention(q, q, q, causal=True)
    assert metrics.REGISTRY.snapshot()[
        "hvd_flash_tuner_trials_total"]["values"][0]["value"] == trials


# --- multi-rank lockstep (ISSUE 14 spmd sweep) ------------------------------

def test_np2_divergent_caches_adopt_rank0_winner(tmp_path):
    """Regression pin for the real divergence the spmd sweep fixed:
    two ranks seeded with DIFFERENT per-host cache winners for one
    shape must both trace rank 0's tiles (init ships rank 0's cache
    view to every rank; pre-fix each rank returned its own hit and
    lowered divergent programs), with NO collective at trace time
    (the worker poisons broadcast_object around its lookups) and
    multi-rank cold-tuning refused uniformly. Runs a REAL np=2 fleet
    over the native control plane — the assertions live in
    tests/flash_sync_worker.py."""
    from tests.test_native_core import _launch

    codes, outputs = _launch(
        2, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flash_sync_worker.py"),
        extra_env={"HVD_FLASH_SYNC_CACHE_DIR": str(tmp_path)})
    for r, (c, out) in enumerate(zip(codes, outputs)):
        assert c == 0, "rank %d failed:\n%s" % (r, out)
    assert sum("FLASH_SYNC_OK" in o for o in outputs) == 2


def test_synced_view_overrides_local_env_gate(monkeypatch):
    """Review fix: a rank whose own HVD_FLASH_TUNE is unset must still
    adopt tiles from the world-synced view (rank 0's settings are
    authoritative) — per-rank env divergence must never split the
    traced programs."""
    from horovod_tpu.common import basics

    monkeypatch.delenv("HVD_FLASH_TUNE", raising=False)
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    key = block_tuner.shape_key(256, 256, 64, "float32", True,
                                block_tuner._device_kind())
    monkeypatch.setattr(block_tuner, "_synced_cache",
                        {key: _rec(key, 256, 512)})
    monkeypatch.setattr(block_tuner, "_synced_generation",
                        basics.init_generation())
    assert block_tuner.best_blocks(256, 256, 64, "float32", True) \
        == (256, 512)
    # No synced view and tuning locally off: defaults, no key math.
    monkeypatch.setattr(block_tuner, "_synced_cache", None)
    assert block_tuner.best_blocks(256, 256, 64, "float32", True) \
        is None


def test_local_sync_optout_env_cannot_split_the_read_path(monkeypatch):
    """Review fix: HVD_FLASH_TUNE_SYNC=0 in THIS rank's env (stale
    launcher env on a respawn, say) must not flip this rank alone to
    local cache reads while peers adopt the synced view — the opt-out
    is rank-0-authoritative, carried by the sync broadcast, so the
    local env is ignored on the read path."""
    from horovod_tpu.common import basics

    monkeypatch.setenv("HVD_FLASH_TUNE_SYNC", "0")
    monkeypatch.delenv("HVD_FLASH_TUNE", raising=False)
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    key = block_tuner.shape_key(256, 256, 64, "float32", True,
                                block_tuner._device_kind())
    monkeypatch.setattr(block_tuner, "_synced_cache",
                        {key: _rec(key, 256, 512)})
    monkeypatch.setattr(block_tuner, "_synced_generation",
                        basics.init_generation())
    monkeypatch.setattr(block_tuner, "_synced_optout", False)
    assert block_tuner.best_blocks(256, 256, 64, "float32", True) \
        == (256, 512)
    assert block_tuner.world_synced_view_active()
    # The broadcast opt-out (rank 0's decision) DOES flip the world
    # to local reads — uniformly, because every rank received it.
    monkeypatch.setattr(block_tuner, "_synced_optout", True)
    monkeypatch.setenv("HVD_FLASH_TUNE", "cache")
    block_tuner.append_record(_rec(key, 128, 128))
    block_tuner._mem_cache = {}
    block_tuner._mem_cache_path = None
    assert block_tuner.best_blocks(256, 256, 64, "float32", True) \
        == (128, 128)
    assert not block_tuner.world_synced_view_active()


def test_flash_attention_consults_synced_view_without_local_env(
        monkeypatch):
    """Review fix: flash_attention's local HVD_FLASH_TUNE gate must
    not bypass best_blocks when the world synced rank 0's tile view —
    otherwise a rank with the env unset traces DEFAULT tiles against
    rank 0's tuned ones, the per-rank-env divergence the init-time
    sync exists to close. Pinned at the caller level: the synced
    winner (32, 16) is a tile choice the defaults (256, 512) would
    never produce at this shape."""
    import jax.numpy as jnp

    from horovod_tpu.common import basics
    from horovod_tpu.ops import pallas_attention

    monkeypatch.delenv("HVD_FLASH_TUNE", raising=False)
    monkeypatch.delenv("HVD_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("HVD_FLASH_BLOCK_K", raising=False)
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    key = block_tuner.shape_key(64, 64, 8, "float32", True,
                                block_tuner._device_kind())
    monkeypatch.setattr(block_tuner, "_synced_cache",
                        {key: _rec(key, 32, 16)})
    monkeypatch.setattr(block_tuner, "_synced_generation",
                        basics.init_generation())
    assert block_tuner.world_synced_view_active()

    picked = {}
    real_flash = pallas_attention._flash

    def spy(qt, kt, vt, causal, window, block_q, block_k, scale, interpret):
        picked["blocks"] = (block_q, block_k)
        return real_flash(qt, kt, vt, causal, window, block_q, block_k,
                          scale, interpret)

    monkeypatch.setattr(pallas_attention, "_flash", spy)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 64, 1, 8), jnp.float32)
    out = pallas_attention.flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape
    assert picked["blocks"] == (32, 16)
