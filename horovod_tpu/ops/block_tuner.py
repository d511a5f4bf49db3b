"""Flash-attention VMEM block-size autotuner with a journaled cache.

``HVD_FLASH_BLOCK_Q/K`` existed since the kernel landed, but nothing
searched them: every job ran one built-in default (now a rule on the
sequence length, ``pallas_attention._default_blocks``) regardless of
its own (seq, head_dim, dtype, causal) shape or chip generation
(ROADMAP open item #3). This module closes that loop:

- ``best_blocks(...)``: consult a persistent cache keyed by
  shape + device; on a miss (and when tuning is allowed) run an
  on-first-call sweep over candidate (block_q, block_k) pairs on
  synthetic data of the live shape, timing one fwd+bwd step each, and
  journal the winner.
- The cache is an append-only JSONL file written with the PR 5 driver-
  journal discipline (O_APPEND single-line writes + fsync, readers fold
  records last-wins and skip torn/garbage lines), so concurrent
  workers tuning the same shape can never corrupt it — they at worst
  both measure and the later record wins.

Enable with ``HVD_FLASH_TUNE=1`` (tune on miss) or
``HVD_FLASH_TUNE=cache`` (use cached winners only, never measure —
for fleets where one tuning job warms the cache and serving jobs just
read it). Explicit ``HVD_FLASH_BLOCK_Q/K`` env overrides and explicit
``block_q=/block_k=`` arguments always win over the tuner
(docs/mfu.md has the full precedence table and a walkthrough).

SPMD caveat: winners are timing-derived, so two processes cold-tuning
the same shape concurrently can pick DIFFERENT tiles — and divergent
tile choices lower to divergent programs across ranks of one jitted
step, which desyncs its collectives. In a multi-rank world the tile
decision is therefore RANK-0-AUTHORITATIVE and synced at INIT time:
``sync_cache_across_world`` (called by ``basics.init`` on every world
formation, elastic reinits included — every rank runs init, so the
broadcast is symmetric) ships rank 0's folded cache to all ranks, and
``best_blocks`` answers exclusively from that uniform view. No
collective ever runs at TRACE time — a trace-time broadcast would
wedge whenever only a subset of ranks re-traces (a respawned elastic
peer traces from scratch while survivors' jitted steps stay
compiled). Cold-tuning (``=1``) is refused in a multi-rank world:
misses fall back to defaults uniformly; warm the cache from one
process first (docs/mfu.md; ``tests/test_block_tuner.py`` pins the
lockstep with a real np=2 run). Uninitialized/single-process tuning
is unchanged.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

from horovod_tpu.utils import metrics as _metrics

logger = logging.getLogger("horovod_tpu")

CACHE_VERSION = 1

# One trial = one timed (block_q, block_k) candidate for one shape key.
_M_TRIALS = _metrics.counter(
    "hvd_flash_tuner_trials_total",
    "Flash-attention block-size candidates timed by the autotuner "
    "(one per (block_q, block_k) pair per tuned shape).")

DEFAULT_CANDIDATES = (128, 256, 512)
DEFAULT_ITERS = 3

# Process-local fold of the cache file plus winners tuned this
# process; avoids re-reading the JSONL on every traced call site.
_mem_cache: Dict[str, Dict] = {}
_mem_cache_path: Optional[str] = None

# Rank-0-authoritative synced cache view for THIS world, established
# by sync_cache_across_world at init/reinit (the generation stamp
# rejects a stale view from a previous world). Multi-rank tile reads
# come exclusively from here — per-host cache drift cannot desync
# traces, and trace time stays collective-free.
_synced_cache: Optional[Dict[str, Dict]] = None
_synced_generation: Optional[int] = None
# Rank 0 had HVD_FLASH_TUNE_SYNC=0 at world formation (carried by the
# same broadcast, so the opt-out applies to every rank or none).
_synced_optout = False
_warned_cold_multirank = False


def tune_mode() -> str:
    """Resolved ``HVD_FLASH_TUNE``: '' (off), '1' (tune on miss) or
    'cache' (cached winners only)."""
    mode = os.environ.get("HVD_FLASH_TUNE", "").strip().lower()
    if mode in ("", "0", "off", "false"):
        return ""
    if mode == "cache":
        return "cache"
    return "1"


def cache_path() -> str:
    """``HVD_FLASH_TUNE_CACHE`` or ``~/.cache/horovod_tpu/``."""
    path = os.environ.get("HVD_FLASH_TUNE_CACHE", "")
    if path:
        return path
    return os.path.join(os.path.expanduser("~"), ".cache", "horovod_tpu",
                        "flash_blocks.jsonl")


def shape_key(seq_q: int, seq_kv: int, head_dim: int, dtype, causal: bool,
              device_kind: str) -> str:
    """Cache key for one attention shape on one chip generation.

    Batch and head count are deliberately absent: they scale the grid,
    not the per-block VMEM working set the tile sizes trade off.
    """
    return "q%d.kv%d.d%d.%s.%s.%s" % (
        seq_q, seq_kv, head_dim, str(dtype),
        "causal" if causal else "full",
        str(device_kind).replace(" ", "_"))


def load_cache(path: Optional[str] = None) -> Dict[str, Dict]:
    """Fold the JSONL journal into {key: winner-record}, last wins.

    Torn tails and garbage lines are skipped, not fatal — the same
    tolerance the PR 5 driver journal replay has; a cache that cannot
    be parsed at all is just an empty cache.
    """
    path = path or cache_path()
    out: Dict[str, Dict] = {}
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (isinstance(rec, dict)
                        and rec.get("version") == CACHE_VERSION
                        and isinstance(rec.get("key"), str)
                        and isinstance(rec.get("block_q"), int)
                        and isinstance(rec.get("block_k"), int)):
                    out[rec["key"]] = rec
    except OSError:
        pass
    return out


def append_record(rec: Dict, path: Optional[str] = None) -> None:
    """Journal one winner: O_APPEND single-line write + fsync.

    POSIX appends of one small line are atomic with respect to other
    appenders, so concurrent tuning processes interleave whole records
    instead of corrupting each other; ``load_cache`` takes the last
    record per key.
    """
    path = path or cache_path()
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    line = json.dumps(rec, sort_keys=True) + "\n"
    # Torn-tail guard (the PR 5 attach lesson): a writer that died
    # mid-append leaves a partial line; appending straight after it
    # would weld this record onto the fragment and lose BOTH. Lead
    # with a newline instead — the fragment stays its own (skipped)
    # line and this record parses.
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            if fh.tell():
                fh.seek(-1, os.SEEK_END)
                if fh.read(1) != b"\n":
                    line = "\n" + line
    except OSError:
        pass
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, line.encode("utf-8"))
        os.fsync(fd)
    finally:
        os.close(fd)


def _cached(key: str, path: str) -> Optional[Dict]:
    global _mem_cache, _mem_cache_path
    if _mem_cache_path != path:
        _mem_cache = load_cache(path)
        _mem_cache_path = path
    return _mem_cache.get(key)


def candidate_pairs(seq_q: int, seq_kv: int,
                    candidates=None) -> List[Tuple[int, int]]:
    """(block_q, block_k) sweep grid, clamped to the sequence lengths
    and deduplicated (a 64-long sequence turns 128/256/512 into one
    candidate, not three)."""
    if candidates is None:
        raw = os.environ.get("HVD_FLASH_TUNE_CANDIDATES", "")
        candidates = [int(c) for c in raw.split(",") if c.strip()] or \
            list(DEFAULT_CANDIDATES)
    qs = sorted({min(c, max(seq_q, 1)) for c in candidates})
    ks = sorted({min(c, max(seq_kv, 1)) for c in candidates})
    return [(bq, bk) for bq in qs for bk in ks]


def tune(seq_q: int, seq_kv: int, head_dim: int, dtype, causal: bool,
         *, candidates=None, iters: Optional[int] = None,
         batch: int = 1, heads: int = 1,
         interpret: Optional[bool] = None,
         time_fn=None) -> Tuple[int, int]:
    """Sweep candidate tiles for one shape; return the winning pair.

    Times one jitted fwd+bwd step per candidate on synthetic inputs of
    the live shape (compile excluded: one untimed warmup call per
    candidate). ``time_fn(block_q, block_k) -> seconds`` is injectable
    for unit tests. The winner is journaled to the cache.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.pallas_attention import flash_attention

    if iters is None:
        iters = int(os.environ.get("HVD_FLASH_TUNE_ITERS",
                                   str(DEFAULT_ITERS)))
    pairs = candidate_pairs(seq_q, seq_kv, candidates)

    if time_fn is None:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(batch, seq_q, heads, head_dim), dtype)
        k = jnp.asarray(rng.randn(batch, seq_kv, heads, head_dim), dtype)
        v = jnp.asarray(rng.randn(batch, seq_kv, heads, head_dim), dtype)

        def time_fn(bq, bk):
            def loss(q, k, v):
                return flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=interpret).astype(jnp.float32).sum()

            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(q, k, v))  # compile + warmup
            t0 = time.perf_counter()
            for _ in range(max(iters, 1)):
                out = step(q, k, v)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / max(iters, 1)

    results = []
    for bq, bk in pairs:
        _M_TRIALS.inc()
        try:
            dt = time_fn(bq, bk)
        except Exception as e:  # analysis: allow-broad-except — a
            # candidate that fails to compile (VMEM overflow on a big
            # tile) is a losing candidate, not a tuning failure.
            logger.debug("flash tuner: bq=%d bk=%d failed: %s", bq, bk, e)
            continue
        results.append((dt, bq, bk))
    if not results:
        raise RuntimeError(
            "flash block tuner: every candidate failed for shape "
            "q=%d kv=%d d=%d %s" % (seq_q, seq_kv, head_dim, dtype))
    results.sort()
    dt, bq, bk = results[0]
    key = shape_key(seq_q, seq_kv, head_dim, dtype, causal,
                    _device_kind())
    rec = {"version": CACHE_VERSION, "key": key, "block_q": bq,
           "block_k": bk, "ms_per_step": round(dt * 1e3, 4),
           "trials": len(results), "iters": iters}
    append_record(rec)
    _mem_cache[key] = rec
    logger.info("flash tuner: %s -> block_q=%d block_k=%d (%.3f ms)",
                key, bq, bk, dt * 1e3)
    return bq, bk


def _device_kind() -> str:
    import jax

    try:
        d = jax.devices()[0]
        return "%s-%s" % (d.platform, d.device_kind)
    except Exception:  # analysis: allow-broad-except — no backend is
        # a legitimate state for cache math in unit tests.
        return "unknown"


def best_blocks(seq_q: int, seq_kv: int, head_dim: int, dtype,
                causal: bool, *,
                interpret: Optional[bool] = None,
                batch: int = 1, heads: int = 1
                ) -> Optional[Tuple[int, int]]:
    """Tuned (block_q, block_k) for the live shape, or None.

    Cache hit wins; on a miss, ``HVD_FLASH_TUNE=1`` measures and
    journals (on-first-call tuning — the sweep runs once per shape per
    cache lifetime), ``HVD_FLASH_TUNE=cache`` returns None so the
    caller keeps its defaults.
    """
    mode = tune_mode()
    # Multi-rank worlds answer exclusively from the init-time synced
    # view (see sync_cache_across_world): reads stay purely local at
    # trace time, and per-host cache drift cannot desync the traced
    # programs. The synced view OVERRIDES the local env gate — rank
    # 0's settings are authoritative for the world, so a rank whose
    # own HVD_FLASH_TUNE is unset must still adopt tiles rank 0
    # synced (per-rank env divergence must never split the traced
    # programs). HVD_FLASH_TUNE_SYNC=0 on RANK 0 opts the whole world
    # back into local reads (the caller owns the docs/mfu.md
    # divergence hazard) — the opt-out rides the broadcast payload,
    # never the local env, so it cannot apply to a subset of ranks.
    if _multi_rank_world() and not _world_opted_out():
        if _synced_view() is None and not mode:
            return None  # nobody tuning: skip the key computation
        key = shape_key(seq_q, seq_kv, head_dim, dtype, causal,
                        _device_kind())
        return _best_blocks_synced(key, mode)
    if not mode:
        return None
    path = cache_path()
    key = shape_key(seq_q, seq_kv, head_dim, dtype, causal,
                    _device_kind())
    hit = _cached(key, path)
    if hit is not None:
        return hit["block_q"], hit["block_k"]
    if mode == "cache":
        return None
    return tune(seq_q, seq_kv, head_dim, dtype, causal,
                interpret=interpret, batch=batch, heads=heads)


def _multi_rank_world() -> bool:
    from horovod_tpu.common import basics

    return basics.is_shared_world()


def _sync_enabled() -> bool:
    """Local env read — consulted ONLY by rank 0 when building the
    sync payload (sync_cache_across_world). The READ path must never
    look at it: a per-rank HVD_FLASH_TUNE_SYNC=0 (stale launcher env
    on a respawned elastic worker, say) would flip that rank alone to
    local cache reads while its peers adopt the synced view — the
    asymmetric divergence the sync exists to close. Use
    _world_opted_out() on read paths instead."""
    return os.environ.get("HVD_FLASH_TUNE_SYNC", "1") != "0"


def _world_opted_out() -> bool:
    """Rank-0-authoritative sync opt-out for THIS world, carried by
    the init-time broadcast: True only when rank 0 had
    HVD_FLASH_TUNE_SYNC=0 at world formation. A world whose sync never
    ran (generation mismatch) is NOT opted out — reads stay on the
    uniform no-view path rather than falling back to divergent
    per-host caches."""
    from horovod_tpu.common.basics import init_generation

    return _synced_generation == init_generation() and _synced_optout


def sync_cache_across_world() -> None:
    """Ship rank 0's folded winner cache to every rank of the world.

    Called by ``basics.init()`` at every world formation — elastic
    reinits included, where EVERY rank (survivor and respawn alike)
    runs init, so the broadcast is symmetric. That symmetry is the
    whole design: a TRACE-time collective would wedge whenever only a
    subset of ranks re-traces (a respawned peer traces from scratch
    while survivors' jitted steps stay compiled and never re-enter
    best_blocks). No-op when tuning is off, the sync is opted out, or
    the world is not shared."""
    global _synced_cache, _synced_generation, _synced_optout
    from horovod_tpu.common import basics
    from horovod_tpu.common.objects import broadcast_object

    if not basics.is_shared_world():
        return
    # Participation is UNCONDITIONAL for every rank of the world —
    # gating it on per-rank env (HVD_FLASH_TUNE / HVD_FLASH_TUNE_SYNC)
    # would wedge every rank inside init the moment the env diverges
    # (e.g. tuning exported on rank 0 only). Rank 0's own settings
    # decide the PAYLOAD instead: the opt-out flag rides the broadcast
    # (so it applies to every rank or none), and the cache is None
    # when rank 0 has tuning off — downstream reads treat that as "no
    # synced view". One tiny broadcast per world formation.
    payload = {"optout": False, "cache": None}
    if basics.rank() == 0:
        if not _sync_enabled():
            payload["optout"] = True
        elif tune_mode():
            payload["cache"] = load_cache()
    payload = broadcast_object(payload, root_rank=0,
                               name="flash_tune.cache_sync")
    _synced_optout = bool(payload["optout"])
    _synced_cache = payload["cache"]
    _synced_generation = basics.init_generation()
    if _synced_cache is not None:
        logger.info("flash tuner: synced %d cached winner(s) from "
                    "rank 0", len(_synced_cache))


def _synced_view() -> Optional[Dict[str, Dict]]:
    """The world-synced cache when it belongs to THIS world (the
    generation stamp rejects a view from a previous world), else
    None."""
    from horovod_tpu.common.basics import init_generation

    if _synced_generation != init_generation():
        return None
    return _synced_cache


def world_synced_view_active() -> bool:
    """True when a multi-rank world holds a synced (rank-0) tile view
    this rank must consult even with its own ``HVD_FLASH_TUNE`` unset
    — rank 0's settings are authoritative for the world, so a caller
    that gates the ``best_blocks`` lookup on its LOCAL env alone
    (``flash_attention`` does) would re-open the per-rank-env
    divergence hole the sync closes. Purely local reads, trace-safe."""
    return (_multi_rank_world() and not _world_opted_out()
            and _synced_view() is not None)


def _best_blocks_synced(key: str, mode: str) -> Optional[Tuple[int, int]]:
    """Tile lookup against the world-synced view — purely local, no
    collective, identical on every rank by construction. Cold-tuning
    is refused here: a per-rank timing sweep is the divergence hazard
    itself, and a rank-0-only sweep would need a trace-time collective
    to publish (the wedge shape above). Misses fall back to defaults
    uniformly; warm the cache from one process first (docs/mfu.md)."""
    global _warned_cold_multirank

    rec = (_synced_view() or {}).get(key)
    if rec is not None:
        return rec["block_q"], rec["block_k"]
    if mode == "1" and not _warned_cold_multirank:
        _warned_cold_multirank = True
        logger.warning(
            "flash tuner: HVD_FLASH_TUNE=1 in a multi-rank world — "
            "cold-tuning is refused (per-rank timing sweeps trace "
            "divergent programs); shape %s falls back to defaults on "
            "every rank. Warm the cache from a single process and "
            "relaunch with HVD_FLASH_TUNE=cache (docs/mfu.md)", key)
    return None


def tuned_snapshot() -> Dict[str, Dict]:
    """Folded cache view for benchmarks/diagnostics (bench.py embeds
    this in its JSON result so a TPU capture records which tiles ran)."""
    return dict(load_cache())
