"""Jax-free equality worker for the wire-path schedule tests.

Launched np-at-a-time by tests/test_wire.py under different wire
schedules (pipelined chunked ring, serial legacy ring, scatter-gather
vs pack-path fused sends — HVD_RING_CHUNK_BYTES / HVD_WIRE_SG are set
by the test): every schedule must produce bit-identical collective
results. The matrix deliberately hits the chunk-math boundaries —
``count % n != 0``, counts smaller than the world, counts that split
into many sub-chunks under a tiny HVD_RING_CHUNK_BYTES — across all
wire dtypes and the non-commutative-ish ops (min/max/product), plus a
grouped (fused) submission so the segment-list path carries multiple
tensors per frame.

Rank 0 prints one ``WIRE_EQ_COUNTERS {...}`` line so the test can
assert whether the pipelined schedule actually engaged (sub-chunk
steps > 0) or stayed serial (== 0).

Wire compression (docs/wire.md#compression): when the test stages a
codec via HVD_WIRE_CODEC, float32 results are asserted within the
SHARED tolerance table (horovod_tpu.common.compression.WIRE_TOLERANCE —
imported, not copied, so the docs/tests/native can never drift apart);
every other dtype must stay bit-exact under every codec, because the
wire only compresses fp32. Every rank also prints a
``WIRE_EQ_HASH <hex>`` digest over all collective outputs, so the
chaos test can prove a healed compressed transfer produced the exact
bytes of an unfaulted run, and codec=none the exact bytes of the
codec-less default.
"""

import json
import os
import sys
import types

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Stub parent package: submodule imports below resolve against the real
# source tree without executing horovod_tpu/__init__.py (jax-free).
_pkg = types.ModuleType("horovod_tpu")
_pkg.__path__ = [os.path.join(_REPO, "horovod_tpu")]
sys.modules["horovod_tpu"] = _pkg

import numpy as np  # noqa: E402

from horovod_tpu.common.compression import (  # noqa: E402
    WIRE_TOLERANCE,
    codec_name,
)
from horovod_tpu.core.session import (  # noqa: E402
    OP_ALLREDUCE,
    CoreSession,
    _Group,
)

OP_SUM, OP_MIN, OP_MAX, OP_PRODUCT = 1, 3, 4, 5

# The codec the native core stages from the environment at init
# (core/src/controller.cc); "none" when unset/unknown.
CODEC = codec_name(os.environ.get("HVD_WIRE_CODEC", "none")) or "none"
TOL = WIRE_TOLERANCE[CODEC]

# count % n boundaries for every np this worker runs at (2, 3, 4):
# smaller than the world, one extra element, balanced, large + ragged.
COUNTS = [1, 3, 7, 64, 1000, 4099]


def _allreduce(session, name, arr, op=OP_SUM):
    group = _Group(1)
    session.submit(OP_ALLREDUCE, name, arr, group=group, index=0, op=op)
    return group.future.result(timeout=120)[0]


def _make(count, dtype, rank):
    # Rank-dependent but locally recomputable for any rank.
    base = (np.arange(count) % 7 + 1 + rank).astype(np.float64)
    if dtype == "bfloat16":
        import ml_dtypes

        return base.astype(ml_dtypes.bfloat16)
    return base.astype(dtype)


def main():
    assert "jax" not in sys.modules, "wire equality worker must stay jax-free"
    topo = types.SimpleNamespace(
        rank=int(os.environ["HOROVOD_RANK"]),
        size=int(os.environ["HOROVOD_SIZE"]))
    session = CoreSession.start(topo)
    r, n = topo.rank, topo.size

    # Digest over every collective output, in submission order: two
    # runs with the same config (faulted vs not, codec=none vs unset)
    # must produce IDENTICAL bytes, which is how the chaos test proves
    # a mid-compressed-chunk heal replayed exactly what was sent.
    import hashlib

    digest = hashlib.sha256()

    # --- dtype x count matrix, Sum ---------------------------------------
    for dtype in ("float32", "float64", "float16", "bfloat16",
                  "int32", "int64", "int8", "uint8"):
        for count in COUNTS:
            if dtype in ("float16", "bfloat16", "int8", "uint8") \
                    and count > 64:
                continue  # keep low-precision sums exact and runs fast
            mine = _make(count, dtype, r)
            expect = sum(_make(count, dtype, k).astype(np.float64)
                         for k in range(n))
            out = _allreduce(session, "eq.%s.%d" % (dtype, count), mine)
            digest.update(np.asarray(out).tobytes())
            if dtype == "float32" and CODEC != "none":
                # Lossy wire: the SHARED per-codec tolerance table is
                # the contract (docs/wire.md#compression cites it
                # verbatim). Only fp32 pays it.
                np.testing.assert_allclose(
                    np.asarray(out).astype(np.float64), expect,
                    atol=TOL["atol"] * n, rtol=TOL["rtol"])
            else:
                np.testing.assert_allclose(
                    np.asarray(out).astype(np.float64), expect, rtol=1e-2
                    if dtype in ("float16", "bfloat16") else 1e-12)

    # --- min / max / product on a ragged count ---------------------------
    xi = (np.arange(4099) % 11 + 1 + r).astype(np.int32)
    allv = np.stack([(np.arange(4099) % 11 + 1 + k) for k in range(n)])
    out_min = _allreduce(session, "eq.min", xi, OP_MIN)
    out_max = _allreduce(session, "eq.max", xi, OP_MAX)
    out_prod = _allreduce(session, "eq.prod", np.full(33, 2, np.int64),
                          OP_PRODUCT)
    for out_ in (out_min, out_max, out_prod):
        digest.update(np.asarray(out_).tobytes())
    np.testing.assert_array_equal(out_min, allv.min(axis=0))
    np.testing.assert_array_equal(out_max, allv.max(axis=0))
    np.testing.assert_array_equal(out_prod, np.full(33, 2 ** n, np.int64))

    # --- grouped (fused) submission: the segment-list wire path ----------
    # Ragged sizes so segment boundaries never line up with chunk
    # boundaries; one explicit group, as ``NativeBackend.
    # allreduce_async`` submits it, so the members wait for each other
    # in the core's group table. WHICH tensors share a fused buffer is
    # still decided cycle by cycle from what has arrived (a loaded
    # machine ticks between two submits), and a lossy codec's blocks
    # follow the buffer's layout: under a codec these bytes are held to
    # the tolerance, not to the digest.
    import zlib

    sizes = [129, 1, 2047, 513]
    for round_ in range(3):
        group = _Group(len(sizes))
        arrs = [np.full(sz, float(i + 1 + r + round_), np.float32)
                for i, sz in enumerate(sizes)]
        names = ["eq.fused.%d.%d" % (round_, i) for i in range(len(sizes))]
        group_id = zlib.crc32("|".join(names).encode())
        for i, a in enumerate(arrs):
            session.submit(OP_ALLREDUCE, names[i], a, group=group, index=i,
                           op=OP_SUM, group_id=group_id)
        outs = group.future.result(timeout=120)
        for i, out in enumerate(outs):
            expect = sum(float(i + 1 + k + round_) for k in range(n))
            if CODEC == "none":
                digest.update(np.asarray(out).tobytes())
            if CODEC != "none":
                np.testing.assert_allclose(
                    out, np.full(sizes[i], expect),
                    atol=TOL["atol"] * n, rtol=TOL["rtol"])
            else:
                np.testing.assert_allclose(out, np.full(sizes[i], expect))

    counters = session.counters()
    if r == 0:
        print("WIRE_EQ_COUNTERS " + json.dumps(
            {k: counters[k] for k in ("tx_bytes", "rx_bytes",
                                      "ring_subchunk_steps",
                                      "fused_tensors", "reconnects",
                                      "frames_retransmitted",
                                      "reconnect_failures",
                                      "codec_saved_bytes",
                                      "codec_bf16_sends",
                                      "codec_fp16_sends",
                                      "codec_int8_sends")}))
    print("WIRE_EQ_HASH rank %d %s" % (r, digest.hexdigest()))

    # Pin the cross-rank collective sequence number (docs/flightrec.md):
    # every rank dumps its native flight-recorder ring and reports the
    # highest executed seq — the test asserts they agree, which is the
    # property tools/trace's divergence detection stands on.
    import tempfile

    fr_path = os.path.join(
        tempfile.gettempdir(),
        "wire_eq_flightrec_r%d_pid%d.jsonl" % (r, os.getpid()))
    assert session.dump_flight_record(fr_path), "native dump failed"
    max_seq = -1
    with open(fr_path) as f:
        header = json.loads(f.readline())
        assert header.get("flightrec") == 1 and header["rank"] == r
        for line in f:
            rec = json.loads(line)
            if rec["kind"] == "RESP_BEGIN":
                max_seq = max(max_seq, rec["seq"])
    os.unlink(fr_path)
    print("WIRE_EQ_SEQ %d" % max_seq)

    session.shutdown()
    print("WIRE_EQ_OK rank %d" % r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
