"""np=2 worker asserting native timeline phase STRUCTURE.

Reference pattern: test/parallel/test_timeline.py validates the emitted
chrome-trace JSON; the phase hierarchy mirrors timeline.cc:496-558 —
per-tensor lanes carrying NEGOTIATE_<OP> (with coordinator rank-ready
instants), then the top-level op span nesting QUEUE, the fused-buffer
memcpys, and the TCP wire op.
"""

import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def load_trace(path):
    text = open(path).read().rstrip().rstrip(",").rstrip()
    if not text.endswith("]"):
        text += "]"
    return json.loads(text)


def tensor_lane(events, tensor_name):
    """Events on the trace thread named ``tensor_name``, in file
    (= emission) order."""
    tid = None
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and e.get("args", {}).get("name") == tensor_name):
            tid = e["tid"]
            break
    assert tid is not None, "no lane metadata for %r" % tensor_name
    return [e for e in events if e.get("tid") == tid and e.get("ph") != "M"]


def walk(lane):
    """(name, depth) sequence for B spans and instants, validating that
    every span closes and the lane's clock is monotonic."""
    stack, seq = [], []
    for e in lane:
        if e["ph"] == "B":
            seq.append((e["name"], len(stack)))
            stack.append(e["name"])
        elif e["ph"] == "E":
            assert stack, "E without open span"
            stack.pop()
        elif e["ph"] == "i":
            seq.append(("i:" + e["name"], len(stack)))
    assert not stack, "unclosed spans: %r" % stack
    ts = [e["ts"] for e in lane]
    assert ts == sorted(ts), "lane clock went backwards"
    return seq


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 2
    out_dir = os.environ["HVD_TL_DIR"]
    path = os.path.join(out_dir, "tl_rank%d.json" % r)
    hvd.start_timeline(path)
    # Every rank's writer is on before any rank submits ``tlh.x``: a
    # request that reaches the coordinator before ITS timeline started
    # leaves no rank-ready instant there (a fast rank 1 on a loaded
    # machine).
    hvd.barrier()
    hvd.allreduce(np.ones(16, np.float32), name="tlh.x", op=hvd.Sum)
    outs = hvd.grouped_allreduce(
        [np.ones(8, np.float32), np.full(8, 2.0, np.float32)],
        name="tlh.g", op=hvd.Sum)
    hvd.stop_timeline()
    np.testing.assert_allclose(outs[0], 2.0)
    np.testing.assert_allclose(outs[1], 4.0)

    events = load_trace(path + ".core.json")

    # --- single allreduce: full phase hierarchy on its own lane ---
    seq = walk(tensor_lane(events, "tlh.x"))
    names = [nm for nm, _ in seq]
    depths = dict(seq)
    assert names[0] == "NEGOTIATE_ALLREDUCE", names
    assert depths["NEGOTIATE_ALLREDUCE"] == 0
    if r == 0:
        # The coordinator marks each rank's request arriving inside the
        # negotiation span.
        assert "i:0" in names and "i:1" in names, names
        for mark in ("i:0", "i:1"):
            assert names.index(mark) > names.index("NEGOTIATE_ALLREDUCE")
    else:
        assert not any(nm.startswith("i:") for nm in names), names
    assert depths["ALLREDUCE"] == 0  # negotiation closed before the op
    assert depths["QUEUE"] == 1
    assert depths["TCP_ALLREDUCE"] == 1
    assert names.index("QUEUE") < names.index("TCP_ALLREDUCE")

    # --- collective sequence numbers (docs/flightrec.md) ---
    # The loop-row op events carry args.seq — the cross-rank execution
    # sequence the flight recorder indexes by. Strictly increasing on
    # this rank, and present for every executed op. (This used to be
    # dropped entirely; tools/trace needs it for divergence detection.)
    loop_ops = [e for e in events
                if e.get("tid") == 0 and e.get("ph") == "X"
                and e.get("cat") in ("ALLREDUCE", "BARRIER")]
    op_seqs = [e.get("args", {}).get("seq") for e in loop_ops]
    assert op_seqs and all(s is not None for s in op_seqs), loop_ops
    assert op_seqs == sorted(op_seqs), op_seqs

    # The eager (python) timeline stamps the per-process-set submit
    # seq on both span edges.
    py_events = load_trace(path)
    py_spans = [e for e in py_events
                if e.get("cat") == "allreduce" and e.get("ph") in "BE"]
    py_seqs = {e.get("args", {}).get("seq") for e in py_spans}
    assert py_spans and py_seqs - {None}, py_events

    # --- cycle marks on the loop row when the knob is set ---
    if os.environ.get("HOROVOD_TIMELINE_MARK_CYCLES", "") not in ("", "0"):
        marks = [e for e in events
                 if e.get("name") == "CYCLE_START" and e.get("tid") == 0]
        assert marks, "HOROVOD_TIMELINE_MARK_CYCLES set but no marks"

    # --- grouped allreduce: phase structure depends on the wire path.
    # Legacy pack path (HVD_WIRE_SG=0): fused-buffer memcpys bracket
    # the wire op on every member lane. Scatter-gather path (default
    # since the zero-copy wire PR): the ring gathers straight from /
    # scatters straight into tensor memory, so the memcpy spans MUST
    # NOT appear — their absence on a fused op is the timeline's proof
    # the zero-copy path actually ran (docs/wire.md).
    wire_sg = os.environ.get("HVD_WIRE_SG", "1") != "0"
    lanes_checked = 0
    for e in events:
        if e.get("ph") != "M":
            continue
        tname = e.get("args", {}).get("name", "")
        if not tname.startswith("tlh.g"):
            continue
        lane = [x for x in events
                if x.get("tid") == e["tid"] and x.get("ph") in "BEi"]
        lane_names = [x["name"] for x in lane if x["ph"] == "B"]
        assert "TCP_ALLREDUCE" in lane_names, lane_names
        if wire_sg:
            assert "MEMCPY_IN_FUSION_BUFFER" not in lane_names, lane_names
            assert "MEMCPY_OUT_FUSION_BUFFER" not in lane_names, lane_names
        else:
            assert "MEMCPY_IN_FUSION_BUFFER" in lane_names, lane_names
            assert "MEMCPY_OUT_FUSION_BUFFER" in lane_names, lane_names
            assert (lane_names.index("MEMCPY_IN_FUSION_BUFFER")
                    < lane_names.index("TCP_ALLREDUCE")
                    < lane_names.index("MEMCPY_OUT_FUSION_BUFFER"))
        lanes_checked += 1
    assert lanes_checked == 2, lanes_checked

    hvd.shutdown()
    print("TIMELINE_OK rank=%d" % r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
