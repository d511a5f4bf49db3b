#!/usr/bin/env python
"""Native wire microbenchmark harness (docs/wire.md).

Loopback allreduce busbw sweep over payload sizes through the native
TCP data plane, measured with jax-free workers
(tests/wire_bench_worker.py) — the data-plane A/B instrument this box
needs because ``bench_scaling.py`` is broken by jax API drift and the
host has ~2x run-to-run swings (only interleaved pre/post trials are
trustworthy; see docs/benchmarks.md).

Examples:

    python bench_wire.py --np 2                      # default sweep
    python bench_wire.py --np 4 --sizes 65536,1048576
    python bench_wire.py --chunk-bytes 0             # serial fallback
    python bench_wire.py --sg 0                      # pack-path fused
    python bench_wire.py --out wire.json             # machine-readable
    python bench_wire.py --null-ab --trials 5        # A/A slot bias
    python bench_wire.py --ab chunk_bytes=0          # A/B with bias gate
    python bench_wire.py --ab compress=bf16          # wire-codec A/B

A/B discipline (docs/benchmarks.md): this box has ~2x run-to-run
swings AND a paired-slot bias — an A/A null test (identical config in
both slots of each trial) has measured the second slot up to 22%
slower at >= 8 MB payloads. ``--null-ab`` measures that bias;
``--ab KEY=VAL[,KEY=VAL]`` runs interleaved A/B trials (B applies the
overrides) and ALWAYS runs the null test alongside, printing each
size's delta next to the observed bias ratio and verdicting it
``within_slot_bias`` unless the delta exceeds the null spread. A
config that wins from the disadvantaged slot is a real win; anything
smaller than the bias is noise, now enforced by the tool instead of a
memory note.

Exit code 0 and one JSON document on stdout (and in --out when given).
"""

import argparse
import json
import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.abspath(__file__))
_WORKER = os.path.join(_REPO, "tests", "wire_bench_worker.py")

DEFAULT_SIZES = "65536,1048576,8388608,67108864"  # 64 KB -> 64 MB


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_sweep(np_, sizes, iters, warmup, chunk_bytes=None, sg=None,
              sockbuf=None, flightrec=None, fault=None, compress=None,
              timeout=600):
    """One np-wide sweep; returns the rank-0 JSON payload. ``fault``
    is an injector env dict (common.fault_injection.fault_env) exported
    to every worker — the self-healing-wire measurement hook
    (docs/wire.md#reconnect). ``compress`` is a wire-codec name
    (none/bf16/fp16/int8) exported as HVD_WIRE_CODEC — the bench
    worker relaxes its correctness floor to the shared tolerance table
    under a lossy codec (docs/wire.md#compression)."""
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r),
            "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_CROSS_RANK": "0",
            "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
            "HOROVOD_CONTROLLER_PORT": str(port),
            "HOROVOD_CYCLE_TIME": "1.0",
            "HVD_WIRE_BENCH_SIZES": sizes,
            "HVD_WIRE_BENCH_ITERS": str(iters),
            "HVD_WIRE_BENCH_WARMUP": str(warmup),
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            # Workers are jax-free; pinned anyway so nothing in the
            # process tree can claim a chip.
            "JAX_PLATFORMS": "cpu",
        })
        if chunk_bytes is not None:
            env["HVD_RING_CHUNK_BYTES"] = str(chunk_bytes)
        if sg is not None:
            env["HVD_WIRE_SG"] = str(sg)
        if sockbuf is not None:
            env["HOROVOD_SOCKET_BUF_BYTES"] = str(sockbuf)
        if flightrec is not None:
            env["HVD_FLIGHTREC"] = str(flightrec)
        if compress is not None:
            env["HVD_WIRE_CODEC"] = str(compress)
        if fault:
            env.update(fault)
        procs.append(subprocess.Popen(
            [sys.executable, _WORKER], env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outputs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outputs.append(out)
    for r, p in enumerate(procs):
        if p.returncode != 0:
            raise RuntimeError("wire bench rank %d failed (rc=%s):\n%s"
                               % (r, p.returncode, outputs[r]))
    for line in outputs[0].splitlines():
        if line.startswith("WIRE_BENCH_JSON "):
            return json.loads(line[len("WIRE_BENCH_JSON "):])
    raise RuntimeError("rank 0 emitted no WIRE_BENCH_JSON line:\n%s"
                       % outputs[0])


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def _busbw_by_size(payload):
    return {size: res["busbw_gbps"]
            for size, res in payload["results"].items()}


def _parse_overrides(spec):
    """``--ab chunk_bytes=0,sg=1,sockbuf=...,flightrec=...,
    compress=bf16`` -> ``run_sweep`` kwargs (sockbuf =
    HOROVOD_SOCKET_BUF_BYTES, the online tuner's other wire knob —
    docs/autotune.md; flightrec = HVD_FLIGHTREC, the always-on
    recorder's overhead gate — docs/flightrec.md; compress =
    HVD_WIRE_CODEC, the quantized-ring wire codec —
    docs/wire.md#compression)."""
    allowed = {"chunk_bytes": int, "sg": int, "sockbuf": int,
               "flightrec": int, "compress": str}
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise SystemExit("--ab expects KEY=VAL, got %r" % part)
        key, val = part.split("=", 1)
        key = key.strip()
        if key not in allowed:
            raise SystemExit("--ab key %r not supported (use %s)"
                             % (key, "/".join(sorted(allowed))))
        out[key] = allowed[key](val)
    if not out:
        raise SystemExit("--ab needs at least one KEY=VAL override")
    return out


def run_paired_trials(args, b_overrides=None, collect_b=None):
    """Interleaved slot-paired trials: each trial runs slot A then
    slot B back-to-back. Identical configs (``b_overrides=None``)
    measure the box's slot bias (the A/A null test); with overrides the
    same pairing measures the A/B delta *on top of* that bias.

    Returns {size: {"ratios": [B/A busbw per trial], "median_ratio"}}.
    """
    base = dict(chunk_bytes=args.chunk_bytes, sg=args.sg)
    b_cfg = dict(base)
    if b_overrides:
        b_cfg.update(b_overrides)
    per_size = {}
    for trial in range(args.trials):
        a = run_sweep(args.np_, args.sizes, args.iters, args.warmup,
                      timeout=args.timeout, **base)
        b = run_sweep(args.np_, args.sizes, args.iters, args.warmup,
                      timeout=args.timeout, **b_cfg)
        if collect_b is not None:
            collect_b.append(b)
        bw_a, bw_b = _busbw_by_size(a), _busbw_by_size(b)
        for size in bw_a:
            if size in bw_b:
                per_size.setdefault(size, []).append(
                    bw_b[size] / bw_a[size])
        print("# trial %d/%d done" % (trial + 1, args.trials),
              file=sys.stderr)
    return {size: {"ratios": ratios,
                   "median_ratio": round(_median(ratios), 4)}
            for size, ratios in per_size.items()}


def _verdict(ab_ratio, null_ratios):
    """Significant only when the A/B ratio clears the WHOLE observed
    null spread (plus the null's own median bias direction): a delta
    inside the band an identical config produced is slot bias."""
    lo, hi = min(null_ratios), max(null_ratios)
    if lo <= ab_ratio <= hi:
        return "within_slot_bias"
    return "faster" if ab_ratio > hi else "slower"


def run_gated_trials(args, b_overrides, ratio_key, b_label,
                     collect_b=None):
    """The null-gated A/B discipline shared by ``--ab`` and
    ``--fault reconnect_storm``: run the A/A null trials, run the
    interleaved B trials, and verdict each size's B/A ratio against
    the observed slot-bias band. Returns the ``per_size`` payload
    (ratio under ``ratio_key``) after printing the verdict table."""
    print("# null A/A trials (slot-bias gate)...", file=sys.stderr)
    null = run_paired_trials(args)
    print("# %s trials..." % b_label, file=sys.stderr)
    b = run_paired_trials(args, b_overrides, collect_b=collect_b)
    per_size = {}
    for s in sorted(set(null) & set(b), key=int):
        row = {
            ratio_key: b[s]["median_ratio"],
            "null_bias_median_ratio": null[s]["median_ratio"],
            "null_bias_spread": [round(min(null[s]["ratios"]), 4),
                                 round(max(null[s]["ratios"]), 4)],
            "verdict": _verdict(b[s]["median_ratio"], null[s]["ratios"]),
        }
        per_size[s] = row
        print("# %10s %s %.3f | null bias %.3f (spread %.3f-%.3f) -> %s"
              % (s, ratio_key, row[ratio_key],
                 row["null_bias_median_ratio"],
                 row["null_bias_spread"][0], row["null_bias_spread"][1],
                 row["verdict"]), file=sys.stderr)
    return per_size


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--np", type=int, default=2, dest="np_")
    ap.add_argument("--sizes", default=DEFAULT_SIZES,
                    help="comma-separated payload bytes "
                         "(default %s)" % DEFAULT_SIZES)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--chunk-bytes", type=int, default=None,
                    help="HVD_RING_CHUNK_BYTES for the workers "
                         "(0 = serial fallback; default: core default)")
    ap.add_argument("--sg", type=int, default=None, choices=(0, 1),
                    help="HVD_WIRE_SG for the workers")
    ap.add_argument("--timeout", type=int, default=600)
    ap.add_argument("--out", default=None, help="also write JSON here")
    ap.add_argument("--null-ab", action="store_true",
                    help="run the A/A slot-bias null test: --trials "
                         "paired sweeps with IDENTICAL config in both "
                         "slots; reports the per-size bias ratio an "
                         "honest A/B delta must exceed")
    ap.add_argument("--ab", default=None, metavar="KEY=VAL[,KEY=VAL]",
                    help="interleaved A/B trials: slot B applies the "
                         "overrides (chunk_bytes=..., sg=..., "
                         "sockbuf=..., compress=bf16). The A/A null "
                         "test runs alongside automatically and gates "
                         "each delta's verdict")
    ap.add_argument("--trials", type=int, default=5,
                    help="paired trials for --null-ab/--ab (default 5)")
    ap.add_argument("--fault", default=None,
                    choices=("reset", "reconnect_storm"),
                    help="self-healing-wire measurement "
                         "(docs/wire.md#reconnect): 'reset' injects "
                         "one hard RST on rank 1 mid-sweep and reports "
                         "recovery latency (break -> resumed stream); "
                         "'reconnect_storm' resets every "
                         "--fault-every-frames frames and reports "
                         "busbw degradation as interleaved "
                         "fault-vs-clean trials gated by the A/A null "
                         "test, like --ab")
    ap.add_argument("--fault-after-frames", type=int, default=50,
                    help="frames before the first injected reset "
                         "(default 50: past bootstrap + warmup)")
    ap.add_argument("--fault-every-frames", type=int, default=50,
                    help="reconnect_storm period in frames (default 50)")
    ap.add_argument("--fault-count", type=int, default=5,
                    help="reconnect_storm reset bound (default 5)")
    args = ap.parse_args(argv)

    if args.fault == "reset":
        # Recovery-latency measurement: one sweep with a single hard
        # RST injected on rank 1 mid-run. The sweep must complete
        # (healing is transparent); `recovery` reports the native
        # break-detect -> handshake+retransmit-done duration.
        from horovod_tpu.common.fault_injection import fault_env

        fenv = fault_env(1, "reset",
                         after_frames=args.fault_after_frames)
        run = run_sweep(args.np_, args.sizes, args.iters, args.warmup,
                        chunk_bytes=args.chunk_bytes, sg=args.sg,
                        fault=fenv, timeout=args.timeout)
        counters = run.get("counters", {})
        recovery = run.get("reconnect", {})
        healed = (counters.get("reconnects", 0) >= 1
                  and counters.get("reconnect_failures", 0) == 0)
        payload = {
            "mode": "fault",
            "fault": "reset",
            "np": args.np_,
            "fault_env": fenv,
            "healed": healed,
            "recovery": recovery,
            "results": run["results"],
            "counters": counters,
        }
        print("# reset injected after %d frames -> healed=%s "
              "recovery last=%.1fms max=%.1fms (reconnects=%d, "
              "frames retransmitted=%d)"
              % (args.fault_after_frames, healed,
                 recovery.get("last_heal_us", 0) / 1000.0,
                 recovery.get("max_heal_us", 0) / 1000.0,
                 counters.get("reconnects", 0),
                 counters.get("frames_retransmitted", 0)),
              file=sys.stderr)
        if not healed:
            print("# WARNING: no heal observed — sweep too short to "
                  "reach the injection point, or reconnect failed",
                  file=sys.stderr)
    elif args.fault == "reconnect_storm":
        # Busbw degradation under repeated blips, measured with the
        # same discipline as --ab: interleaved clean-vs-storm trials,
        # the A/A null test alongside, verdicts gated by the observed
        # slot bias (docs/benchmarks.md).
        from horovod_tpu.common.fault_injection import fault_env

        fenv = fault_env(1, "reconnect_storm",
                         after_frames=args.fault_after_frames,
                         every_frames=args.fault_every_frames,
                         count=args.fault_count)
        b_payloads = []
        per_size = run_gated_trials(
            args, {"fault": fenv}, "storm_median_ratio",
            "storm (B: %d resets every %d frames)"
            % (args.fault_count, args.fault_every_frames),
            collect_b=b_payloads)
        recovery = {
            "reconnects": max((b.get("counters", {}).get("reconnects", 0)
                               for b in b_payloads), default=0),
            "max_heal_us": max((b.get("reconnect", {}).get(
                "max_heal_us", 0) for b in b_payloads), default=0),
            "reconnect_failures": sum(
                b.get("counters", {}).get("reconnect_failures", 0)
                for b in b_payloads),
        }
        payload = {
            "mode": "fault",
            "fault": "reconnect_storm",
            "np": args.np_,
            "trials": args.trials,
            "fault_env": fenv,
            "recovery": recovery,
            "per_size": per_size,
        }
    elif args.ab:
        overrides = _parse_overrides(args.ab)
        payload = {
            "mode": "ab",
            "np": args.np_,
            "trials": args.trials,
            "b_overrides": overrides,
            "per_size": run_gated_trials(args, overrides,
                                         "ab_median_ratio",
                                         "A/B (B: %s)" % args.ab),
        }
    elif args.null_ab:
        payload = {
            "mode": "null_ab",
            "np": args.np_,
            "trials": args.trials,
            "per_size": run_paired_trials(args),
        }
        for s, row in sorted(payload["per_size"].items(), key=lambda kv:
                             int(kv[0])):
            print("# %10s A/A slot ratio median %.3f (trials: %s)"
                  % (s, row["median_ratio"],
                     " ".join("%.3f" % r for r in row["ratios"])),
                  file=sys.stderr)
    else:
        payload = run_sweep(args.np_, args.sizes, args.iters, args.warmup,
                            chunk_bytes=args.chunk_bytes, sg=args.sg,
                            timeout=args.timeout)
    doc = json.dumps(payload, indent=2, sort_keys=True)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
