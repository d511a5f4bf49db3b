"""Synthetic ResNet-50 training throughput benchmark.

TPU-native analog of the reference's headline harness
(reference: examples/pytorch/pytorch_synthetic_benchmark.py): synthetic
ImageNet-shaped data, forward+backward+SGD step, images/sec.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference's published illustrative throughput of 1656.82
images/sec on 16 Pascal GPUs (reference: docs/benchmarks.rst:38-42) =
103.55 images/sec/accelerator; vs_baseline is per-chip throughput divided
by that.

Architecture: the top-level process NEVER imports jax (a process that
has touched jax holds the chip). It spawns the actual benchmark as a
child in its own process group with a hard timeout, so a backend that
hangs inside PJRT init, where no Python-level timeout can fire, costs a
bounded wait before the child group is SIGKILLed. There is no fallback:
when the child finds another platform than the one asked for
(``--backend``, default ``tpu``), fails or times out, this exits
non-zero and says why on stderr. ``--backend cpu`` is a harness dry
run for the control flow; pass small sizes with it, and read none of
its numbers as a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_ACCEL = 1656.82 / 16.0

# Analytic forward-pass FLOPs per image at 224x224 (multiply-add = 2
# FLOPs; the standard published counts). Training step = 3x forward
# (forward + ~2x backward). Scaled by (image_size/224)^2 for other
# resolutions (conv FLOPs scale with spatial area).
RESNET_FWD_FLOPS_224 = {
    "resnet18": 1.82e9, "resnet34": 3.67e9, "resnet50": 4.09e9,
    "resnet101": 7.85e9, "resnet152": 11.58e9,
}

# Peak dense bf16 FLOP/s, keyed by the exact ``jax.Device.device_kind``.
# A kind that is not listed is an error, not a default.
CHIP_PEAK_BF16 = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197e12,
}


def chip_peak_flops(device_kind: str) -> float:
    try:
        return CHIP_PEAK_BF16[device_kind]
    except KeyError:
        raise KeyError(
            "device_kind %r is not in bench.CHIP_PEAK_BF16 (known: %s); "
            "add it with the source of its peak"
            % (device_kind, sorted(CHIP_PEAK_BF16))) from None


def _mfu(achieved_flops_per_sec, platform: str, device_kind: str):
    """Model FLOPs utilization: analytic model FLOP/s over the chip's
    published bf16 peak. None on the ``--backend cpu`` dry run, which
    has no device metric."""
    if platform == "cpu":
        return None
    return round(achieved_flops_per_sec / chip_peak_flops(device_kind), 4)


# --------------------------------------------------------------------------
# Child: the real benchmark. Only ever run with a parent supervising it.
# --------------------------------------------------------------------------

def _timed_loop(step_fn, carry, warmup, iters):
    """Shared timing harness: run ``step_fn(carry) -> tuple`` (last
    element = loss) ``warmup`` then ``iters`` times; return (carry,
    seconds) for the timed portion, which ends when the device does."""
    import jax

    for _ in range(warmup):
        carry = step_fn(carry)[:-1]
    jax.block_until_ready(carry)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step_fn(carry)
        carry = out[:-1]
    jax.block_until_ready(out)
    return carry, time.perf_counter() - t0


def _bench_resnet(args, platform, device_kind):
    import jax
    import jax.numpy as jnp
    import optax
    from functools import partial

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import models

    model_cls = {"resnet18": models.ResNet18, "resnet34": models.ResNet34,
                 "resnet50": models.ResNet50, "resnet101": models.ResNet101,
                 "resnet152": models.ResNet152}[args.model]
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(
        rng, (args.batch_size, args.image_size, args.image_size, 3),
        jnp.bfloat16)
    labels = jax.random.randint(rng, (args.batch_size,), 0, 1000)

    variables = model.init(jax.random.PRNGKey(1), images, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    tx = hvd_jax.DistributedOptimizer(optax.sgd(0.05, momentum=0.9))
    opt_state = tx.init(params)

    def loss_fn(params, batch_stats, images, labels):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            images, train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updates["batch_stats"]

    def _step(params, batch_stats, opt_state, images, labels):
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, batch_stats, opt_state, jnp.float32(loss)

    # Donating params/batch_stats/opt_state lets XLA update weights in
    # place instead of allocating fresh buffers every step — HBM
    # bandwidth is the constraint, not FLOPs.
    if args.steps_per_call > 1:
        # Amortize dispatch latency: run several optimizer steps
        # inside one executable (compiler-friendly fori_loop).
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step(params, batch_stats, opt_state, images, labels):
            def body(_, carry):
                p, bs, os_, _ = carry
                return _step(p, bs, os_, images, labels)
            return jax.lax.fori_loop(
                0, args.steps_per_call, body,
                (params, batch_stats, opt_state, jnp.float32(0)))
    else:
        train_step = partial(jax.jit, donate_argnums=(0, 1, 2))(_step)

    _, dt = _timed_loop(
        lambda c: train_step(*c, images, labels),
        (params, batch_stats, opt_state), args.warmup, args.iters)

    img_per_sec = (args.batch_size * args.iters
                   * max(args.steps_per_call, 1) / dt)
    train_flops_per_img = (3.0 * RESNET_FWD_FLOPS_224[args.model]
                           * (args.image_size / 224.0) ** 2)
    return {
        "metric": "%s_images_per_sec_per_chip" % args.model,
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip (%s, bs=%d, bf16)" % (device_kind,
                                                       args.batch_size),
        "vs_baseline": round(
            img_per_sec / BASELINE_IMG_PER_SEC_PER_ACCEL, 3),
        "mfu": _mfu(img_per_sec * train_flops_per_img, platform,
                    device_kind),
        "flops_model": "3 x %.2fe9 fwd-FLOPs/img (analytic, %dpx)" % (
            RESNET_FWD_FLOPS_224[args.model] / 1e9, args.image_size),
    }


def _bench_transformer(args, platform, device_kind, long_context=False,
                       big=False):
    """Flagship decoder-only transformer causal-LM step, tokens/sec.

    ``long_context=True`` benches the long-sequence configuration
    (seq 2048, Pallas flash attention — measured 1.5x the XLA dense
    path at this length on v5e; at seq 512 dense wins, so each length
    uses its best kernel).

    ``big=True`` benches a GPT-2-small-scale decoder (d_model 768,
    12 layers, 12 heads, ~124M params, seq 1024): the larger matmuls
    keep the MXU busier than the 17M-param flagship, so this is the
    configuration that shows the framework's MFU ceiling rather than
    dispatch overhead.

    MFU uses the standard analytic count: 6 * n_params FLOPs per token
    for the parameter matmuls (fwd + bwd) plus the 12 * L * S * d_model
    attention term.
    """
    import dataclasses
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    import __graft_entry__ as graft
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.models import Transformer

    cfg = graft._flagship_config()
    batch, seq = args.tf_batch, args.tf_seq
    iters, warmup, steps_per_call = (args.iters, args.warmup,
                                     args.steps_per_call)
    metric_name = "transformer_tokens_per_sec_per_chip"
    if big:
        metric_name = "transformer_big_tokens_per_sec_per_chip"
        cfg = dataclasses.replace(
            cfg, vocab_size=32000, d_model=768, n_heads=12,
            n_layers=12, d_ff=3072, max_seq_len=1024)
        batch, seq = 8, 1024
        iters, steps_per_call = max(iters // 2, 4), 10
    elif long_context:
        metric_name = "transformer_long_tokens_per_sec_per_chip"
        batch, seq = 4, 2048
        iters, steps_per_call = max(iters // 2, 4), 10
        cfg = dataclasses.replace(cfg, max_seq_len=seq,
                                  attention="flash")

    model = Transformer(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    n_params = sum(v.size for v in jax.tree_util.tree_leaves(params))

    tx = hvd_jax.DistributedOptimizer(optax.adamw(1e-3))
    opt_state = tx.init(params)

    def loss_fn(params, tokens):
        logits = model.apply(params, tokens)
        targets = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    def _step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jnp.float32(loss)

    if steps_per_call > 1:
        @partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, tokens):
            def body(_, carry):
                p, s, _ = carry
                return _step(p, s, tokens)
            return jax.lax.fori_loop(
                0, steps_per_call, body,
                (params, opt_state, jnp.float32(0)))
    else:
        train_step = partial(jax.jit, donate_argnums=(0, 1))(_step)

    _, dt = _timed_loop(
        lambda c: train_step(*c, tokens),
        (params, opt_state), warmup, iters)

    tokens_per_sec = batch * seq * iters * steps_per_call / dt
    flops_per_token = (6.0 * n_params
                       + 12.0 * cfg.n_layers * seq * cfg.d_model)
    dtype_name = jnp.dtype(cfg.dtype).name
    return {
        "metric": metric_name,
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec/chip (%s, %.1fM params, bs=%d, seq=%d, %s)"
                % (device_kind, n_params / 1e6, batch, seq, dtype_name),
        "vs_baseline": None,  # the reference publishes no LM baseline
        "mfu": _mfu(tokens_per_sec * flops_per_token, platform,
                    device_kind),
        "flops_model": "(6 x %.1fM + 12*L*S*d) FLOPs/token (analytic)"
                       % (n_params / 1e6),
    }


def _perf_config():
    """In-graph perf knobs + tuner state, embedded in the result JSON.

    Recording the exact tile configuration a result was
    measured under is what lets a later run prove (or falsify) a delta
    against it (docs/mfu.md).
    """
    from horovod_tpu.ops import block_tuner
    from horovod_tpu.utils import metrics

    snap = metrics.REGISTRY.snapshot()

    def _total(family):
        fam = snap.get(family) or {}
        return sum(v.get("value", 0) for v in fam.get("values", []))

    from horovod_tpu.utils import online_tuner

    tuner = online_tuner.online_tuner()
    return {
        "flash_tune_mode": block_tuner.tune_mode() or "off",
        "flash_block_q_env": os.environ.get("HVD_FLASH_BLOCK_Q"),
        "flash_block_k_env": os.environ.get("HVD_FLASH_BLOCK_K"),
        "flash_tuned": block_tuner.tuned_snapshot(),
        "hvd_flash_tuner_trials_total": _total(
            "hvd_flash_tuner_trials_total"),
        # Online-tuner movement (docs/autotune.md): final knob state +
        # the full decision trajectory, so a capture records what the
        # tuner did, not just where it ended.
        "tune": {
            "mode": online_tuner.tune_mode() or "off",
            "state": tuner.state() if tuner is not None else None,
            "trajectory": tuner.trajectory() if tuner is not None
            else None,
        },
    }


def run_child(args) -> int:
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.backend == "cpu":
        jax.config.update("jax_platforms", "cpu")

    # Claim the device FIRST, before any framework machinery — if the
    # backend is unavailable this raises (or hangs, and the parent's
    # timeout handles it) without leaving hvd state behind.
    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    if platform != args.backend:
        print("bench.py: --backend %s, but jax found platform %r "
              "(device_kind %r, %d device(s)); not benchmarking it"
              % (args.backend, platform, device_kind, len(devices)),
              file=sys.stderr)
        return 1
    if platform != "cpu":
        chip_peak_flops(device_kind)  # unknown chip: fail before the work

    hvd.init()

    # HVD_TUNE (the --tune flag exports it): run the online tuner for
    # the duration of the benchmark; _perf_config embeds its decision
    # trajectory in the result JSON.
    from horovod_tpu.utils.online_tuner import start_online_tuner

    start_online_tuner(role="training")

    # Parent always resolves --workloads; the fallback covers a direct
    # --child invocation (debugging).
    workloads_str = args.workloads or (
        "resnet50,transformer" if args.model == "resnet50" else args.model)
    entries = []
    for workload in workloads_str.split(","):
        workload = workload.strip()
        if not workload:
            continue
        if workload == "transformer":
            entries.append(_bench_transformer(args, platform, device_kind))
        elif workload == "transformer_long":
            entries.append(_bench_transformer(args, platform, device_kind,
                                              long_context=True))
        elif workload == "transformer_big":
            entries.append(_bench_transformer(args, platform, device_kind,
                                              big=True))
        else:
            wl_args = argparse.Namespace(**vars(args))
            wl_args.model = workload
            entries.append(_bench_resnet(wl_args, platform, device_kind))
        entries[-1]["platform"] = platform
        entries[-1]["device_kind"] = device_kind
        if platform == "cpu":
            # The dry run's numbers never carry a device metric's name.
            entries[-1]["metric"] = "cpu_dryrun_" + entries[-1]["metric"]

    if not entries:
        print("bench.py: no workloads requested: %r" % workloads_str,
              file=sys.stderr)
        return 1
    headline = dict(entries[0])
    if len(entries) > 1:
        headline["entries"] = entries
    headline["perf_config"] = _perf_config()
    print(json.dumps(headline))
    return 0


# --------------------------------------------------------------------------
# Parent: bounded-time supervisor; never imports jax.
# --------------------------------------------------------------------------

def _spawn(argv_extra, timeout_s):
    """Run this script as a --child in its own process group; return
    (last_json_dict_or_None, diagnostic_tail:str)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + argv_extra
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True, env=env)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
        return None, "timeout after %ds (backend hang?)" % timeout_s
    parsed = _last_metric_json(out)
    if proc.returncode == 0 and parsed is not None:
        return parsed, ""
    lines = [ln for ln in (out or "").strip().splitlines() if ln.strip()]
    return None, "rc=%d tail=%r" % (proc.returncode, lines[-8:])


def _flightrec_dumps(since):
    """Flight-record dump files written after ``since`` (a dying
    child's abort/SIGTERM dump, docs/flightrec.md), named in the
    failure message so the post-mortem starts from them."""
    directory = os.environ.get("HVD_FLIGHTREC_DIR") or "."
    found = []
    try:
        for fn in sorted(os.listdir(directory)):
            if fn.startswith("flightrec.rank") and fn.endswith(".jsonl"):
                path = os.path.join(directory, fn)
                if os.path.getmtime(path) >= since - 1.0:
                    found.append(path)
    except OSError:
        pass
    return found


def _last_metric_json(text):
    """Last line of ``text`` that parses as a result dict, or None.

    This is the output contract between the supervisor and its child
    (and between bench.py and external harnesses): the result is the
    final JSON object line carrying a "metric" key.
    """
    for ln in reversed((text or "").strip().splitlines()):
        try:
            parsed = json.loads(ln)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            return parsed
    return None


def main():
    run_started = time.time()
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true",
                   help="(internal) run the benchmark in-process")
    p.add_argument("--backend", choices=["tpu", "cpu"], default="tpu",
                   help="The platform jax must find; any other fails. "
                        "'cpu' is a harness dry run (pass small sizes), "
                        "never a device measurement.")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--model", default="resnet50",
                   help="(legacy alias) single resnet workload; prefer "
                        "--workloads")
    p.add_argument("--workloads", default=None,
                   help="Comma list of benchmark workloads, run in order; "
                        "first is the headline metric. "
                        "resnet18/34/50/101/152, transformer, "
                        "transformer_big (GPT-2-small scale, ~124M params), "
                        "or transformer_long "
                        "(seq 2048, flash attention). Default: "
                        "'resnet50,transformer', or just --model when "
                        "that legacy flag names a different resnet.")
    p.add_argument("--tf-batch", type=int, default=16,
                   help="Transformer workload batch size.")
    p.add_argument("--tf-seq", type=int, default=512,
                   help="Transformer workload sequence length.")
    p.add_argument("--steps-per-call", type=int, default=30,
                   help="Optimizer steps fused into one executable "
                        "(amortizes dispatch latency).")
    p.add_argument("--timeout", type=int,
                   default=int(os.environ.get("HVD_BENCH_TIMEOUT", "600")),
                   help="Hard wall-clock budget for the accelerator "
                        "child process.")
    p.add_argument("--tune-flash", action="store_true",
                   help="Export HVD_FLASH_TUNE=1 to the benchmark "
                        "child: flash-attention workloads autotune "
                        "their VMEM tiles on first call and journal "
                        "the winners (docs/mfu.md).")
    p.add_argument("--tune", action="store_true",
                   help="Export HVD_TUNE=1 to the benchmark child: the "
                        "online tuner (docs/autotune.md) runs during "
                        "the benchmark and its decision trajectory is "
                        "embedded in the result JSON "
                        "(perf_config.tune) so a result records "
                        "tuned-vs-default movement.")
    args = p.parse_args()
    # Perf-knob flags are plain env exports so the supervised child
    # inherits them without plumbing.
    if args.tune_flash:
        os.environ["HVD_FLASH_TUNE"] = "1"
    if args.tune:
        os.environ.setdefault("HVD_TUNE", "1")
        # Bench runs are short; a 30 s window would never complete a
        # round. Users can still override explicitly.
        os.environ.setdefault("HVD_TUNE_WINDOW_SEC", "5")
    # iters=0 would divide by zero; negative warmup is meaningless.
    args.iters = max(args.iters, 1)
    args.warmup = max(args.warmup, 0)

    if args.child:
        return run_child(args)

    # Resolve the workload list: an explicit --workloads wins verbatim;
    # otherwise the legacy --model alias keeps its one-workload meaning
    # (no silent transformer run inside the same --timeout budget).
    if args.workloads is not None:
        workloads = args.workloads
    elif args.model != "resnet50":
        workloads = args.model
    else:
        workloads = "resnet50,transformer"
    if not [w for w in workloads.split(",") if w.strip()]:
        p.error("no workloads requested: %r" % workloads)
    result, diag = _spawn(
        ["--backend", args.backend,
         "--batch-size", str(args.batch_size),
         "--image-size", str(args.image_size),
         "--warmup", str(args.warmup),
         "--iters", str(args.iters),
         "--model", args.model,
         "--workloads", workloads,
         "--tf-batch", str(args.tf_batch),
         "--tf-seq", str(args.tf_seq),
         "--steps-per-call", str(args.steps_per_call)],
        args.timeout)
    if result is None:
        print("bench.py: no result from the %s child: %s"
              % (args.backend, diag), file=sys.stderr)
        dumps = _flightrec_dumps(run_started)
        if dumps:
            print("bench.py: flight-record dumps: %s" % ", ".join(dumps),
                  file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
