"""chip_smoke.py -- the quickest proof that horovod_tpu still starts on the chip.

    python3 chip_smoke.py

drives the training path once through the entry points a user calls, at
the full width of the models the repo defines (depth as defined, weights
random from a seed), on whatever TPU host it is started on, and exits 0
only if every leg passed on ``platform == "tpu"``. There is no CPU mode:
with no accelerator it exits non-zero and names the platform it found.

A chip belongs to one process at a time, so this parent never imports
jax. It runs, one after another, each in a process group of its own and
under a wall-clock limit:

1. ``--legs``: ONE child that drives every chip of the host --
   leg 1 *device* (all TPU, a kind ``benchmark/peaks.json`` lists),
   leg 2 *resnet50* (``DistributedOptimizer``, donated plain-jit step),
   leg 3 *transformer_flash* (the Pallas kernel against the float32
   dense reference, then the d_model-768 decoder with it compiled in),
   leg 4 *plan_all_chips* (the same decoder through ``hvd.plan()`` on
   every chip, checked against leg 3's losses);
2. ``__graft_entry__.dryrun_multichip(n)`` in its default
   partial-manual mode on the real devices (two or more chips);
3. leg 5 *hvdrun*: ``python -m horovod_tpu.runner -np <chips>
   --platform tpu python chip_smoke.py --worker`` -- one process per
   chip, the native core built from ``core/src``, gradients crossing
   ``io_callback`` into the TCP ring.

On success stdout holds two lines. The first is ``SMOKE_SUMMARY {json}``:
the versions, the compile-cache directory and each leg's observations;
compile and step seconds in it are SMOKE OBSERVATIONS of one cold or
warm run, not benchmark numbers. The last is the verdict and nothing
else, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``, the device as jax reports it. Everything else goes to
stderr; a failing run prints nothing on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "SMOKE_RESULT "    # child -> parent
SUMMARY_TAG = "SMOKE_SUMMARY "  # parent's stdout, before the verdict

# Wall-clock limits; together they stay inside the 1200 s the whole
# command is allowed, compilation included.
LEGS_LIMIT_S = 660
DRYRUN_LIMIT_S = 180
HVDRUN_LIMIT_S = 300

# A decoder of GPT-2-small's scale (d_model 768, 12 heads, 12 layers)
# with the flash kernel at a 2048-token context, and the reference's
# headline ResNet-50 batch.
SIZES = dict(
    resnet_batch=128, image_size=224,
    vocab_size=32000, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
    seq_len=2048, batch=4,
    # flash kernel vs dense reference: (B, H, D) and the two lengths
    # (one tile-aligned, one ragged).
    kernel_bhd=(2, 12, 64), kernel_seqs=(2048, 1000),
    steps=4,
)

# Stated tolerances. Kernel vs float32 dense reference (reference at
# "highest" matmul precision): max abs error over the reference's max
# abs value, forward and each of dq/dk/dv. The kernel's dots run at
# Mosaic's default precision, so it sits where XLA's own
# default-precision dense path does (reported beside it as
# ``xla_default``: 4e-3 forward, 5e-3 gradients on a v5 lite, the
# kernel 3e-3 and 5e-3); a wrong mask, scale or tile shows up at 1e-1
# and more.
KERNEL_REL_TOL = 1e-2
# One-chip vs all-chip loss after each step, same global batch and
# seed, bf16 activations: the two differ only in the order the batch
# is reduced in, which AdamW's normalised update amplifies a little
# from step 2 on.
LOSS_REL_TOL = 5e-3


def log(msg: str) -> None:
    print("[chip_smoke] " + msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Parent: JAX-free supervisor.
# --------------------------------------------------------------------------

def _run(cmd, limit_s):
    """Run ``cmd`` from the checkout in a process group of its own,
    echoing its output to stderr. Returns (returncode or None on
    timeout, output lines). The whole group is gone when this returns:
    SIGTERM first so hvdrun's handler reaps its slots (they run in
    sessions of their own), then SIGKILL."""
    proc = subprocess.Popen(
        cmd, cwd=HERE, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True,
        env=dict(os.environ, PYTHONUNBUFFERED="1"))
    lines = []

    def pump():
        for line in proc.stdout:
            sys.stderr.write(line)
            lines.append(line.rstrip("\n"))

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    rc = None
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        log("TIMEOUT after %d s: %s" % (limit_s, " ".join(cmd)))
    finally:
        for sig, grace in ((signal.SIGTERM, 10), (signal.SIGKILL, 5)):
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                break
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
    reader.join(timeout=5)
    return rc, lines


def _tagged_result(lines):
    """The last ``SMOKE_RESULT {json}`` a child printed (hvdrun prefixes
    worker lines, so the tag may sit mid-line), or None."""
    for line in reversed(lines):
        at = line.find(RESULT_TAG)
        if at >= 0:
            return json.loads(line[at + len(RESULT_TAG):])
    return None


def verdict_line(device) -> str:
    """The last stdout line of a passing run: exactly ``ok`` and
    ``device`` = ``platform``/``kind``/``count``. What it leaves out is
    in the summary line above it."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": int(device["count"])}})


def parent_main() -> int:
    me = os.path.join(HERE, "chip_smoke.py")
    summary = {"ok": False}

    rc, lines = _run([sys.executable, me, "--legs"], LEGS_LIMIT_S)
    result = _tagged_result(lines)
    if result is not None:
        summary.update(result)
    legs = summary.setdefault("legs", {})
    if rc != 0 or result is None:
        legs["legs_child"] = {"ok": False, "returncode": rc,
                              "result": result is not None}
    n_chips = (summary.get("device") or {}).get("count", 0)

    def passing():
        return all(leg.get("ok") for leg in legs.values())

    if passing() and n_chips >= 2:
        # Partial-manual shard_map (data manual, model auto) on the
        # real devices, in a process of its own: a partitioner CHECK
        # would abort the process, not raise.
        t0 = time.time()
        rc, lines = _run(
            [sys.executable, "-c",
             "import __graft_entry__ as g; g.dryrun_multichip(%d)"
             % n_chips], DRYRUN_LIMIT_S)
        ok = rc == 0 and any("dryrun_multichip OK" in ln for ln in lines)
        legs["dryrun_multichip_partial_manual"] = {
            "ok": ok, "returncode": rc, "devices": n_chips,
            "seconds": round(time.time() - t0, 1),
            "tail": lines[-1] if lines else ""}

    if passing():
        # Built from what git would commit: the workers must build the
        # native core from core/src, not trust a library left on disk.
        shutil.rmtree(os.path.join(HERE, "horovod_tpu", "core", "build"),
                      ignore_errors=True)
        rc, lines = _run(
            [sys.executable, "-m", "horovod_tpu.runner",
             "-np", str(n_chips), "--platform", "tpu",
             sys.executable, me, "--worker"], HVDRUN_LIMIT_S)
        result = _tagged_result(lines) or {"ok": False}
        result["returncode"] = rc
        result["ok"] = bool(result.get("ok")) and rc == 0
        legs["hvdrun"] = result

    summary["ok"] = passing()
    line = json.dumps(summary)
    if not summary["ok"]:
        log("FAILED: " + line)
        return 1
    print(SUMMARY_TAG + line)
    print(verdict_line(summary["device"]), flush=True)
    return 0


# --------------------------------------------------------------------------
# Children. Everything below imports jax and may hold the chip.
# --------------------------------------------------------------------------

def chip_peak_flops(device_kind: str) -> float:
    """The chip's published dense bf16 FLOP/s from the benchmark's own
    table, keyed by the exact ``jax.Device.device_kind``. A kind the
    table lacks is an error, not a default."""
    with open(os.path.join(HERE, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    if device_kind not in peaks:
        raise KeyError(
            "device_kind %r is not in benchmark/peaks.json (known: %s); "
            "add it with the source of its peak"
            % (device_kind, sorted(peaks)))
    return peaks[device_kind]["bf16_flops"]


def _check_devices():
    """Leg 1: every device jax finds (no platform forced in code) is a
    TPU of a kind the peak table lists."""
    import jax

    devices = jax.devices()
    found = sorted({d.platform for d in devices})
    if found != ["tpu"]:
        raise SystemExit(
            "chip_smoke: jax found platform %s (device_kind %r, %d "
            "device(s)); this smoke passes only on a TPU"
            % ("/".join(found), devices[0].device_kind, len(devices)))
    kind = devices[0].device_kind
    chip_peak_flops(kind)  # raises on a kind the table lacks
    return devices


def _versions():
    from importlib import metadata

    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu")}


def _bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def _cache_entries(cache_dir) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def _compile_and_run(step, args, n_steps, n_carry):
    """Lower + compile ``step`` (a jitted fn whose first ``n_carry``
    outputs feed its first ``n_carry`` inputs and whose last output is
    the loss), then take ``n_steps`` steps. Returns (observations,
    the lowered step, final carry, the loss of each step)."""
    import jax

    t0 = time.perf_counter()
    lowered = step.lower(*args)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    carry, rest = tuple(args[:n_carry]), tuple(args[n_carry:])
    losses = []
    out = compiled(*carry, *rest)
    carry, loss = out[:n_carry], out[-1]
    losses.append(loss)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n_steps - 1):
        out = compiled(*carry, *rest)
        carry = out[:n_carry]
        losses.append(out[-1])
    jax.block_until_ready(out)
    step_s = (time.perf_counter() - t0) / max(n_steps - 1, 1)
    obs = {"compile_seconds": round(compile_s, 2),
           "seconds_per_step": round(step_s, 4),
           "steps": n_steps}
    return obs, lowered, carry, losses


def _check_losses(losses):
    import numpy as np

    losses = [float(np.mean(np.asarray(x))) for x in losses]
    if not all(np.isfinite(losses)):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if abs(losses[-1] - losses[0]) < 1e-4:
        raise AssertionError("loss did not move: %r" % (losses,))
    return losses


def leg_resnet(sz):
    """Leg 2: ResNet-50 through DistributedOptimizer on one chip, the
    step of examples/jax/jax_synthetic_benchmark.py."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import models

    hvd.init()
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    b, px = sz["resnet_batch"], sz["image_size"]
    images = jax.random.normal(jax.random.PRNGKey(hvd.rank()),
                               (b, px, px, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(1), (b,), 0, 1000)
    variables = jax.jit(partial(model.init, train=True))(
        jax.random.PRNGKey(0), images)
    params, batch_stats = variables["params"], variables["batch_stats"]
    params = hvd_jax.broadcast_parameters(params, root_rank=0)
    tx = hvd_jax.DistributedOptimizer(
        optax.sgd(0.01 * hvd.size(), momentum=0.9))
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p, bs):
            logits, updates = model.apply(
                {"params": p, "batch_stats": bs}, images, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(), updates["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), batch_stats,
                opt_state, jnp.float32(loss))

    obs, _, carry, losses = _compile_and_run(
        train_step, (params, batch_stats, opt_state, images, labels),
        sz["steps"], 3)
    losses = _check_losses(losses)
    obs.update(batch=b, image_size=px, first_loss=losses[0],
               last_loss=losses[-1])
    return obs, carry[0]


def _kernel_vs_dense(sz):
    """flash_attention forward and dq/dk/dv against
    _dense_causal_attention in float32, on this backend."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import _dense_causal_attention
    from horovod_tpu.ops.pallas_attention import flash_attention

    b, h, d = sz["kernel_bhd"]
    report = {}
    for s in sz["kernel_seqs"]:
        keys = jax.random.split(jax.random.PRNGKey(s), 4)
        q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
                      for kk in keys)

        def flash(q, k, v):
            out = flash_attention(q, k, v, causal=True)
            return jnp.sum(out * w), out

        def dense(q, k, v):
            out = _dense_causal_attention(q, k, v, jnp.float32)
            return jnp.sum(out * w), out

        def run(f):
            grads, out = jax.jit(
                jax.grad(f, (0, 1, 2), has_aux=True))(q, k, v)
            return (out,) + grads

        def rel_err(got, ref):
            return {name: float(jnp.max(jnp.abs(g - r))
                                / jnp.max(jnp.abs(r)))
                    for name, g, r in zip(("out", "dq", "dk", "dv"),
                                          got, ref)}

        with jax.default_matmul_precision("highest"):
            ref = run(dense)
        errs = rel_err(run(flash), ref)
        report["S=%d" % s] = {"flash": errs,
                              "xla_default": rel_err(run(dense), ref)}
        bad = {n: e for n, e in errs.items()
               if not e <= KERNEL_REL_TOL}
        if bad:
            raise AssertionError(
                "flash_attention vs float32 dense at S=%d: %r exceeds "
                "%g" % (s, bad, KERNEL_REL_TOL))
    return report


def _decoder(sz):
    """The decoder and its causal-LM loss."""
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import Transformer, TransformerConfig

    model = Transformer(TransformerConfig(
        vocab_size=sz["vocab_size"], d_model=sz["d_model"],
        n_heads=sz["n_heads"], n_layers=sz["n_layers"], d_ff=sz["d_ff"],
        max_seq_len=sz["seq_len"], dtype=jnp.bfloat16, attention="flash"))

    def loss_fn(params, tokens):
        logits = model.apply(params, tokens)
        targets = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    return model, loss_fn


def leg_transformer_flash(sz):
    """Leg 3: the kernel against the dense reference, then the decoder
    with the kernel compiled in, AdamW through DistributedOptimizer on
    one chip. Returns what leg 4 compares against."""
    from functools import partial

    import jax
    import optax
    from flax.core import meta

    import horovod_tpu.jax as hvd_jax

    obs = {"kernel_rel_err": _kernel_vs_dense(sz),
           "kernel_rel_tol": KERNEL_REL_TOL}
    model, loss_fn = _decoder(sz)
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (sz["batch"], sz["seq_len"]), 0,
        sz["vocab_size"])
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(1), tokens))
    params_host = jax.device_get(params)  # the step donates params
    tx = hvd_jax.DistributedOptimizer(optax.adamw(1e-3))
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    run, lowered, _, losses = _compile_and_run(
        step, (params, opt_state, tokens), sz["steps"], 2)
    # The kernel was COMPILED by Mosaic, not interpreted: the lowered
    # step carries its custom call.
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    if jax.default_backend() == "tpu" and not mosaic_calls:
        raise AssertionError("no tpu_custom_call in the lowered step")
    losses = _check_losses(losses)
    obs.update(run, batch=list(tokens.shape), mosaic_custom_calls=mosaic_calls,
               n_params=sum(x.size for x in jax.tree.leaves(params_host)),
               losses=losses, first_loss=losses[0], last_loss=losses[-1])
    return obs, (loss_fn, params_host, tokens, losses)


def leg_plan_all_chips(sz, reference):
    """Leg 4: the same decoder, seed and global batch on every chip of
    the host through hvd.plan() -> Plan.apply/optimizer/shard_map."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.jax import introspect

    loss_fn, params_host, tokens, ref_losses = reference
    n = jax.device_count()
    plan = hvd.plan(params_host, batch=sz["batch"], seq_len=sz["seq_len"],
                    d_model=sz["d_model"], n_layers=sz["n_layers"])
    log("plan: " + plan.summary())
    obs = {"plan": plan.summary(), "devices": n}
    if plan.mesh_axes != {"data": n}:
        # Recorded for ROADMAP S7; the smoke pins the data-parallel
        # layout whose result it can check against one chip.
        obs["planner_own_choice"] = dict(plan.mesh_axes)
        plan = hvd.plan(params_host, batch=sz["batch"],
                        seq_len=sz["seq_len"], d_model=sz["d_model"],
                        n_layers=sz["n_layers"], require_axes={"data": n})
    mesh = plan.apply()
    tx = plan.optimizer(optax.adamw(1e-3))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        # Per-shard loss out, averaged on the host: the only psums in
        # the traced step are then the framework's own.
        return optax.apply_updates(params, updates), opt_state, loss[None]

    data_spec = plan.batch_spec(2)
    sharded = plan.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), data_spec),
        out_specs=(P(), P(), P(data_spec[0])))
    replicated = NamedSharding(mesh, P())
    params = jax.device_put(params_host, replicated)
    opt_state = jax.device_put(tx.init(params_host), replicated)
    tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))

    # (a) the framework's collectives are in the traced step.
    obs["collectives"] = introspect.assert_in_graph_gradient_sync(
        sharded, params, opt_state, tokens, required=("psum",))
    # (b) parameters and batch shards sit on n distinct devices.
    for name, arr in (("params", jax.tree.leaves(params)[0]),
                      ("tokens", tokens)):
        on = {s.device for s in arr.addressable_shards}
        if len(on) != n:
            raise AssertionError(
                "%s shards sit on %d device(s), want %d" % (name, len(on), n))
    shard_shape = tokens.addressable_shards[0].data.shape
    if shard_shape != (sz["batch"] // n, sz["seq_len"]):
        raise AssertionError("token shard shape %r" % (shard_shape,))

    run, lowered, _, losses = _compile_and_run(
        jax.jit(sharded, donate_argnums=(0, 1)),
        (params, opt_state, tokens), sz["steps"], 2)
    losses = _check_losses(losses)
    in_use = [_bytes_in_use(d) for d in jax.devices()]
    if not all(in_use):
        raise AssertionError("a device holds nothing: %r" % (in_use,))
    # (c) the average over the interconnect is right: the losses equal
    # the one-chip run of the same global batch and seed.
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    if not max(rel) <= LOSS_REL_TOL:
        raise AssertionError(
            "all-chip losses %r vs one-chip %r: rel diff %r exceeds %g"
            % (losses, ref_losses, rel, LOSS_REL_TOL))
    obs.update(run,
               mosaic_custom_calls=lowered.as_text().count("tpu_custom_call"),
               bytes_in_use=in_use, losses=losses,
               first_loss=losses[0], last_loss=losses[-1],
               loss_rel_diff_vs_one_chip=[float(np.float32(r)) for r in rel],
               loss_rel_tol=LOSS_REL_TOL)
    return obs, None


def legs_main() -> int:
    """Legs 1-4 in one process (it holds every chip of the host)."""
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = _cache_entries(cache_dir)
    devices = _check_devices()
    summary = {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "versions": _versions(),
        "note": "compile/step seconds are smoke observations, "
                "not benchmark numbers",
        "legs": {"device": {"ok": True}},
    }
    legs = summary["legs"]

    def run_leg(name, fn, *args):
        """Run one leg; returns what it hands to a later leg."""
        log("leg %s ..." % name)
        t0 = time.time()
        handed = None
        try:
            obs, handed = fn(SIZES, *args)
            obs["ok"] = True
        except Exception:  # leg boundary: record, report, fail the run
            traceback.print_exc()
            obs = {"ok": False,
                   "error": traceback.format_exc().strip().splitlines()[-1]}
        obs["leg_seconds"] = round(time.time() - t0, 1)
        legs[name] = obs
        log("leg %s: %s" % (name, json.dumps(obs)))
        return handed

    run_leg("resnet50", leg_resnet)  # its parameters are dropped here
    reference = run_leg("transformer_flash", leg_transformer_flash)
    if reference is not None:
        run_leg("plan_all_chips", leg_plan_all_chips, reference)
    else:
        legs["plan_all_chips"] = {
            "ok": False, "error": "needs leg transformer_flash's losses"}
    summary["compile_cache"] = {
        "dir": cache_dir, "entries_before": entries_before,
        "entries_after": _cache_entries(cache_dir)}
    print(RESULT_TAG + json.dumps(summary), flush=True)
    return 0 if all(leg["ok"] for leg in legs.values()) else 1


def worker_main() -> int:
    """Leg 5 worker, one per chip under hvdrun: build the core, take
    ResNet-50 steps with the gradients crossing io_callback into the
    TCP ring, then agree on the result."""
    import numpy as np

    from horovod_tpu.core.build import library_path

    t0 = time.time()
    library_path()  # builds from core/src; raises with the compiler's message
    build_s = time.time() - t0

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = _check_devices()
    if len(devices) != 1:
        raise SystemExit("hvdrun worker sees %d TPU devices, want exactly "
                         "one: %r" % (len(devices), devices))

    import jax

    import horovod_tpu as hvd

    obs, params = leg_resnet(SIZES)  # hvd.init() is its first call
    checksum = float(sum(np.abs(np.asarray(x, np.float64)).sum()
                         for x in jax.tree.leaves(params)))
    mine = {"rank": hvd.rank(), "local_rank": hvd.local_rank(),
            "visible_chip": os.environ.get("TPU_VISIBLE_CHIPS"),
            "device": str(devices[0]), "checksum": checksum,
            "core_build_seconds": round(build_s, 1),
            "bytes_in_use": _bytes_in_use(devices[0])}
    everyone = hvd.allgather_object(mine)
    ok = True
    if hvd.rank() == 0:
        chips = [w["visible_chip"] for w in everyone]
        sums = {w["checksum"] for w in everyone}
        if hvd.size() > 1 and len(set(chips)) != hvd.size():
            log("workers do not hold distinct chips: %r" % (chips,))
            ok = False
        if len(sums) != 1:
            log("parameter checksums differ across ranks: %r" % (sums,))
            ok = False
        obs.update(ok=ok, world_size=hvd.size(), workers=everyone)
        print(RESULT_TAG + json.dumps(obs), flush=True)
    hvd.shutdown()
    return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--legs", action="store_true",
                      help="(internal) legs 1-4 in this process")
    mode.add_argument("--worker", action="store_true",
                      help="(internal) leg 5 worker under hvdrun")
    args = p.parse_args()
    if args.legs:
        return legs_main()
    if args.worker:
        return worker_main()
    return parent_main()


if __name__ == "__main__":
    sys.exit(main())
