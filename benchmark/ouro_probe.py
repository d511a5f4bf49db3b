"""The comparisons behind ``ouro-2.6b.json``'s ``check`` bounds, at the
cell's real widths on the chip, outside any timed window:

    python3 benchmark/ouro_probe.py --seeds <n>[,<n>...] [--assert]
                                    [--only a,b] [--rehearse-cpu]

One seeded sequence a seed, the program in its compute dtype against the
plain float32 reference ("highest" matmul precision) on the same eight
blocks, four passes and 49,152 rows, each THROUGH THE HARNESS'S OWN
COMPARISON with the configuration's limits
(``check.sgd_step_gradients`` on the assembled normal path, then
``check.against_reference``: the ``ok`` that decides ``correct`` in
``run.py``): the sound program (``sound``), which has to come out
``ok``, and the defects that have to come out NOT ``ok``:

- ``reference_fp8``: the reference itself computed below the stated
  precision: every matmul operand and every cotangent that reaches one
  rounded to ``float8_e4m3``'s 3 mantissa bits, accumulation in float32;
- ``three_passes``: the stack applied three times where the model has
  four (``TransformerConfig.passes``);
- ``no_norm_between``: ``ln_f`` on the readouts alone, the next pass
  handed the un-normed state (``TransformerConfig.loop_norm``);
- ``last_gated``: the last pass given ``g_T prod (1 - g_j)`` where it
  takes the remainder (``models.transformer._exit_log_p``), so the exit
  distribution no longer sums to one;
- ``no_entropy``: the entropy term left out (``exit_entropy_beta`` 0 in
  the program alone);
- ``gate_unnormed``: the gate over the state BEFORE its norm where the
  program's reads the normed one (on the reference's side,
  ``reference.ouro._gate_input``: the comparison is symmetric).

The defects change one field of the program's ``TransformerConfig``, one
key of the configuration or one small function; they add no option to
the program. Also logged a seed, from the sound step's state: the mean
exit share of each pass, the mean entropy and the four mean cross
entropies (``models.transformer.record_loop_stats`` holds the shares as
gauges). With ``--assert`` the exit code is 1 unless ``sound`` is ``ok``
and every defect is not, on every seed. The last line of stdout is one
JSON object. No CPU fallback: without the chip it exits non-zero, unless
``--rehearse-cpu`` (tiny sizes, where the verdicts are not asserted).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
from types import SimpleNamespace  # noqa: E402

CELL = "ouro-s4096-ut4-c1"
DEFECTS = ("reference_fp8", "three_passes", "no_norm_between", "last_gated",
           "no_entropy", "gate_unnormed")


def not_as_it_has_to_be(verdicts):
    """The names of one seed's ``verdicts`` that did not come out as
    they have to: ``sound`` ok, each of ``DEFECTS`` not ok."""
    return [name for name, v in verdicts.items()
            if v["ok"] != (name not in DEFECTS)]


@contextlib.contextmanager
def replaced(owner, name, value):
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield sound
    finally:
        setattr(owner, name, sound)


def _last_gated(score):
    """``_exit_log_p`` with the defect: the last pass passes its own
    gate too."""
    import jax
    import jax.numpy as jnp

    stays = jnp.cumsum(jax.nn.log_sigmoid(-score), axis=0)
    return jnp.concatenate([
        jax.nn.log_sigmoid(score[:1]),
        jax.nn.log_sigmoid(score[1:]) + stays[:-1]])


def verdicts(cell, devices, asm, params, state, batch, key, only=None):
    """name -> ``check.against_reference``'s dict for the sound program
    and each defect, the reference always at the sound parameters; and
    the sound step's statistics."""
    import jax

    from benchmark import cell as cells
    from benchmark import check
    from benchmark.glm_routing import _three_bits
    from benchmark.reference import ouro as reference
    from horovod_tpu.models import transformer

    def spoiled(config=None, **fields):
        """The normal path assembled round a model with ``fields`` of
        its ``TransformerConfig`` changed, or built from ``config``: a
        path of its own, because the sound one's traced step is
        cached."""
        builder = SimpleNamespace(
            build=lambda sound, traffic: cell.builder.build(
                config or sound, traffic, **fields))
        return cells.assemble(
            SimpleNamespace(**dict(vars(cell), builder=builder)), devices)

    def through_the_program(path):
        lifted, grads, loss = check.sgd_step_gradients(path, params, state,
                                                       batch, key)
        return check.against_reference(asm, grads, loss, lifted, state,
                                       batch)

    def reference_side(**hooks):
        """The REFERENCE with ``hooks`` (name -> replacement of one of
        its functions), compared as if it were the program."""
        def side(params, state, batch):
            (loss, _), grads = jax.value_and_grad(
                asm.model.reference_loss, has_aux=True)(params, state, batch)
            return grads, loss

        with contextlib.ExitStack() as stack:
            for name, hook in hooks.items():
                stack.enter_context(replaced(reference, name, hook))
            stack.enter_context(jax.default_matmul_precision("highest"))
            grads, loss = jax.jit(side)(params, state, batch)
        return check.against_reference(asm, grads, float(loss), params,
                                       state, batch)

    def last_gated():
        with replaced(transformer, "_exit_log_p", _last_gated):
            return through_the_program(spoiled())

    rows = {
        "sound": lambda: through_the_program(asm),
        "reference_fp8": lambda: reference_side(_operand=_three_bits()[1]),
        "three_passes": lambda: through_the_program(spoiled(
            passes=cell.config["total_ut_steps"] - 1)),
        "no_norm_between": lambda: through_the_program(spoiled(
            loop_norm=False)),
        "last_gated": last_gated,
        "no_entropy": lambda: through_the_program(spoiled(
            dict(cell.config, exit_entropy_beta=0.0))),
        "gate_unnormed": lambda: reference_side(
            _gate_input=lambda normed, raw: raw),
    }
    out = {}
    for name in ("sound",) + DEFECTS:
        if only and name not in only:
            continue
        v = rows[name]()
        v["refused_by"] = [limit for limit, over in (
            ("loss_rtol", not v["loss_rel"] <= v["loss_rtol"]),
            ("grad_rel_l2", not v["grad_rel_l2_max"]
             <= v["grad_rel_l2_tol"])) if over]
        out[name] = v
    # One plain step of the sound path, for the loss's statistics.
    step = jax.jit(asm.sharded_step(asm.check_tx, False))
    _, stats, _, _ = step(params, state, asm.check_tx.init(params), batch)
    stats = {k: [float(x) for x in jax.numpy.ravel(v)]
             for k, v in jax.device_get(stats).items()}
    transformer.record_loop_stats(stats)
    return out, stats


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", required=True,
                   help="comma-separated, one sequence each")
    p.add_argument("--assert", dest="asserted", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated verdicts (default: all)")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args()

    from benchmark import cell as cells

    cell = cells.load(CELL, tiny=args.rehearse_cpu)

    import jax

    from benchmark import run
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices, _ = run.check_devices(cell, args.rehearse_cpu)
    asm = cells.assemble(cell, devices)
    out = {"rehearsal": args.rehearse_cpu,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind},
           "compute_dtype": cell.config["compute_dtype"],
           "limits": {k: cell.config["check"][k]
                      for k in ("loss_rtol", "grad_rel_l2")},
           "seeds": {}, "loop_stats": {}}
    failures = []
    only = [n for n in args.only.split(",") if n]
    for seed in [int(s) for s in args.seeds.split(",")]:
        # The weights and the check's one sequence as run.py makes them.
        k_init, _, k_check, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
        params, state = jax.jit(asm.model.init,
                                out_shardings=asm.replicated)(k_init)
        (batch,) = run.pool_of_batches(
            asm, k_check, 1, dict(cell.traffic["data"], pool=1))
        here, stats = verdicts(cell, devices, asm, params, state, batch,
                               k_check, only)
        del params, state, batch
        out["seeds"][str(seed)] = here
        out["loop_stats"][str(seed)] = stats
        for name, v in here.items():
            run.log("seed %d %-16s ok=%s loss_rel %.3g worst leaf %.4g %s "
                    "median %.3g" % (seed, name, v["ok"], v["loss_rel"],
                                     v["grad_rel_l2_max"],
                                     v["grad_worst_leaf"],
                                     v["grad_rel_l2_median"]))
        run.log("seed %d loop statistics: exit share by pass %s, entropy "
                "%.4f, cross entropy by pass %s" % (
                    seed, stats["exit_share"], stats["entropy"][0],
                    stats["cross_entropy"]))
        failures += ["%d:%s" % (seed, name)
                     for name in not_as_it_has_to_be(here)]
    out["not_as_it_has_to_be"] = failures
    print(json.dumps(out), flush=True)
    return 1 if args.asserted and failures and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
