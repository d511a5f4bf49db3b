"""The comparisons behind ``phi-4-mini-flash-reasoning.json``'s ``check``
bounds, at the cell's real widths on the chip, outside any timed window:

    python3 benchmark/phi4flash_probe.py --seeds <n>[,<n>...] [--assert]
                                         [--only a,b] [--rehearse-cpu]

One seeded sequence a seed, the program in its compute dtype against the
plain float32 reference ("highest" matmul precision) on the same six
layers and 25,008 rows, each THROUGH THE HARNESS'S OWN COMPARISON with
the configuration's limits (``check.sgd_step_gradients`` on the
assembled normal path, then ``check.against_reference``: the ``ok``
that decides ``correct`` in ``run.py``): the sound program (``sound``),
which has to come out ``ok``, and the defects that have to come out NOT
``ok``:

- ``reference_fp8``: the reference itself computed below the stated
  precision: every matmul operand and every cotangent that reaches one
  rounded to ``float8_e4m3``'s 3 mantissa bits, accumulation in float32;
- ``no_d``: the scan's ``D x'`` left out;
- ``no_softplus``: ``Delta = r W_dt + b_dt`` without its ``softplus``;
- ``gated_memory``: the memory units handed the scan output AFTER its
  gate, ``y * silu(z)``;
- ``kv_from_layer_1``: the cross-attention reading the keys and values
  of published layer 1 (the sliding one) and not layer 17's;
- ``no_subtraction``: the second softmax map not subtracted;
- ``no_factor``: the factor ``1 - lambda_init`` left out;

and one defect that stands BELOW what the check can refuse, asserted by
a floor of its own:

- ``future_key``: every query admitted to the key one position ahead.
  Its worst leaf reads 0.17-0.25 on the chip where the sound program's
  reads 0.04-0.16 (a map over up to 8,192 keys hardly moves for one
  more), so no limit parts them; its MEDIAN leaf reads 0.056-0.069
  against 0.026-0.029 and has to stand at ``MEDIAN_FLOOR`` times the
  sound run's on the same seed. That no position sees a later token is
  exact on the CPU (``benchmark/tests/test_phi4flash.py``).

A defect that cuts a parameter off the loss (``D``, the lambda vectors)
hands the optimizer a zero gradient for it: the comparison sees zeros
there. The defects replace one small function of the program each
(``ops.pallas_scan.selective_scan``, ``flax.linen.softplus``,
``models.transformer._published_scan`` / ``_differential_output`` /
``_attend``) or change one ``BlockSpec`` field; they add no option to
the program. With ``--assert`` the exit code is 1 unless ``sound`` is
``ok``, every defect is not and ``future_key`` stands over its floor,
on every seed. The last line of stdout
is one JSON object. No CPU fallback: without the chip it exits
non-zero, unless ``--rehearse-cpu`` (tiny sizes, where the verdicts
are not asserted).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
from types import SimpleNamespace  # noqa: E402

CELL = "phi4flash-s8192-yoco-c1"
DEFECTS = ("reference_fp8", "no_d", "no_softplus", "gated_memory",
           "kv_from_layer_1", "no_subtraction", "no_factor")
BELOW_THE_CHECK = ("future_key",)
MEDIAN_FLOOR = 1.5


def not_as_it_has_to_be(verdicts):
    """The names of one seed's ``verdicts`` that did not come out as
    they have to: ``sound`` ok, each of ``DEFECTS`` not ok, each of
    ``BELOW_THE_CHECK`` with a median leaf at ``MEDIAN_FLOOR`` times the
    sound run's (where both were run)."""
    wrong = [name for name, v in verdicts.items()
             if name not in BELOW_THE_CHECK
             and v["ok"] != (name not in DEFECTS)]
    if "sound" in verdicts:
        floor = MEDIAN_FLOOR * verdicts["sound"]["grad_rel_l2_median"]
        wrong += [name for name in BELOW_THE_CHECK if name in verdicts
                  and not verdicts[name]["grad_rel_l2_median"] >= floor]
    return wrong


@contextlib.contextmanager
def replaced(owner, name, value):
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield sound
    finally:
        setattr(owner, name, sound)


def spoiled_function(defect):
    """(owner, name, the function with ``defect``) for the defects that
    replace a function of the program."""
    import flax.linen as nn
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import pallas_scan

    if defect == "no_d":
        sound = pallas_scan.selective_scan
        return pallas_scan, "selective_scan", \
            lambda x, delta, a, b, c, d: sound(x, delta, a, b, c, 0.0 * d)
    if defect == "no_softplus":
        return nn, "softplus", lambda x: x
    if defect == "gated_memory":
        return transformer, "_published_scan", lambda y, gated: gated
    if defect in ("no_subtraction", "no_factor"):
        sound = transformer._differential_output

        def output(first, second, lam, lambda_init, scale):
            if defect == "no_subtraction":
                return sound(first, second, 0.0 * lam, lambda_init, scale)
            return sound(first, second, lam, 0.0, scale)

        return transformer, "_differential_output", output
    if defect == "future_key":
        sound = transformer._attend

        def attend(cfg, q, k, v, window=None, select=None):
            # Key t + 1 in key t's place: a query sees one key ahead.
            return sound(cfg, q, jnp.roll(k, -1, axis=1),
                         jnp.roll(v, -1, axis=1), window, select)

        return transformer, "_attend", attend
    raise ValueError(defect)


def verdicts(cell, devices, asm, params, state, batch, key, only=None):
    """name -> ``check.against_reference``'s dict for the sound program
    and each defect, the reference always at the sound parameters."""
    import jax

    from benchmark import cell as cells
    from benchmark import check
    from benchmark.glm_routing import _three_bits
    from benchmark.reference import phi4flash as reference

    spec = cell.builder.block_spec(cell.config)

    def spoiled(**changes):
        """The normal path assembled round a model with ``changes`` to
        its ``BlockSpec``: a path of its own, because the sound one's
        traced step is cached."""
        block = dataclasses.replace(spec, **changes)
        builder = SimpleNamespace(
            build=lambda config, traffic: cell.builder.build(
                config, traffic, block))
        return cells.assemble(
            SimpleNamespace(**dict(vars(cell), builder=builder)), devices)

    def through_the_program(path):
        # The conv bias starts at zero: the check lifts it, and the
        # reference is computed at what the program was given.
        lifted, grads, loss = check.sgd_step_gradients(path, params, state,
                                                       batch, key)
        return check.against_reference(asm, grads, loss, lifted, state,
                                       batch)

    def with_spoiled(defect):
        with replaced(*spoiled_function(defect)):
            return through_the_program(spoiled())

    def reference_below_its_precision():
        _, both_ways = _three_bits()

        def side(params, state, batch):
            (loss, _), grads = jax.value_and_grad(
                asm.model.reference_loss, has_aux=True)(params, state, batch)
            return grads, loss

        lifted = jax.jit(check._lift_zeros,
                         out_shardings=asm.replicated)(key, params)
        with replaced(reference, "_operand", both_ways), \
                jax.default_matmul_precision("highest"):
            grads, loss = jax.jit(side)(lifted, state, batch)
        return check.against_reference(asm, grads, float(loss), lifted,
                                       state, batch)

    rows = {"sound": lambda: through_the_program(asm),
            "reference_fp8": reference_below_its_precision,
            "kv_from_layer_1": lambda: through_the_program(spoiled(
                kv_from=cell.config["layers_kept"].index(1)))}
    rows.update({defect: lambda defect=defect: with_spoiled(defect)
                 for defect in DEFECTS + BELOW_THE_CHECK
                 if defect not in rows})
    out = {}
    for name in ("sound",) + DEFECTS + BELOW_THE_CHECK:
        if only and name not in only:
            continue
        v = rows[name]()
        v["refused_by"] = [limit for limit, over in (
            ("loss_rtol", not v["loss_rel"] <= v["loss_rtol"]),
            ("grad_rel_l2", not v["grad_rel_l2_max"]
             <= v["grad_rel_l2_tol"])) if over]
        out[name] = v
    return out


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
           "seeds": {}}
    failures = []
    only = [n for n in args.only.split(",") if n]
    for seed in [int(s) for s in args.seeds.split(",")]:
        # The weights and the check's one sequence as run.py makes them.
        k_init, _, k_check, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
        params, state = jax.jit(asm.model.init,
                                out_shardings=asm.replicated)(k_init)
        (batch,) = run.pool_of_batches(
            asm, k_check, 1, dict(cell.traffic["data"], pool=1))
        here = verdicts(cell, devices, asm, params, state, batch, k_check,
                        only)
        del params, state, batch
        out["seeds"][str(seed)] = here
        for name, v in here.items():
            run.log("seed %d %-16s ok=%s loss_rel %.3g worst leaf %.4g %s "
                    "median %.3g" % (seed, name, v["ok"], v["loss_rel"],
                                     v["grad_rel_l2_max"],
                                     v["grad_worst_leaf"],
                                     v["grad_rel_l2_median"]))
        failures += ["%d:%s" % (seed, name)
                     for name in not_as_it_has_to_be(here)]
    out["not_as_it_has_to_be"] = failures
    print(json.dumps(out), flush=True)
    return 1 if args.asserted and failures and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
