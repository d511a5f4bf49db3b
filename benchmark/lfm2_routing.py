"""The comparisons behind ``lfm2-8b-a1b.json``'s ``check`` bounds, at
the cell's real widths on the chip, outside any timed window:

    python3 benchmark/lfm2_routing.py --seeds <n>[,<n>...] [--assert]
                                      [--only a,b] [--rehearse-cpu]

One seeded sequence a seed, the program in its compute dtype against the
plain float32 reference ("highest" matmul precision) given the SAME
share (held experts, sliced vocabulary, the same bias), each THROUGH THE
HARNESS'S OWN COMPARISON with the configuration's limits
(``check.sgd_step_gradients`` on the assembled normal path, then
``check.against_reference``: the ``ok`` that decides ``correct`` in
``run.py``): the sound program (``free``), which has to come out
``ok``, and the defects that have to come out NOT ``ok``:

- ``reference_fp8``: the reference itself computed below the stated
  precision: every matmul operand and every cotangent that reaches one
  rounded to ``float8_e4m3``'s 3 mantissa bits, accumulation in float32;
- ``no_c_gate``: the convolution's output not multiplied by c;
- ``no_b_gate``: the convolution's input u not multiplied by b;
- ``acausal``: the taps at t .. t + 2 where t - 2 .. t belong;
- ``no_head_norms``: q and k without their norm per head;
- ``no_norm``: the gates not divided by their sum over the chosen;
- ``sum_for_mean``: the reduced gradients four times what they are, as
  a sum over four chips where a mean belongs.

A defect that drops a parameter (the head norms' scales) hands the
optimizer no gradient for it: the comparison sees zeros there. The
three defects of the convolution replace
``models.transformer._gated_taps``, the one function between the mixer's
two projections; they add no option to the program. With ``--assert``
the exit code is 1 unless ``free`` is ``ok`` and every defect is not, on
every seed. The last line of stdout is one JSON object. No CPU fallback:
without the chip it exits non-zero, unless ``--rehearse-cpu`` (tiny
sizes, where the verdicts are not asserted).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
from types import SimpleNamespace  # noqa: E402

CELL = "lfm2-s16384-ep4-c1"
CONV_DEFECTS = ("no_c_gate", "no_b_gate", "acausal")
DEFECTS = ("reference_fp8",) + CONV_DEFECTS + (
    "no_head_norms", "no_norm", "sum_for_mean")


def spoiled_gated_taps(defect):
    """``models.transformer._gated_taps`` with one of ``CONV_DEFECTS``."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    def acausal_taps(x, w):
        # Tap j at t + j: the causal taps of the reversed sequence with
        # the taps reversed, put back in order.
        return jnp.flip(transformer._causal_taps(
            jnp.flip(x, 1), w[:, ::-1]), 1)

    def gated_taps(bcu, w):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        taps = acausal_taps if defect == "acausal" \
            else transformer._causal_taps
        z = taps(u if defect == "no_b_gate" else b * u, w.astype(bcu.dtype))
        return z if defect == "no_c_gate" else c * z

    return gated_taps


def verdicts(cell, devices, asm, params, state, batch, key, only=None):
    """name -> ``check.against_reference``'s dict for the sound program
    and each defect, the reference always at ``params``."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import cell as cells
    from benchmark import check
    from benchmark.glm_routing import _three_bits
    from benchmark.reference import lfm2_moe as reference
    from horovod_tpu.models import transformer

    spec = cell.builder.block_spec(cell.config)

    def spoiled(**changes):
        """The normal path assembled round a model with ``changes`` to
        its ``BlockSpec``."""
        block = dataclasses.replace(spec, **changes)
        builder = SimpleNamespace(
            build=lambda config, traffic: cell.builder.build(
                config, traffic, block))
        return cells.assemble(
            SimpleNamespace(**dict(vars(cell), builder=builder)), devices)

    def only_the_leaves_of(path, tree):
        """``tree`` cut to the parameters ``path``'s model has."""
        have, _ = jax.eval_shape(path.model.init, jax.random.PRNGKey(0))
        flat = dict(jax.tree_util.tree_leaves_with_path(tree))
        return jax.tree_util.tree_map_with_path(
            lambda where, _: flat[where], have)

    def zeros_where_absent(grads):
        flat = dict(jax.tree_util.tree_leaves_with_path(grads))
        return jax.tree_util.tree_map_with_path(
            lambda where, p: flat.get(where, jnp.zeros_like(p)), params)

    def through_the_program(path):
        weights = only_the_leaves_of(path, params)
        _, grads, loss = check.sgd_step_gradients(path, weights, state,
                                                  batch, key)
        del weights
        return check.against_reference(
            asm, zeros_where_absent(grads), loss, params, state, batch)

    def with_spoiled_conv(defect):
        sound = transformer._gated_taps
        transformer._gated_taps = spoiled_gated_taps(defect)
        try:
            # A path of its own: the sound one's traced step is cached.
            return through_the_program(spoiled())
        finally:
            transformer._gated_taps = sound

    def four_times():
        path = spoiled()
        path.check_tx = optax.chain(path.check_tx, optax.scale(4.0))
        return through_the_program(path)

    def reference_below_its_precision():
        _, both_ways = _three_bits()

        def side(params, state, batch):
            (loss, _), grads = jax.value_and_grad(
                asm.model.reference_loss, has_aux=True)(params, state, batch)
            return grads, loss

        whole = reference._operand
        reference._operand = both_ways
        try:
            with jax.default_matmul_precision("highest"):
                grads, loss = jax.jit(side)(params, state, batch)
        finally:
            reference._operand = whole
        return check.against_reference(asm, grads, float(loss), params,
                                       state, batch)

    rows = {"free": lambda: through_the_program(asm),
            "reference_fp8": reference_below_its_precision}
    rows.update({defect: lambda defect=defect: with_spoiled_conv(defect)
                 for defect in CONV_DEFECTS})
    rows.update({
        "no_head_norms": lambda: through_the_program(
            spoiled(qk_norm_per_head=False)),
        "no_norm": lambda: through_the_program(spoiled(norm_topk=False)),
        "sum_for_mean": four_times})
    out = {}
    for name, row in rows.items():
        if only and name not in only:
            continue
        v = row()
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
            run.log("seed %d %-14s ok=%s loss_rel %.3g worst leaf %.4g %s "
                    "median %.3g" % (seed, name, v["ok"], v["loss_rel"],
                                     v["grad_rel_l2_max"],
                                     v["grad_worst_leaf"],
                                     v["grad_rel_l2_median"]))
            if v["ok"] != (name not in DEFECTS):
                failures.append("%d:%s" % (seed, name))
    out["not_as_it_has_to_be"] = failures
    print(json.dumps(out), flush=True)
    return 1 if args.asserted and failures and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
