"""The comparisons behind ``glm-4.7-flash.json``'s ``check`` bounds, at
the cell's real widths on the chip, outside any timed window:

    python3 benchmark/glm_routing.py --seeds <n>[,<n>...] [--assert]
                                     [--rehearse-cpu]

One seeded sequence a seed, the program in its compute dtype against the
plain float32 reference ("highest" matmul precision) given the SAME
share (held experts, sliced vocabulary, the same bias).

``detail``, loss and every gradient leaf (relative L2) with the routing
beside them:

- FORCED routing: the program is given the experts the reference chose,
  so the two differ by precision alone;
- FREE routing: each side takes its own top-4 of 64. Also the share of
  the T x k (token, slot) pairs that differ, the rows the held experts
  received, and the largest expert's load over the mean.

``verdicts``, each THROUGH THE HARNESS'S OWN COMPARISON with the
configuration's limits (``check.sgd_step_gradients`` on the assembled
normal path, then ``check.against_reference``: the ``ok`` that decides
``correct`` in ``run.py``): the sound program (``free``), which has to
come out ``ok``, and the defects that have to come out NOT ``ok``:

- ``reference_fp8``: the reference itself computed below the stated
  precision: every matmul operand and every cotangent that reaches one
  rounded to ``float8_e4m3``'s 3 mantissa bits, accumulation in float32;
- ``fp8_weights``: the program with its parameters rounded so;
- ``no_norm``: the gates not divided by their sum over the chosen;
- ``no_scale``: ``routed_scaling_factor`` left out;
- ``no_shared``: the shared expert left out (its output projection
  times 1e-20 in the program's weights);
- ``k_pe_one_head``: the rotary key part not shared across the heads
  (head 0 alone gets it).

With ``--assert`` the exit code is 1 unless ``free`` is ``ok`` and
every defect is not, on every seed. The last line of stdout is one JSON
object. No CPU fallback: without the chip it exits non-zero, unless
``--rehearse-cpu`` (tiny sizes, where the verdicts are not asserted).
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

CELL = "glm47f-s8192-ep8-c1"
DEFECTS = ("reference_fp8", "fp8_weights", "no_shared", "no_norm",
           "no_scale", "k_pe_one_head")


def detail(cell, model, params, state, tokens):
    """Forced and free routing: distances and what the routers did."""
    import jax
    import numpy as np

    from benchmark import check
    from benchmark.reference import glm4_moe_lite as reference

    config = cell.config
    n_dense = config["first_k_dense_replace"]

    # Every array is an ARGUMENT of the jitted functions: one that is
    # closed over becomes a constant of the program.
    def reference_side(params, state, tokens):
        def loss(p):
            logits, aux = reference.forward(config, p, state, tokens[:, :-1])
            return (reference.cross_entropy(logits, tokens[:, 1:]),
                    aux["chosen"])
        (value, chosen), grads = jax.value_and_grad(loss, has_aux=True)(
            params)
        return value, chosen, grads

    with jax.default_matmul_precision("highest"):
        ref_loss, chosen, ref_grads = jax.jit(reference_side)(
            params, state, tokens)
    ref_loss = float(ref_loss)

    def program_side(params, state, tokens, assignments, ref_grads):
        (value, stats), grads = jax.value_and_grad(
            lambda p: model.loss_and_stats(p, state, tokens, assignments),
            has_aux=True)(params)
        return value, stats, check._distances(grads, ref_grads)

    program = jax.jit(program_side)
    out = {"reference_loss": ref_loss}
    for name, assignments in (("forced", [None] * n_dense + list(chosen)),
                              ("free", None)):
        loss, stats, dist = jax.device_get(program(
            params, state, tokens, assignments, ref_grads))
        rel = {jax.tree_util.keystr(path): float(d[0] / d[1]) for path, d
               in jax.tree_util.tree_leaves_with_path(dist)}
        worst = max(rel, key=rel.get)
        mine, theirs = np.sort(stats["experts"], -1), np.sort(
            np.asarray(chosen), -1)
        same = (mine[..., :, None] == theirs[..., None, :]).any(-1)
        counts = stats["tokens_per_expert"].astype(np.float64)
        out[name] = {
            "loss": float(loss), "loss_rel": abs(float(loss) - ref_loss)
            / abs(ref_loss),
            "grad_rel_l2_max": rel[worst], "grad_worst_leaf": worst,
            "grad_rel_l2_median": float(np.median(list(rel.values()))),
            "grad_rel_l2": {k: round(v, 5) for k, v in rel.items()},
            "flipped_share": float(1.0 - same.mean()),
            "tokens_with_a_flip_share": float(1.0 - same.all(-1).mean()),
            "rows_held": [int(r) for r in stats["rows_held"]],
            "held_load_max_over_balanced": float(
                counts[:, :config["n_routed_experts"]].max()
                / counts.mean()),
            "load_max_over_mean": float((counts.max(-1)
                                         / counts.mean(-1)).max()),
            "tokens_per_expert_sum": int(counts.sum(-1).max()),
        }
    return out


def _three_bits():
    """The rounding to 3 mantissa bits, float8_e4m3's, with float32's
    exponent range, of a value on the way forward and of its cotangent
    on the way back. Not a cast there and back: the TPU compiler drops
    a pair of converts."""
    import jax

    def rounded(a):
        return jax.lax.reduce_precision(a, 8, 3)

    both_ways = jax.custom_vjp(rounded)
    both_ways.defvjp(lambda a: (rounded(a), None),
                     lambda _, g: (rounded(g),))
    return rounded, both_ways


def verdicts(cell, devices, asm, params, state, batch, key):
    """name -> ``check.against_reference``'s dict for the sound program
    and each defect, the reference always at ``params``."""
    import jax
    import jax.numpy as jnp

    from benchmark import cell as cells
    from benchmark import check
    from benchmark.reference import glm4_moe_lite as reference
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

    rounded, both_ways = _three_bits()

    def without_shared(p):
        p = jax.tree.map(lambda a: a, p)
        for layer in p["params"].values():
            if isinstance(layer, dict) and "moe" in layer:
                # Not zeros: the check gives an all-zero leaf noise.
                layer["moe"]["shared"]["wo"] = (
                    layer["moe"]["shared"]["wo"] * 1e-20)
        return p

    def one_head(k_pe, n_heads):
        pad = [(0, 0), (0, 0), (0, n_heads - 1), (0, 0)]
        return jnp.pad(k_pe, pad)

    def through_the_program(path, weights):
        _, grads, loss = check.sgd_step_gradients(path, weights, state,
                                                  batch, key)
        del weights
        return check.against_reference(asm, grads, loss, params, state,
                                       batch)

    def reference_below_its_precision():
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

    def one_head_program():
        share = transformer._to_every_head
        transformer._to_every_head = one_head
        try:
            return through_the_program(spoiled(), params)
        finally:
            transformer._to_every_head = share

    rows = {
        "free": lambda: through_the_program(asm, params),
        "reference_fp8": reference_below_its_precision,
        "fp8_weights": lambda: through_the_program(
            asm, jax.jit(lambda p: jax.tree.map(rounded, p))(params)),
        "no_shared": lambda: through_the_program(
            asm, jax.jit(without_shared)(params)),
        "no_norm": lambda: through_the_program(
            spoiled(norm_topk=False), params),
        "no_scale": lambda: through_the_program(
            spoiled(routed_scale=1.0), params),
        "k_pe_one_head": one_head_program,
    }
    out = {}
    for name, row in rows.items():
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
    p.add_argument("--detail", action="store_true",
                   help="also forced against free routing, leaf by leaf")
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
    for seed in [int(s) for s in args.seeds.split(",")]:
        # The weights and the check's one sequence as run.py makes them.
        k_init, _, k_check, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
        params, state = jax.jit(asm.model.init,
                                out_shardings=asm.replicated)(k_init)
        (batch,) = run.pool_of_batches(
            asm, k_check, 1, dict(cell.traffic["data"], pool=1))
        here = {"verdicts": verdicts(cell, devices, asm, params, state,
                                     batch, k_check)}
        if args.detail:
            here["detail"] = detail(cell, asm.model, params, state, batch)
        del params, state, batch
        out["seeds"][str(seed)] = here
        for name, v in here["verdicts"].items():
            run.log("seed %d %-14s ok=%s loss_rel %.3g worst leaf %.4g %s"
                    % (seed, name, v["ok"], v["loss_rel"],
                       v["grad_rel_l2_max"], v["grad_worst_leaf"]))
            if v["ok"] != (name not in DEFECTS):
                failures.append("%d:%s" % (seed, name))
    out["not_as_it_has_to_be"] = failures
    print(json.dumps(out), flush=True)
    return 1 if args.asserted and failures and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
