"""The two comparisons behind ``olmoe-1b-7b.json``'s ``check`` bounds, at
the cell's real widths on the chip, outside any timed window:

    python3 benchmark/olmoe_routing.py --seed <n> [--rehearse-cpu]

One seeded sequence, the program in its compute dtype against the plain
float32 reference ("highest" matmul precision), loss and every gradient
leaf (relative L2):

- FORCED routing: the program is given the experts the reference chose,
  so the two differ by precision alone and must agree as GPT-2 does
  (loss 2e-4, gradient leaf 5e-2);
- FREE routing, what ``run.py``'s ``correct`` compares: each side takes
  its own top-k. Also the share of the T x k (token, slot) assignments
  that differ, which is what the free comparison's floor is made of,
  and the largest expert's load over the mean.

and two readings that the configuration's bounds have to REFUSE:

- ``free_fp8_weights``: the program computing below the stated
  precision, its parameters rounded to ``float8_e4m3``'s 3 mantissa
  bits (bfloat16 keeps 7);
- ``forced_no_aux``: the program with both router losses left out of
  its loss (the router's gradient leaf is the one to look at).

The last line of stdout is one JSON object. No CPU fallback: without
the chip it exits non-zero, unless ``--rehearse-cpu`` (tiny sizes).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

import argparse  # noqa: E402
import json  # noqa: E402

CELL = "olmoe-s4096-c1"


def compare(asm, asm_no_aux, params, tokens):
    import jax
    import numpy as np

    from benchmark import check
    from benchmark.reference import olmoe as reference

    config = asm.cell.config

    # Every array is an ARGUMENT of the jitted functions: one that is
    # closed over (2.5 GB of gradients) becomes a constant of the program.
    def reference_side(params, tokens):
        def loss(p):
            logits, aux = reference.forward(config, p, tokens[:, :-1])
            return (reference.total_loss(config, logits, aux, tokens[:, 1:]),
                    aux["chosen"])
        (value, chosen), grads = jax.value_and_grad(loss, has_aux=True)(
            params)
        return value, chosen, grads

    with jax.default_matmul_precision("highest"):
        ref_loss, chosen, ref_grads = jax.jit(reference_side)(params, tokens)
    ref_loss = float(ref_loss)

    def program_side(model):
        def side(params, tokens, assignments, ref_grads):
            (value, stats), grads = jax.value_and_grad(
                lambda p: model.loss_and_stats(p, tokens, assignments),
                has_aux=True)(params)
            return value, stats, check._distances(grads, ref_grads)
        return jax.jit(side)

    # 3 mantissa bits, float8_e4m3's, with float32's exponent range (4
    # exponent bits would flush most normal(0.02) weights to zero: an
    # 8-bit format is used with a scale). Not a cast there and back:
    # the TPU compiler drops a pair of converts as excess precision.
    rounded = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 8, 3), p))
    out = {"reference_loss": ref_loss}
    program, no_aux = program_side(asm.model), program_side(asm_no_aux.model)
    for name, side, weights, assignments in (
            ("forced", program, params, list(chosen)),
            ("free", program, params, None),
            ("free_fp8_weights", program, rounded(params), None),
            ("forced_no_aux", no_aux, params, list(chosen))):
        loss, stats, dist = jax.device_get(side(
            weights, tokens, assignments, ref_grads))
        del weights
        rel = {jax.tree_util.keystr(path): float(d[0] / d[1]) for path, d
               in jax.tree_util.tree_leaves_with_path(dist)}
        worst = max(rel, key=rel.get)
        mine, theirs = np.sort(stats["experts"], -1), np.sort(
            np.asarray(chosen), -1)
        # Per token, the chosen experts the two sides do not share.
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
            "load_max_over_mean": float((counts.max(-1)
                                         / counts.mean(-1)).max()),
            "tokens_per_expert_sum": int(counts.sum(-1).max()),
        }
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
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
    bare = cells.load(CELL, tiny=args.rehearse_cpu)
    bare.config.update(router_aux_loss_coef=0.0, router_z_loss_coef=0.0)
    asm_no_aux = cells.assemble(bare, devices)
    k_init, k_data = jax.random.split(jax.random.PRNGKey(args.seed))
    params, _ = jax.jit(asm.model.init, out_shardings=asm.replicated)(k_init)
    (tokens,) = run.pool_of_batches(
        asm, k_data, 1, dict(cell.traffic["data"], pool=1))
    out = compare(asm, asm_no_aux, params, tokens)
    out.update(seed=args.seed, rehearsal=args.rehearse_cpu,
               device={"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
               compute_dtype=cell.config["compute_dtype"],
               tokens=int(tokens.shape[0] * (tokens.shape[1] - 1)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
