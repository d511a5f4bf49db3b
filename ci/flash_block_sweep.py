#!/usr/bin/env python
"""Sweep the Pallas flash-attention VMEM tile sizes on the local chip.

Usage:  python ci/flash_block_sweep.py [--seq 2048] [--batch 4]

Runs fwd+bwd through ``flash_attention`` for each (block_q, block_k)
pair and prints a ranked table (host clock, whole step). The default
is a rule on the sequence length (``pallas_attention._default_blocks``;
its kernel-alone device timings are in PERF.md, PR 25): a pair that
beats it across shapes belongs in that rule; per-job overrides go
through HVD_FLASH_BLOCK_Q / HVD_FLASH_BLOCK_K.

The sweep runs on whatever backend jax selects; off the TPU the kernel
runs in interpret mode (it logs that once), and its timings say
nothing about the chip.
"""
from __future__ import annotations

import argparse
import itertools
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--blocks", default="128,256,512",
                   help="comma list of candidate tile sizes")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (interpret-mode smoke; "
                        "timings are only meaningful on a TPU)")
    args = p.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from horovod_tpu.ops.pallas_attention import flash_attention

    dev = jax.devices()[0]
    print("# device: %s (%s)" % (dev.device_kind, dev.platform))

    shape = (args.batch, args.seq, args.heads, args.head_dim)
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.bfloat16) for i in range(3))

    candidates = [int(b) for b in args.blocks.split(",")]
    results = []
    for bq, bk in itertools.product(candidates, candidates):
        def loss(q, k, v, bq=bq, bk=bk):
            return flash_attention(q, k, v, block_q=bq,
                                   block_k=bk).astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            out = step(q, k, v)  # compile + smoke
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = step(q, k, v)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / args.iters
        except Exception as e:  # noqa: BLE001 - report and keep sweeping
            print("bq=%-4d bk=%-4d FAILED: %s" % (bq, bk, e))
            continue
        results.append((dt, bq, bk))
        print("bq=%-4d bk=%-4d %8.3f ms/step" % (bq, bk, dt * 1e3))

    if results:
        results.sort()
        dt, bq, bk = results[0]
        print("# best: block_q=%d block_k=%d (%.3f ms/step)"
              % (bq, bk, dt * 1e3))


if __name__ == "__main__":
    main()
