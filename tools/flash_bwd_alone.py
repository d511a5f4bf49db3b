"""The flash backward ALONE on the chip: ``hvd_flash_bwd`` (one pass)
against ``hvd_flash_dkv`` + ``hvd_flash_dq`` at the benchmark cells'
attention shapes, causal, bf16, both reached through the wrappers
``_flash_bwd`` chooses between (docs/mfu.md, "The backward in one
pass": the table this reproduces). A shape with a ``topk`` runs under
a LEARNED mask, each query's ``topk`` highest of random scores:
``hvd_dsa_bwd`` against ``hvd_dsa_dkv`` + ``hvd_dsa_dq``.

    chiprun --chips 1 -- python3 tools/flash_bwd_alone.py [shape,shape]

Host clock round ``block_until_ready``, the median of ten calls after
two warm ones; a ROW line a shape, and the whole as
``chiprun_out/flash_bwd_alone.json``. Off the chip it runs one tiny
shape in interpret mode, to show that it runs.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from horovod_tpu.ops import pallas_attention as pa  # noqa: E402

SHAPES = [  # name, B, S, H, H_kv, d, d_v, window[, topk]
    ("keye", 1, 8192, 32, 4, 128, 128, None, 2048),
    ("glm", 1, 8192, 20, 20, 256, 256, None),
    ("trinity-full", 1, 8192, 32, 4, 128, 128, None),
    ("trinity-window", 1, 8192, 32, 4, 128, 128, 2048),
    ("lfm2", 1, 16384, 32, 8, 64, 64, None),
    ("gpt2m-s4096", 1, 4096, 16, 16, 64, 64, None),
    ("gpt2m-s1024", 4, 1024, 16, 16, 64, 64, None),
    ("phi4flash-full", 1, 8192, 40, 20, 64, 128, None),
    ("phi4flash-window", 1, 8192, 40, 20, 64, 128, 512),
    ("olmoe", 1, 4096, 16, 16, 128, 128, None),
]
INTERPRET = jax.default_backend() != "tpu"
REPS = 1 if INTERPRET else 10


def timed(fn, *args):
    for _ in range(1 if INTERPRET else 2):
        out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPS):
        start = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return statistics.median(times), out


def learned_planes(key, b, s, topk):
    """``pack_selection`` of each query's ``topk`` highest of random
    scores among the keys at or before it."""
    from horovod_tpu.models.transformer import select_rows

    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                       jax.random.uniform(key, (b, s, s)), -jnp.inf)
    return pa.pack_selection(select_rows(scores, topk))


def main(argv):
    shapes = SHAPES
    if INTERPRET:
        shapes = [("tiny", 1, 600, 4, 2, 64, 128, 200),
                  ("tiny-learned", 1, 600, 4, 2, 64, 128, None, 100)]
    elif argv:
        shapes = [s for s in SHAPES if s[0] in argv[0].split(",")]
    print("device", jax.devices()[0].device_kind, flush=True)
    rows = []
    for name, b, s, h, h_kv, d, d_v, window, *topk in shapes:
        keys = jax.random.split(jax.random.PRNGKey(len(name) * 7919 + s), 5)
        q, k, v, g = (jax.random.normal(key, (b, n, s, w), jnp.bfloat16)
                      for key, n, w in zip(
                          keys, (h, h_kv, h_kv, h), (d, d, d_v, d_v)))
        select = jax.jit(learned_planes, static_argnums=(1, 2, 3))(
            keys[4], b, s, topk[0]) if topk else None
        scale = d ** -0.5
        block_q, block_k = pa._default_blocks(s, s)
        forward = jax.jit(lambda q, k, v, select: pa._flash_fwd_impl(
            q, k, v, True, window, block_q, block_k, scale, INTERPRET,
            select))
        fwd_ms, (_, res) = timed(forward, q, k, v, select)
        # The tiles are static facts: made outside the jitted operands.
        tiles = pa._Tiles(block_q, block_k, True, s, s, window,
                          select is not None)
        operands = jax.jit(lambda res, g: pa._bwd_operands(
            block_q, block_k, True, window, res, g)[1:])(res, g)
        two_ms, two = timed(jax.jit(lambda *o: pa._bwd_two_kernels(
            tiles, scale, INTERPRET, *o)), *operands)
        one_ms, one = timed(jax.jit(lambda *o: pa._bwd_one_pass(
            tiles, scale, INTERPRET, *o)), *operands)
        apart = [float(np.abs(np.asarray(x, np.float32)
                              - np.asarray(y, np.float32)).max()
                       / np.abs(np.asarray(y, np.float32)).max())
                 for x, y in zip(one, two)]
        rows.append({"shape": name, "fwd_ms": fwd_ms, "two_kernels_ms": two_ms,
                     "one_pass_ms": one_ms, "ratio": one_ms / two_ms,
                     "dq_dk_dv_apart": apart})
        print("ROW", json.dumps(rows[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_bwd_alone.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
