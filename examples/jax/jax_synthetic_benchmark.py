"""Synthetic ResNet-50 benchmark (parity with the reference's
examples/pytorch/pytorch_synthetic_benchmark.py:16-40, including the
--fp16-allreduce and --use-adasum flags).

Run:  python examples/jax/jax_synthetic_benchmark.py            # 1 chip
      python -m horovod_tpu.runner -np 8 python examples/jax/...
"""

import argparse
from functools import partial
import time

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu import models
from horovod_tpu.jax.compression import Compression


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--use-adasum", action="store_true")
    args = p.parse_args()

    hvd.init()

    model_cls = getattr(models, {"resnet50": "ResNet50",
                                 "resnet101": "ResNet101",
                                 "resnet18": "ResNet18"}[args.model])
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16)
    images = jax.random.normal(jax.random.PRNGKey(hvd.rank()),
                               (args.batch_size, 224, 224, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch_size,), 0, 1000)
    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    params = hvd_jax.broadcast_parameters(params, root_rank=0)

    compression = Compression.fp16 if args.fp16_allreduce else Compression.none
    op = hvd.Adasum if args.use_adasum else hvd.Average
    tx = hvd_jax.DistributedOptimizer(
        optax.sgd(0.01 * hvd.size(), momentum=0.9),
        op=op, compression=compression)
    opt_state = tx.init(params)

    # Donated buffers: the weight/batch-stat/optimizer arrays are
    # updated in place by XLA rather than copied every step, the same
    # donation the benchmark's step uses (docs/mfu.md).
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state):
        def loss_fn(p, bs):
            logits, updates = model.apply(
                {"params": p, "batch_stats": bs}, images, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(), updates["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), batch_stats, \
            opt_state, loss

    def run_batches(n):
        nonlocal params, batch_stats, opt_state
        for _ in range(n):
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state)
        float(loss)

    run_batches(args.num_warmup_batches)
    img_secs = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        if hvd.rank() == 0:
            print("Iter: %.1f img/sec per chip" % img_sec)
        img_secs.append(img_sec)

    if hvd.rank() == 0:
        import numpy as np

        mean = np.mean(img_secs)
        print("Img/sec per chip: %.1f +- %.1f" % (mean, 1.96 * np.std(img_secs)))
        print("Total img/sec on %d chip(s): %.1f"
              % (hvd.size(), hvd.size() * mean))
    hvd.shutdown()


if __name__ == "__main__":
    main()
