"""The two plain references against the program, on the CPU at a small
size (the builders' ``TINY`` sizes: 2 layers at d 64; ResNet-50 at 32
px), so that the reference itself is guarded.

With the program computing in float32 the two are the same mathematics
and agree to rounding: 1e-4 on the loss, and on the parameter leaves'
reduced gradients (relative L2) 5e-3 at the median and 5e-2 at worst
(measured 4e-7 for GPT-2; for ResNet-50's 161 leaves through 53
train-mode batch norms over 8 images, whose last stage normalises 8
values a channel, 1e-5 to 1.5e-3 at the median and 4e-4 to 1.3e-2 at
worst over seeds: flax takes the variance as E[x^2] - E[x]^2, the
reference in two passes). In bf16 the same comparison
shows what the precision costs, and must stay inside the tolerances the
configuration files state for the chip (GPT-2 only: 8 images of 32 px
leave ResNet-50's last stage with 8 values a batch-norm channel, which
bf16 turns into 30% gradient noise).
"""

import jax
import pytest

from benchmark import cell as cells
from benchmark import check, traffic


def _compare(name, dtype, sample, spoil=None):
    """The ``sgd_step`` comparison at the tiny sizes (the
    ``first_moment`` one runs in the CPU rehearsal of the GPT-2 cells)."""
    cell = cells.load(name, tiny=True)
    cell.config["compute_dtype"] = dtype
    asm = cells.assemble(cell, jax.devices()[:1])
    if spoil:
        asm.check_tx = spoil(asm.check_tx)
    key = jax.random.PRNGKey(3)
    params, state = jax.jit(asm.model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=sample,
        config=cell.config, **asm.model.pool_kwargs)
    batch = jax.tree.map(lambda a: a[0], pool)
    lifted, grads, loss = check.sgd_step_gradients(asm, params, state,
                                                   batch, key)
    return check.against_reference(asm, grads, loss, lifted, state, batch)


@pytest.mark.parametrize("name,sample", [("gpt2m-s1024-c1", 2),
                                         ("resnet50-b256-c1", 8)])
def test_program_in_float32_is_the_reference(name, sample):
    got = _compare(name, "float32", sample)
    assert got["loss_rel"] < 1e-4, got
    assert got["grad_rel_l2_median"] < 5e-3, got
    assert got["grad_rel_l2_max"] < 5e-2, got


def test_gpt2_in_bf16_stays_inside_the_stated_tolerance():
    got = _compare("gpt2m-s1024-c1", "bfloat16", 2)
    assert got["ok"], got
    # and bf16 is visible: the check is not blind to precision
    assert got["grad_rel_l2_max"] > 1e-3, got


def test_a_sum_where_a_mean_belongs_fails():
    """What the check exists for: gradients 4x too large."""
    import optax

    got = _compare("gpt2m-s1024-c1", "bfloat16", 2,
                   spoil=lambda tx: optax.chain(tx, optax.scale(4.0)))
    assert not got["ok"] and got["grad_rel_l2_max"] > 2.5, got


def test_first_moment_gives_back_the_gradient():
    import jax.numpy as jnp
    import optax

    params = {"w": jnp.arange(4.0), "b": jnp.ones(())}
    grads = {"w": jnp.array([1.0, -2.0, 3.0, 0.5]), "b": jnp.array(7.0)}
    for spec in ({"name": "adamw", "b1": 0.9, "b2": 0.95, "weight_decay": 0.1,
                  "learning_rate": 1e-3, "warmup_steps": 5},
                 {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9}):
        tx = optax.chain(optax.identity(), cells.make_optimizer(spec))
        _, opt_state = tx.update(grads, tx.init(params), params)
        got = cells.first_moment_gradients(spec, opt_state)
        for k in grads:
            assert jnp.allclose(got[k], grads[k], rtol=1e-6), (spec, k)


def test_markov_tokens_have_the_stated_entropy():
    key = jax.random.PRNGKey(0)
    pool = traffic.make_pool(
        key, {"kind": "markov_tokens", "successors": 4, "pool": 2},
        global_batch=64, seq_len=256, config={"vocab_size": 50257})
    assert pool.shape == (2, 64, 257) and pool.dtype == jax.numpy.int32
    assert int(pool.min()) >= 0 and int(pool.max()) < 50257
    import numpy as np

    tokens = np.asarray(pool).reshape(-1, 257)
    successors = {}
    for row in tokens:
        for a, b in zip(row[:-1], row[1:]):
            successors.setdefault(int(a), set()).add(int(b))
    assert max(len(s) for s in successors.values()) <= 4
    again = traffic.make_pool(
        key, {"kind": "markov_tokens", "successors": 4, "pool": 2},
        global_batch=64, seq_len=256, config={"vocab_size": 50257})
    assert (np.asarray(again) == np.asarray(pool)).all()
