"""Phi-4-mini-flash-reasoning on the CPU at tiny widths: the program
against its plain float32 reference, whole and block by block; the
selective scan's kernels (interpreted) and its plain path against a
loop over positions; the arrays a layer publishes and the sum of their
readers' gradients; recomputation; the new ``BlockSpec`` fields'
defaults; the configuration file, the parameter count and
``flops_phi4flash.py`` by hand; the new scopes and their readers; the
cell through the CPU rehearsal."""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_afmoe, flops_phi4flash, scope_view
from benchmark import traffic
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import reader
from benchmark.reference import phi4flash as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "phi4flash-s8192-yoco-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAMBA, GMU = "mamba", "memory_unit"
SLIDING, FULL, CROSS = "sliding_attention", "full_attention", \
    "cross_attention"
KINDS = [MAMBA, SLIDING, MAMBA, FULL, GMU, CROSS]


def _seen(params):
    """The weights with every leaf moved off its initial value (a zero
    bias, a scale of one and ``D`` = 1 hide a wrong use of themselves)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    return treedef.unflatten([
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)])


def _cell(dtype="float32", attention="flash", remat=True, **config):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention, **config)
    cell.traffic["remat"] = remat
    return cell


@functools.cache
def _assembled(dtype="float32", attention="flash", remat=True):
    cell = _cell(dtype, attention, remat)
    model = cell.builder.build(cell.config, cell.traffic)
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **model.pool_kwargs)
    return cell, model, _seen(params), state, pool[0]


def _both_sides(cell, model, params, state, tokens):
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss, has_aux=True))(params, state, tokens)
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            model.reference_loss, has_aux=True))(params, state, tokens)
    return float(loss), grads, float(want), want_grads


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_float32_program_is_the_reference(attention):
    loss, grads, want, want_grads = _both_sides(
        *_assembled("float32", attention))
    assert loss == pytest.approx(want, rel=2e-6)
    distances = _leaf_distances(grads, want_grads)
    assert len(distances) == 76
    assert max(distances.values()) < 2e-3, max(distances, key=distances.get)
    assert np.median(list(distances.values())) < 1e-5


def test_bf16_program_is_inside_gpt2s_bounds():
    """bf16 compute against the float32 reference at tiny widths: the
    loss inside the cells' 2e-4, the median leaf inside GPT-2's 0.05
    and every leaf inside the rehearsal's 0.3 (an attention layer's
    ``diff`` leaf among them: its lambda rows alone would read anything
    at all, ``SelfAttention._differential``)."""
    loss, grads, want, want_grads = _both_sides(*_assembled("bfloat16"))
    assert abs(loss - want) / want < 2e-4
    distances = _leaf_distances(grads, want_grads)
    assert np.median(list(distances.values())) < 0.05
    assert max(distances.values()) < 0.3


@functools.cache
def _layer_outputs():
    """Each block's output and what it published, on both sides, the
    program without recomputation."""
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    False)
    inputs = tokens[:, :-1]
    _, sown = model.module.apply(
        params, inputs, capture_intermediates=lambda m, name: isinstance(
            m, transformer.Block) and name == "__call__")
    got = [sown["intermediates"]["layer_%d" % i]["__call__"][0]
           for i in range(6)]
    p = params["params"]
    x, want, published = p["embed"][inputs], [], {}
    with jax.default_matmul_precision("highest"):
        for i, (kind, layer) in enumerate(zip(
                KINDS, cell.config["layers_kept"])):
            reads = {GMU: published.get(16), CROSS: published.get(17)}.get(
                kind)
            x, published[layer] = reference.block(
                x, p["layer_%d" % i], reads, config=cell.config, kind=kind,
                layer=layer)
            want.append((x, published[layer]))
    return got, want


@pytest.mark.parametrize("layer", range(6))
def test_each_block_against_the_reference(layer):
    got, want = _layer_outputs()
    x, published = want[layer]
    # Only the layers the spec names hand anything on (the reference's
    # mixers return their arrays whether or not anyone reads them).
    if layer not in (2, 3):
        assert not isinstance(got[layer], tuple)
        assert _rel(got[layer], x) < 2e-5
        return
    out, handed = got[layer]
    assert _rel(out, x) < 2e-5
    if KINDS[layer] == MAMBA:
        assert handed.shape == (2, 128, 128) and _rel(handed, published) < 2e-5
    else:
        assert [a.shape for a in handed] == [(2, 128, 2, 16)] * 2
        assert max(_rel(a, b) for a, b in zip(handed, published)) < 2e-5


def test_the_check_of_the_cell_in_float32():
    """``run.py``'s own comparison on the assembled path."""
    from benchmark import check

    cell = _cell("float32")
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(5)
    params, state = jax.jit(asm.model.init)(key)
    (batch,) = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=1,
        config=cell.config, **asm.model.pool_kwargs)
    lifted, grads, loss = check.sgd_step_gradients(asm, params, state, batch,
                                                   key)
    # The conv biases start at zero and are lifted for the check.
    assert float(jnp.abs(lifted["params"]["layer_0"]["mamba"]["b"]).max()) > 0
    verdict = check.against_reference(asm, grads, loss, lifted, state, batch)
    assert verdict["ok"] and verdict["leaves"] == 76
    assert verdict["grad_rel_l2_max"] < 2e-3 and verdict["loss_rel"] < 2e-6


# ------------------------------------------------------------ the scan ----

def _scan_inputs(t=45, e=256, n=16, batch=2):
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return (jax.random.normal(k[0], (batch, t, e)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, t, e)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (e, n))),
            jax.random.normal(k[3], (batch, t, n)),
            jax.random.normal(k[4], (batch, t, n)),
            jax.random.normal(k[5], (e,)),
            jax.random.normal(k[6], (batch, t, e)))


def _loop_over_positions(x, delta, a, b, c, d):
    """float64 numpy, one position after the other."""
    x, delta, a, b, c, d = (np.asarray(v, np.float64)
                            for v in (x, delta, a, b, c, d))
    h = np.zeros((x.shape[0], x.shape[2], a.shape[1]))
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        h = np.exp(delta[:, t, :, None] * a) * h \
            + (delta[:, t] * x[:, t])[..., None] * b[:, t, None, :]
        y[:, t] = np.einsum("ben,bn->be", h, c[:, t]) + d * x[:, t]
    return y


@functools.cache
def _scan_sides():
    from horovod_tpu.ops import pallas_scan

    *args, weight = _scan_inputs()

    def of(scan):
        y = scan(*args)
        grads = jax.grad(lambda *a: jnp.sum(scan(*a) * weight),
                         argnums=tuple(range(6)))(*args)
        return dict(zip(("y", "x", "delta", "a", "b", "c", "d"),
                        (y,) + grads))

    return (of(lambda *a: pallas_scan.selective_scan(*a, chunk=16)),
            of(lambda *a: pallas_scan.selective_scan_plain(*a, chunk=16)),
            of(reference.scan), _loop_over_positions(*args))


@pytest.mark.parametrize("name", ["y", "x", "delta", "a", "b", "c", "d"])
def test_the_scan_kernels_against_a_loop_over_positions(name):
    """45 positions in chunks of 16: not a whole number of chunks, nor
    of groups of eight. Forward against the float64 loop; each of the
    six gradients against the gradient of the reference's own loop over
    positions (``lax.scan``), and the plain chunked path likewise."""
    kernel, plain, loop, y64 = _scan_sides()
    if name == "y":
        assert _rel(kernel["y"], jnp.asarray(y64, jnp.float32)) < 1e-6
        assert _rel(loop["y"], jnp.asarray(y64, jnp.float32)) < 1e-6
    assert kernel[name].shape == loop[name].shape
    assert _rel(kernel[name], loop[name]) < 2e-6
    assert _rel(plain[name], loop[name]) < 2e-6


@pytest.mark.parametrize("t", [5, 16, 300])
def test_the_scan_at_any_length(t):
    """Shorter than a group, one whole chunk, and two chunks of the
    real size with a part of a third."""
    from horovod_tpu.ops import pallas_scan

    *args, _ = _scan_inputs(t=t, e=128, batch=1)
    want = jnp.asarray(_loop_over_positions(*args), jnp.float32)
    assert _rel(pallas_scan.selective_scan(*args), want) < 2e-6
    assert _rel(pallas_scan.selective_scan_plain(*args), want) < 2e-6


def test_the_scan_keeps_the_state_at_each_chunks_start():
    """The forward call's second result: entry j the state before
    position ``16 j``; what the backward call remakes a chunk from."""
    from horovod_tpu.ops import pallas_scan

    x, delta, a, b, c, d, _ = _scan_inputs(t=48, e=128, batch=1)
    y, states = pallas_scan._fwd_call(
        x, delta, a.T, pallas_scan._grouped(b, c), d[None, :], 16, True)
    assert states.shape == (1, 3, 16, 128)
    h = np.zeros((128, 16))
    for t in range(32):
        h = np.exp(np.asarray(delta[0, t, :, None] * a)) * h + np.asarray(
            (delta[0, t] * x[0, t])[:, None] * b[0, t][None, :])
        if t == 15:
            assert _rel(states[0, 1].T, jnp.asarray(h, jnp.float32)) < 1e-6
    assert _rel(states[0, 2].T, jnp.asarray(h, jnp.float32)) < 1e-6
    assert float(jnp.abs(states[0, 0]).max()) == 0.0


def test_the_scan_calls_are_no_flash_kernel_to_the_readers():
    """``trace_reduce.flash_kernel`` tells a kernel of
    ops/pallas_attention.py by its NAME: the scan's calls are
    ``hvd_ssm_scan_*``, whatever they take (FIVE and SEVEN operands
    today)."""
    from horovod_tpu.jax import introspect
    from horovod_tpu.ops import pallas_scan

    x, delta, a, b, c, d, w = _scan_inputs(t=16, e=128, batch=1)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        pallas_scan.selective_scan(x, delta, a, b, c, d) * w)))(x)
    calls = {}
    for eqn in introspect.equations(jaxpr.jaxpr, skip=("pallas_call",)):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = len(eqn.invars)
    assert calls == {introspect.KERNEL_SSM_SCAN_FWD: 5,
                     introspect.KERNEL_SSM_SCAN_BWD: 7}
    event = ("%%%s.1 = (f32[1,16,128], f32[1,1,16,128]) custom-call("
             "%s), custom_call_target=\"tpu_custom_call\"")
    for name in calls:
        for operands in (3, 5, 6, 7):
            assert tr.flash_kernel(
                event % (name, ", ".join(["%a"] * operands))) == ""
    assert tr.flash_kernel(
        event % ("hvd_flash_dkv", ", ".join(["%a"] * 7))) == "dkv"


# --------------------------------------------- what a layer publishes -----

def _two_readers(remat):
    """Published layers 16 to 21: both publishers, then TWO readers of
    each."""
    cell = _cell("float32", remat=remat, layers_kept=[16, 17, 18, 19, 20, 21])
    model = cell.builder.build(cell.config, cell.traffic)
    key = jax.random.PRNGKey(3)
    params, state = jax.jit(model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **model.pool_kwargs)
    return cell, model, _seen(params), state, pool[0]


@pytest.mark.parametrize("remat", [False, True])
def test_a_published_arrays_gradient_is_the_sum_over_its_readers(remat):
    """With two memory units and two cross-attention layers the
    publishers' own gradients (the scan's leaves of layer 0, the key and
    value projection of layer 1) hold both readers' parts: they are the
    reference's, which differentiates a plain loop over the layers."""
    cell, model, params, state, tokens = _two_readers(remat)
    assert reference.layer_kinds(cell.config) == [
        MAMBA, FULL, GMU, CROSS, GMU, CROSS]
    loss, grads, want, want_grads = _both_sides(cell, model, params, state,
                                                tokens)
    assert loss == pytest.approx(want, rel=2e-6)
    distances = _leaf_distances(grads, want_grads)
    assert max(distances.values()) < 2e-3, max(distances, key=distances.get)
    publishers = [k for k in distances
                  if "['layer_0']['mamba']" in k or "['wkv']" in k]
    assert len(publishers) == 10


def test_the_counters_of_what_is_published_and_read():
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled()

    def read():
        return ({k: transformer._M_SHARED_ARRAYS.labels(role=k).get()
                 for k in ("published", "read")},
                {k: transformer._M_ATTN_LAYERS.labels(kind=k).get()
                 for k in (MAMBA, GMU, CROSS, SLIDING, FULL, "conv")},
                {k: transformer._M_REMAT_BLOCKS.labels(keeps=k).get()
                 for k in ("flash+products", "products")})

    before = read()
    jax.eval_shape(lambda p: model.loss(p, state, tokens)[0], params)
    moved = [{k: after[k] - b[k] for k in after}
             for after, b in zip(read(), before)]
    assert moved[0] == {"published": 2, "read": 2}
    assert moved[1] == {MAMBA: 2, GMU: 1, CROSS: 1, SLIDING: 1, FULL: 1,
                        "conv": 0}
    assert moved[2] == {"flash+products": 3, "products": 3}


@pytest.mark.parametrize("change,message", [
    (dict(scan_from=-1), "no earlier layer published"),
    (dict(kv_from=-1), "no earlier layer published"),
    (dict(scan_from=4), "names a mamba layer"),
    (dict(scan_from=1), "names a mamba layer"),
    (dict(kv_from=0), "names a mamba layer"),
    (dict(kv_from=5), "layer 5 is a cross_attention"),
    (dict(ssm_state=0), "needs BlockSpec.ssm_state"),
    (dict(layer_types=tuple(KINDS[:5]) + ("attention",)), "Unknown"),
])
def test_the_layer_pattern_has_to_fit_the_model(change, message):
    cell = _cell()
    spec = dataclasses.replace(cell.builder.block_spec(cell.config), **change)
    model = cell.builder.build(cell.config, cell.traffic, spec)
    with pytest.raises(ValueError, match=message):
        jax.eval_shape(model.init, jax.random.PRNGKey(0))


# ------------------------------------------------------- recomputation ----

def test_recomputation_changes_no_gradient():
    cell, model, params, state, tokens = _assembled("float32", "flash", True)
    _, other, *_ = _assembled("float32", "flash", False)
    assert model.module.cfg.remat and not other.module.cfg.remat

    def run(m):
        return jax.jit(jax.value_and_grad(m.loss, has_aux=True))(
            params, state, tokens)

    ((loss, _), grads), ((loss2, _), grads2) = run(model), run(other)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    assert max(_leaf_distances(grads, grads2).values()) < 1e-5


def _work(jaxpr, inside=False):
    """(what, inside a ``checkpoint``?) of every ``dot_general`` (its
    operand shapes) and every ``pallas_call`` (its name) of ``jaxpr``, a
    kernel's own body left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], inside
            continue
        if eqn.primitive.name == "dot_general":
            yield tuple(v.aval.shape for v in eqn.invars), inside
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _work(inner, within)


def test_a_recomputed_reader_runs_no_scan_and_no_key_or_value_projection():
    """The gradient's jaxpr under ``remat``: inside the ``checkpoint``
    equations (a block's recomputed forward and its backward) the
    forward scan kernel does not run and no ``dot_general`` has the
    operand shapes of a forward product of a Mamba mixer, a memory
    unit, a key or value projection or a feed-forward: those are kept
    (``_REMAT_KEEPS``), and what a reader reads is its block's INPUT.
    The step's projection (rank 4 deep) alone is multiplied again."""
    from horovod_tpu.jax import introspect
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, state, tokens)[0]))(params)
    work = list(_work(jaxpr.jaxpr))
    fwd, bwd = introspect.KERNEL_SSM_SCAN_FWD, introspect.KERNEL_SSM_SCAN_BWD
    assert work.count((fwd, False)) == 2 and work.count((fwd, True)) == 0
    assert work.count((bwd, True)) == 2
    recomputed = [what for what, inside in work if inside]
    x, m, e = (2, 128, 64), 64, 128
    forward = {
        "mamba in": (x, (m, 2 * e)),
        "mamba [r, B, C]": ((2, 128, e), (e, 4 + 32)),
        "memory unit gate": (x, (m, e)),
        "k or v": (x, (m, 2, 16)),
        "q": (x, (m, 4, 16)),
        "dense up or gate": (x, (m, 96)),
        "step": ((2, 128, 4), (4, e)),
    }
    count = {name: recomputed.count(shapes)
             for name, shapes in forward.items()}
    assert count["step"] == 2, recomputed        # one a mamba layer
    assert count["mamba in"] == count["mamba [r, B, C]"] == 0
    assert count["memory unit gate"] == 0 and count["k or v"] == 0
    assert count["q"] == 0 and count["dense up or gate"] == 0
    # Six flash forward calls, the REQUIRED two maps a differential
    # layer (each over a V of two heads side by side since PR 46; twelve
    # at 64 + 64 before it), none of them again.
    flash = [what for what in work if what[0] == introspect.KERNEL_FLASH_FWD]
    assert flash == [(introspect.KERNEL_FLASH_FWD, False)] * 6
    # A cross-attention block keeps no copy of the keys and values.
    assert introspect.SAVED_FLASH_K in transformer._REMAT_KEEPS
    assert set(transformer._REMAT_KEEPS) - set(transformer._READER_KEEPS) \
        == {introspect.SAVED_FLASH_K, introspect.SAVED_FLASH_V}
    # The control: with nothing kept, every product is made again and
    # the forward scan runs a second time.
    kept = transformer._REMAT_KEEPS, transformer._READER_KEEPS
    transformer._REMAT_KEEPS = transformer._READER_KEEPS = ()
    try:
        bare = jax.make_jaxpr(jax.grad(lambda p: cell.builder.build(
            cell.config, cell.traffic).loss(p, state, tokens)[0]))(params)
    finally:
        transformer._REMAT_KEEPS, transformer._READER_KEEPS = kept
    again = [what for what, inside in _work(bare.jaxpr) if inside]
    assert again.count(fwd) == 2 and again.count(forward["mamba in"]) == 2
    assert again.count(forward["k or v"]) == 4
    assert again.count(forward["memory unit gate"]) == 1


# ------------------------------------------------- the defaults' case -----

@pytest.mark.parametrize("field,default", [
    ("ssm_state", 0), ("ssm_expand", 2), ("scan_from", -1), ("kv_from", -1),
    ("diff_attention", False), ("layer_ids", ())])
def test_the_default_of_each_new_field(field, default):
    from horovod_tpu.models import transformer

    assert getattr(transformer.BlockSpec(), field) == default
    assert getattr(transformer.GPT2_BLOCK, field) == default


def test_the_older_blocks_are_the_defaults_case():
    """The new fields' defaults are what the older blocks are: no scan,
    nothing published or read, plain softmax attention. Their parameter
    trees hold no ``mamba`` and no ``gmu`` and no lambda, and their
    traced losses carry none of the new names."""
    from horovod_tpu.jax import introspect

    for name in ("gpt2m-s1024-c1", "glm47f-s8192-ep8-c1",
                 "trinity-s8192-ep8-c1", "lfm2-s16384-ep4-c1",
                 "keye-s8192-dsa-ep8-c1"):
        cell = cells.load(name, tiny=True)
        model = cell.builder.build(cell.config, cell.traffic)
        params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        layers = {k: v for k, v in params["params"].items()
                  if k.startswith("layer_")}
        assert layers and all(
            "mamba" not in layer and "gmu" not in layer
            and "diff" not in layer.get("attn", {})
            for layer in layers.values()), name
        tokens = jnp.zeros((1, cell.traffic["seq_len"] + 1), jnp.int32)
        traced = str(jax.make_jaxpr(
            lambda p, s: model.loss(p, s, tokens)[0])(params, state))
        for new in (introspect.SAVED_SSM_IN, introspect.SAVED_SSM_Y,
                    introspect.SAVED_GMU_GATE, introspect.SCOPE_SSM_SCAN,
                    introspect.KERNEL_SSM_SCAN_FWD):
            assert new not in traced, (name, new)


def test_the_builder_refuses_what_it_has_no_one_answer_to():
    cell = cells.load(CELL)
    for key, other in (("model_type", "phi3"), ("mlp_bias", True),
                       ("tie_word_embeddings", False), ("mamba_expand", 4),
                       ("mamba_dt_rank", 128), ("num_hidden_layers", 7)):
        config = dict(cell.config, **{key: other})
        with pytest.raises(ValueError, match=key if key != "num_hidden_layers"
                           else "layers_kept"):
            cell.builder.block_spec(config)


# ------------------------------------------------------- the file ---------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "sliding_window", "layer_norm_eps",
        "mb_per_layer", "model_type", "hidden_act", "tie_word_embeddings",
        "mlp_bias", "lm_head_bias", "max_position_embeddings")} == {
        "hidden_size": 2560, "intermediate_size": 10240,
        "num_attention_heads": 40, "num_key_value_heads": 20,
        "sliding_window": 512, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
        "model_type": "phi4flash", "hidden_act": "silu",
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "max_position_embeddings": 262144}
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
        16, 4, 2, math.ceil(2560 / 16))
    # The published map whole: 9 Mamba, 8 sliding, 1 full, 7 memory
    # units, 7 cross-attention; even layers the state-space kinds.
    kinds = config["layer_types"]
    assert len(kinds) == 32
    assert [kinds.count(k) for k in (MAMBA, SLIDING, FULL, GMU, CROSS)] == [
        9, 8, 1, 7, 7]
    assert all((kinds[l] in (MAMBA, GMU)) == (l % 2 == 0) for l in range(32))
    assert kinds[16] == MAMBA and kinds[17] == FULL and kinds[18] == GMU
    assert (config["shared_scan_layer"], config["shared_kv_layer"]) == (16, 17)
    assert config["layers_kept"] == [0, 1, 16, 17, 18, 19]
    assert reference.layer_kinds(config) == KINDS
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 25008)
    assert config["vocab_size"] * 8 == 200064
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    for source in ("arXiv:2507.06607", "arXiv:2410.05258",
                   "arXiv:2312.00752", "modeling_phi4flash.py"):
        assert any(source in text for text in config["assumed"].values())
    assert sum("other reading" in text
               for text in config["assumed"].values()) >= 3
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    assert "eight chips" in config["deployment"]
    assert config["check"]["via"] == "sgd_step"
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s8192-yoco-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 8192, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}
    held = mix["compiled_bytes"]["phi-4-mini-flash-reasoning"][
        "held_bytes_per_chip"]
    assert 0.25 * 16e9 < held < 15.75e9


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Phi-4-mini-flash-reasoning"]
    config = _published()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"])


def test_lambda_init_of_the_layers_kept_by_hand():
    """``0.8 - 0.6 exp(-0.3 l)`` at the PUBLISHED index: the attention
    layers kept are 1, 17 and 19, not 1, 3 and 5."""
    assert reference.lambda_init(1) == pytest.approx(0.355509, abs=1e-6)
    assert reference.lambda_init(17) == pytest.approx(0.796342, abs=1e-6)
    assert reference.lambda_init(19) == pytest.approx(0.797993, abs=1e-6)
    assert reference.lambda_init(3) == pytest.approx(0.556058, abs=1e-6)
    cell, model, params, state, tokens = _assembled()
    assert model.module.cfg.block.layer_ids == (0, 1, 16, 17, 18, 19)
    # Moving a layer's index moves the loss on both sides alike.
    config = dict(cell.config, layers_kept=[0, 1, 2, 3, 18, 19],
                  shared_scan_layer=2, shared_kv_layer=3)
    moved = cell.builder.build(config, cell.traffic)
    loss, want = model.loss(params, state, tokens)[0], \
        moved.loss(params, state, tokens)[0]
    assert abs(float(loss) - float(want)) > 1e-6
    with jax.default_matmul_precision("highest"):
        assert float(moved.reference_loss(params, state, tokens)[0]) \
            == pytest.approx(float(want), rel=2e-6)


def test_the_parameters_by_hand():
    """The program's own tree at the published widths (shapes only)."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    p = params["params"]
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    assert mamba == 41_241_600 == count(p["layer_0"]["mamba"])
    assert p["layer_0"]["mamba"]["w_x"].shape == (5120, 160 + 16 + 16)
    attention = 2560 * 5120 + 2560 * 2560 + 4 * 64 + 128
    assert attention == 19_661_184 == count(p["layer_1"]["attn"])
    assert p["layer_1"]["attn"]["wkv"].shape == (2, 2560, 20, 64)
    assert p["layer_1"]["attn"]["diff"].shape == (4 + 2, 64)
    unit = 2 * 2560 * 5120
    assert unit == 26_214_400 == count(p["layer_4"]["gmu"])
    cross = 2 * 2560 * 2560 + 256 + 128
    assert cross == 13_107_584 == count(p["layer_5"]["attn"])
    assert "wkv" not in p["layer_5"]["attn"]
    rest = 3 * 2560 * 10240 + 2 * 2 * 2560
    assert rest == 78_643_200 + 10_240
    assert [count(p["layer_%d" % i]) for i in range(6)] == [
        119_895_040, 98_314_624, 119_895_040, 98_314_624, 104_867_840,
        91_761_024] == [mixer + rest for mixer in (
            mamba, attention, mamba, attention, unit, cross)]
    assert count(p["embed"]) == 25008 * 2560 == 64_020_480
    assert "lm_head" not in p and "pos" not in p      # tied; no positions
    assert count(params) == 633_048_192 + 64_020_480 + 5_120 == 697_073_792
    assert 11.15e9 < 16 * count(params) < 11.16e9
    assert state == {}


def test_the_step_by_hand():
    from benchmark.builders import phi4flash as builder

    cell = cells.load(CELL)
    sizes = builder.sizes_of(cell.config)
    assert sizes == dict(hidden=2560, n_head=40, n_kv=20, head_dim=64,
                         dense_width=10240, channels=5120, states=16,
                         rank=160)
    s = 8192
    full_pairs, window_pairs = s * (s + 1) // 2, 512 * s - 512 * 511 // 2
    assert flops_afmoe.window_pairs(s, 512) == window_pairs == 4_063_488
    # A pair of heads: two maps, q.k 64 wide and p.v 128 wide each.
    a_pair = 2 * (2 * 64 + 2 * 128)
    attention = lambda pairs, cross: (  # noqa: E731
        2 * 2 * s * 2560 * 2560 + (0 if cross else 2 * 2 * s * 2560 * 1280)
        + 20 * a_pair * pairs)
    assert flops_phi4flash.diff_attention_forward_ops(
        s, hidden=2560, n_head=40, n_kv=20, head_dim=64) \
        == attention(full_pairs, False)
    assert flops_phi4flash.diff_attention_forward_ops(
        s, hidden=2560, n_head=40, n_kv=20, head_dim=64, window=512) \
        == attention(window_pairs, False)
    assert flops_phi4flash.diff_attention_forward_ops(
        s, hidden=2560, n_head=40, n_kv=20, head_dim=64, cross=True) \
        == attention(full_pairs, True)
    scan = s * 5120 * (6 * 16 + 3)
    mamba = (2 * s * 2560 * 10240 + 2 * s * 5120 * 192 + 2 * s * 160 * 5120
             + scan + 2 * s * 5120 * 2560)
    assert flops_phi4flash.mamba_forward_ops(
        s, hidden=2560, channels=5120, states=16, rank=160) == mamba
    unit = 2 * 2 * s * 2560 * 5120
    assert flops_phi4flash.memory_unit_forward_ops(s, 2560, 5120) == unit
    forward = (2 * mamba + attention(window_pairs, False)
               + attention(full_pairs, False) + unit
               + attention(full_pairs, True)
               + 6 * 3 * 2 * s * 2560 * 10240 + 2 * s * 2560 * 25008)
    model = cell.builder.build(cell.config, cell.traffic)
    assert model.step_ops(1) == 3 * forward
    assert 37.0e12 < model.step_ops(1) < 38.0e12
    # The feed-forwards' part of the matmul work: about three quarters.
    assert 0.61 < 3 * 6 * 3 * 2 * s * 2560 * 10240 / model.step_ops(1) < 0.64
    # The scan's own work, a mamba layer: memory-bound on these peaks.
    ops, nbytes = flops_phi4flash.scan_work(s, 5120, 16)
    assert ops == s * 5120 * (25 * 16 + 9)
    assert nbytes == 4 * (8 * s * 5120 + 6 * s * 16 + 3 * 5120 * 17)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, roof = flops.roofline_seconds(ops, nbytes, peak)
    assert roof == "memory" and least == pytest.approx(1.6434e-3, rel=1e-3)


def test_what_the_steps_attention_requires():
    """Three attention layers (sliding at 512, full, cross over the
    published keys), each TWO softmax maps a pair of heads: 20 maps over
    10 key/value heads twice, q.k 64 wide over a V of 128: 2 x (64 +
    128) a pair forward, 2 x (3 x 64 + 2 x 128) backward, whatever
    kernels run them and however many."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    work = model.attention_work(1)
    assert sorted(work) == ["bwd", "fwd"]
    pairs = 4_063_488 + 2 * (8192 * 8193 // 2)
    assert work["fwd"][0] == 2 * 20 * 2 * pairs * (64 + 128)
    assert work["bwd"][0] == 2 * 20 * 2 * pairs * (3 * 64 + 2 * 128)
    # It is what ``model.mfu_pct`` counts for the same layers' pairs.
    assert work["fwd"][0] == sum(
        flops_phi4flash.diff_attention_forward_ops(
            8192, hidden=0, n_head=40, n_kv=20, head_dim=64, window=window)
        for window in (512, None, None))
    # Bytes: q and dQ 20 heads of 64 a map, the output and dO 20 of 128,
    # k 10 of 64, v 10 of 128, a float32 row a map's head; two maps a
    # layer, three layers; the mask does not shrink a panel.
    q, o = 20 * 8192 * 64 * 2, 20 * 8192 * 128 * 2
    k, v = 10 * 8192 * 64 * 2, 10 * 8192 * 128 * 2
    row = 20 * 8192 * 4
    assert work["fwd"][1] == 3 * 2 * (q + k + v + o + row)
    assert work["bwd"][1] == 3 * 2 * (2 * (q + k + v + o) + row)


# ------------------------------------------------------ the new scopes ----

FWD = "jit(step)/jit(main)/jvp(Transformer)/layer_0/"
BWD = "jit(step)/jit(main)/transpose(jvp(Transformer))/layer_0/"


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "mamba/hvd_ssm_scan/hvd_ssm_scan_fwd", "forward", "other"),
    (BWD + "mamba/hvd_ssm_scan/hvd_ssm_scan_bwd", "backward", "other"),
    (FWD + "mamba/hvd_ssm_conv/mul", "forward", "other"),
    (BWD + "gmu/hvd_gmu/mul", "backward", "other"),
    (FWD + "attn/hvd_diff_attn/sub", "forward", "attn"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import ssm_view
    from horovod_tpu.jax import introspect

    assert introspect.SCOPE_SSM_SCAN == ssm_view.SCAN == "hvd_ssm_scan"
    assert (introspect.SCOPE_SSM_CONV, introspect.SCOPE_GMU,
            introspect.SCOPE_DIFF_ATTN) == (
        "hvd_ssm_conv", "hvd_gmu", "hvd_diff_attn")
    assert (ssm_view.MIXER, ssm_view.GMU, ssm_view.ATTN) == (
        "mamba", "gmu", "attn")
    assert (introspect.KERNEL_SSM_SCAN_FWD, introspect.KERNEL_SSM_SCAN_BWD) \
        == ("hvd_ssm_scan_fwd", "hvd_ssm_scan_bwd")
    cell, model, params, state, tokens = _assembled()
    grad = jax.grad(lambda p: model.loss(p, state, tokens)[0])
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for name in ("layer_0/mamba/hvd_ssm_scan", "layer_2/mamba/hvd_ssm_scan",
                 "layer_0/mamba/hvd_ssm_conv", "layer_4/gmu/hvd_gmu",
                 "layer_1/attn/hvd_diff_attn", "layer_3/attn/hvd_diff_attn",
                 "layer_5/attn/hvd_diff_attn",
                 "layer_1/attn/hvd_flash/hvd_flash_fwd", "layer_5/mlp"):
        assert name in text, name
    for name in ("layer_1/mamba", "layer_0/attn", "layer_4/attn",
                 "layer_5/gmu", "rope", "hvd_attn_gate", "/moe"):
        assert name not in text, name
    # The names of what the new blocks keep are traced, and lower to
    # nothing.
    traced = str(jax.make_jaxpr(grad)(params))
    for name in (introspect.SAVED_SSM_IN, introspect.SAVED_SSM_X,
                 introspect.SAVED_SSM_PROJ, introspect.SAVED_SSM_STATES,
                 introspect.SAVED_SSM_Y, introspect.SAVED_SSM_OUT,
                 introspect.SAVED_GMU_GATE, introspect.SAVED_GMU_OUT):
        assert "name=%s]" % name in traced and name not in text, name


def _ssm_step():
    """The recorded step as this model would name it: layer 1 (an
    attention layer) keeps the three kernels; the feed-forward's forward
    matmul becomes layer 0's scan, its backward matmul layer 0's
    in-projection."""
    step = RECORDED_STEP.replace("layer_0/attn", "layer_1/attn").replace(
        "jvp(Transformer)/layer_0/mlp/dot_general",
        "jvp(Transformer)/layer_0/mamba/hvd_ssm_scan/hvd_ssm_scan_fwd",
        1).replace(
        "transpose(jvp(Transformer))/layer_0/mlp/dot_general",
        "transpose(jvp(Transformer))/layer_0/mamba/dot_general")
    assert step.count("hvd_ssm_scan/") == 1 and step.count("/mamba/") == 2
    return step


def test_the_new_readers_on_the_recorded_trace(capsys):
    names = ("ssm.mixer_ms", "ssm.scan_ms", "ssm.scan_roofline",
             "yoco.attn_ms")
    ctx = _ctx(_ssm_step())
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    # The attention module: the three kernels, their glue, the transpose.
    assert got["yoco.attn_ms"] == pytest.approx(sum(
        scope_view.part_ms(ctx, part)
        for part in ("attn", "flash_kernel", "flash_glue")))
    assert 0 < got["ssm.scan_ms"] < got["ssm.mixer_ms"]
    least = 2 * flops.roofline_seconds(
        *flops_phi4flash.scan_work(8192, 5120, 16), ctx.peak)[0]
    assert got["ssm.scan_roofline"] == pytest.approx(
        100 * 1e3 * least / got["ssm.scan_ms"])
    assert "selective scans" in capsys.readouterr().err
    # No layer of the recorded step is a memory unit or a cross one.
    assert reader("ssm.gmu_ms")(ctx) is None
    assert reader("yoco.cross_ms")(ctx) is None
    # The same scopes in a memory unit's layer and a cross layer's.
    later = _ctx(_ssm_step().replace("layer_1/attn", "layer_5/attn").replace(
        "layer_0/mamba/hvd_ssm_scan/hvd_ssm_scan_fwd",
        "layer_4/gmu/hvd_gmu/mul").replace("layer_0/mamba", "layer_4/gmu"))
    later.cell = cells.load(CELL)
    assert reader("yoco.cross_ms")(later) == pytest.approx(
        got["yoco.attn_ms"]) == pytest.approx(reader("yoco.attn_ms")(later))
    assert reader("ssm.gmu_ms")(later) == pytest.approx(got["ssm.mixer_ms"])
    assert reader("ssm.mixer_ms")(later) is None
    # A mamba scope in an ATTENTION layer, an attention scope in a mamba
    # layer: neither is counted.
    crossed = _ctx(_ssm_step().replace("layer_1/", "layer_9/").replace(
        "layer_0/", "layer_1/").replace("layer_9/", "layer_0/"))
    crossed.cell = cells.load(CELL)
    assert all(reader(name)(crossed) is None for name in names)
    # A step without the scan's scope (the parent's program), a cell
    # without mamba layers, a ctx a reader cannot use: nothing, and no
    # exception.
    bare = _ctx(_ssm_step().replace("hvd_ssm_scan/", ""))
    bare.cell = cells.load(CELL)
    assert reader("ssm.mixer_ms")(bare) == pytest.approx(got["ssm.mixer_ms"])
    assert reader("ssm.scan_ms")(bare) is None
    assert reader("ssm.scan_roofline")(bare) is None
    lfm2 = _ctx(_ssm_step())
    lfm2.cell = cells.load("lfm2-s16384-ep4-c1")
    gpt2 = _ctx(_ssm_step())
    gpt2.cell = cells.load("gpt2m-s1024-c1")
    broken = _ctx("HloModule jit_small_step")
    broken.cell = cells.load(CELL)
    broken.win0 = None
    for name in names + ("ssm.gmu_ms", "yoco.cross_ms"):
        assert reader(name)(lfm2) is None, name
        assert reader(name)(gpt2) is None, name
        assert reader(name)(broken) is None, name


def test_the_metrics_of_the_cell():
    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"ssm.mixer_ms", "ssm.scan_ms", "ssm.scan_roofline", "ssm.gmu_ms",
            "yoco.attn_ms", "yoco.cross_ms", "kernel.flash_roofline",
            "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
            "kernel.flash_share_pct",
            "kernel.flash_glue_ms", "model.mfu_pct", "model.step_device_ms",
            "model.head_ms", "device.peak_hbm_gb", "device.idle_pct",
            "device.unscoped_pct", "launch.compile_s",
            "launch.cache_misses"} <= mine
    assert not mine & {"moe.layer_ms", "moe.held_roofline", "mla.attn_ms",
                       "swa.attn_ms", "conv.mixer_ms", "dsa.attn_ms",
                       "sync.collective_ms"}
    new = [m for m in cell.bench["per_layer"]
           if m["name"].startswith(("ssm.", "yoco."))]
    assert len(new) == 6 and {m["name"] for m in new} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "State-space and shared memory"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in new)
    # The new entries stand at the END of their lists.
    assert cell.bench["workloads"][-1]["name"] == CELL
    assert cell.bench["configs"][-1]["name"] == "phi-4-mini-flash-reasoning"
    assert [m["name"] for m in cell.bench["per_layer"][-6:]] == [
        "ssm.mixer_ms", "ssm.scan_ms", "ssm.scan_roofline", "ssm.gmu_ms",
        "yoco.attn_ms", "yoco.cross_ms"]
    assert len(cell.bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) >= 1
    assert len(cell.bench["configs"]) >= 8


def test_the_probes_defects_are_defects():
    """``phi4flash_probe.spoiled_function``: each replaced function
    against the sound one on small arrays."""
    import flax.linen as nn

    from benchmark import phi4flash_probe as probe
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import pallas_scan

    assert len(probe.DEFECTS + probe.BELOW_THE_CHECK) == 8
    # The verdicts' reading, on the chip's own numbers (PR 45, seed
    # 4500000011 under the limit 0.35).
    read = lambda ok, median: {"ok": ok, "grad_rel_l2_median": median}  # noqa
    seed = {"sound": read(True, 0.0262), "reference_fp8": read(False, 0.278),
            "gated_memory": read(False, 0.0371),
            "future_key": read(True, 0.0599)}
    assert probe.not_as_it_has_to_be(seed) == []
    assert probe.not_as_it_has_to_be(
        dict(seed, future_key=read(True, 0.03))) == ["future_key"]
    assert probe.not_as_it_has_to_be(
        dict(seed, gated_memory=read(True, 0.0371), sound=read(
            False, 0.0262))) == ["sound", "gated_memory"]
    x, delta, a, b, c, d, _ = _scan_inputs(t=16, e=128, batch=1)
    with probe.replaced(*probe.spoiled_function("no_d")):
        got = pallas_scan.selective_scan(x, delta, a, b, c, d)
    assert _rel(got + d * x, pallas_scan.selective_scan(
        x, delta, a, b, c, d)) < 1e-6
    with probe.replaced(*probe.spoiled_function("no_softplus")):
        assert float(nn.softplus(jnp.float32(-3.0))) == -3.0
    assert float(nn.softplus(jnp.float32(-3.0))) > 0
    with probe.replaced(*probe.spoiled_function("gated_memory")):
        assert transformer._published_scan(1.0, 2.0) == 2.0
    assert transformer._published_scan(1.0, 2.0) == 1.0
    first, second = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 32))
    sound = transformer._differential_output(first, second, 0.5, 0.2, 1.0)
    want = first - 0.5 * second
    want = 0.8 * want / jnp.sqrt(jnp.mean(want ** 2, -1, keepdims=True) + 1e-5)
    assert _rel(sound, want) < 1e-6
    with probe.replaced(*probe.spoiled_function("no_subtraction")):
        plain = transformer._differential_output(first, second, 0.5, 0.2, 1.0)
    assert _rel(plain, transformer._differential_output(
        first, 0 * second, 0.5, 0.2, 1.0)) < 1e-6
    with probe.replaced(*probe.spoiled_function("no_factor")):
        whole = transformer._differential_output(first, second, 0.5, 0.2, 1.0)
    assert _rel(0.8 * whole, sound) < 1e-6
    cfg = _assembled()[1].module.cfg
    q, k, v = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 32, 2, 16))
    with probe.replaced(*probe.spoiled_function("future_key")):
        ahead = transformer._attend(cfg, q, k, v)
    sound = transformer._attend(cfg, q, k, v)
    # Position 0 now sees key 1 alone: its output is v[1].
    assert _rel(ahead[0, 0], v[0, 1]) < 1e-5 < _rel(sound[0, 0], v[0, 1])
    assert _rel(sound[0, 0], v[0, 0]) < 1e-5


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4500000003", "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 76
    assert line["check"]["leaves_all_zero"] == 0
    assert line["check"]["loss_rel"] < 2e-4


def test_no_position_sees_a_later_token():
    """One token changed at position 70: no logit before it moves, on
    either side (the window's loss falls below ln 4 because the model
    learns its pool of sequences by heart, not because it sees ahead)."""
    cell, model, params, state, tokens = _assembled("float32", "flash", False)
    inputs = tokens[:1, :-1]
    changed = inputs.at[0, 70].set((inputs[0, 70] + 1) % 512)
    with jax.default_matmul_precision("highest"):
        sides = {
            "program": [model.module.apply(params, t)
                        for t in (inputs, changed)],
            "reference": [reference.forward(cell.config, params, state, t)
                          for t in (inputs, changed)]}
    for name, (a, b) in sides.items():
        moved = jnp.abs(a - b).max(-1)[0]
        assert float(moved[:70].max()) == 0.0, name
        assert float(moved[70:].max()) > 1e-3, name
