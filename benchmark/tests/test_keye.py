"""Keye-VL-2.0's language model on the CPU at the builder's ``TINY`` widths
(hidden 64; two expert layers that hold 2 of the 16 experts of 32 they
route over by a softmax router, 2 a token renormalised, no shared expert;
4 query heads over 2 key/value heads of 32; an indexer of 4 heads of 16
that keeps 32 keys of a sequence of 128, so three queries in four choose;
vocabulary 512, the head untied): the program against
``benchmark/reference/keye_vl2.py`` on seeded weights, block by block and
whole, at free and at forced routing and selection; the selection against
a loop over rows; the masked kernels against dense attention under the
same mask; the sectioned rotation with equal components against RoPE;
recomputation; the eight shares against the uncut layer; the counting of
``flops_keye.py`` by hand; the new scopes and their readers.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order: logits to 1e-4 of their largest entry, the
loss to 1e-5, every gradient leaf to 1e-3 relative L2. That holds at
FREE routing and selection too: no token of these seeds changes an
expert and no pair its side of a threshold (asserted).
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_afmoe, flops_glm, flops_keye, scope_view
from benchmark import trace_reduce as tr
from benchmark import traffic
from benchmark.layer_metrics import reader
from benchmark.reference import keye_vl2 as reference
from benchmark.tests.test_lfm2 import _matmuls
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import (AS_RECORDED, NAMED,
                                             RECORDED_STEP, _ctx, named)
from benchmark.tests.test_trinity import _seen

CELL = "keye-s8192-dsa-ep8-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
SPARSE = "sparse_attention"
INDEXER = ("index_wq", "index_wk", "index_ww", "index_k_norm")


def _assembled(dtype, attention="flash"):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention)
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(asm.model.init)(key)
    assert state == {}
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    # The LayerNorm's bias off zero too: ``_seen`` moves what is 1-D and
    # not zero.
    params = _seen(params)
    for i in range(cell.config["num_hidden_layers"]):
        norm = params["params"]["layer_%d" % i]["attn"]["index_k_norm"]
        norm["bias"] = 0.1 * jnp.sin(jnp.arange(norm["bias"].shape[0]) + i)
    return cell, asm.model, params, state, pool[0]


def _random_assignments(key, config, tokens):
    noise = jax.random.uniform(key, (
        config["num_hidden_layers"], tokens, config["experts_routed_over"]))
    return list(jnp.argsort(noise, -1)[
        ..., :config["num_experts_per_tok"]].astype(jnp.int32))


def _random_selections(key, config, batch, s):
    """Per layer a (B, S, S) mask that has nothing to do with any
    indexer: half the pairs, the diagonal always (a row keeps a key)."""
    keep = jax.random.bernoulli(
        key, 0.5, (config["num_hidden_layers"], batch, s, s))
    return list(keep | jnp.eye(s, dtype=bool))


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("choice,attention", [
    ("free", "flash"), ("forced", "flash"), ("free", "dense")])
def test_float32_program_is_the_reference(choice, attention):
    from horovod_tpu.parallel import moe

    cell, model, params, state, tokens = _assembled("float32", attention)
    config = cell.config
    b, s = tokens.shape[0], tokens.shape[1] - 1
    assignments = selections = None
    if choice == "forced":
        assignments = _random_assignments(jax.random.PRNGKey(5), config,
                                          b * s)
        selections = _random_selections(jax.random.PRNGKey(6), config, b, s)

    want, aux = jax.jit(lambda p, x: reference.forward(
        config, p, x, assignments, selections))(params, tokens[:, :-1])
    got, sown = jax.jit(lambda p, x: model.module.apply(
        {"params": p["params"]}, x, assignments, selections,
        mutable=["moe", "dsa"]))(params, tokens[:, :-1])
    stats = moe.sown_stats(sown)
    assert (np.sort(np.asarray(stats["experts"]), -1)
            == np.sort(np.asarray(aux["chosen"]), -1)).all()
    kept = np.asarray(aux["select"]).sum((1, 2, 3))
    count = b * sum(min(t + 1, 32) for t in range(s))
    if choice == "free":
        # The indexers chose: three queries in four, the same pairs on
        # both sides (the sown count is the reference's mask's).
        sown_kept = np.asarray(cell.builder.sown_kept(sown, 2))
        assert (sown_kept == kept).all() and (kept >= count).all()
        assert (kept < 1.02 * count).all()
        assert count < 0.5 * b * s * (s + 1) // 2
    else:
        assert "dsa" not in sown
    assert float(jnp.max(jnp.abs(got - want))) \
        < 1e-4 * float(jnp.max(jnp.abs(want)))
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.shape == (2, 16)
    assert (counts.sum(-1) == b * s * config["num_experts_per_tok"]).all()
    assert (np.asarray(stats["rows_held"]) == counts[:, :2].sum(-1)).all()
    assert (np.asarray(stats["rows_held"]) > 0).all()

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_stats(p, tokens, assignments,
                                       selections)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(config, p, state, tokens, assignments,
                                 selections)[0]))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    # The load-balancing term is in both.
    assert float(loss) > float(jnp.mean(jax.nn.logsumexp(got, -1)
                                        - jnp.take_along_axis(
        got, tokens[:, 1:, None], -1)[..., 0]))
    # embed, lm_head, ln_f; a layer: two block norms, attention's wq,
    # wkv, wo and two head norms, the indexer's three matrices and its
    # LayerNorm's two, router + 3 held.
    flat = {jax.tree_util.keystr(path): (g, w) for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(grads),
        jax.tree.leaves(ref_grads))}
    assert len(flat) == 3 + 2 * (2 + 5 + 5 + 4)
    dead = {k: v for k, v in flat.items() if "index_" in k}
    assert len(dead) == 2 * 5
    for g, w in dead.values():    # EXACTLY zero, on both sides
        assert not np.asarray(g).any() and not np.asarray(w).any()
    live = {k: _rel(g, w) for k, (g, w) in flat.items() if k not in dead}
    assert max(live.values()) < 1e-3, live


def test_bf16_program_at_forced_choices_is_inside_gpt2s_bounds():
    cell, model, params, state, tokens = _assembled("bfloat16")
    config = cell.config
    with open(os.path.join(CONFIGS, "gpt2-medium.json")) as f:
        bounds = json.load(f)["check"]
    aux = jax.jit(lambda p, x: reference.forward(config, p, x)[1])(
        params, tokens[:, :-1])
    chosen, select = list(aux["chosen"]), list(aux["select"])
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_stats(p, tokens, chosen, select)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(config, p, state, tokens, chosen,
                                 select)[0]))(params)
    assert abs(float(loss) - float(ref_loss)) \
        < bounds["loss_rtol"] * float(ref_loss)
    distances = {k: v for k, v in _leaf_distances(grads, ref_grads).items()
                 if "index_" not in k}
    assert max(distances.values()) < bounds["grad_rel_l2"], distances
    assert max(distances.values()) > 1e-3, distances


def test_the_check_of_the_cell_in_float32():
    """``run.py``'s own comparison (``check.sgd_step_gradients`` against
    the reference, free routing and selection). The ten indexer leaves
    read zero on both sides, which the check counts and passes over."""
    got = _compare(CELL, "float32", 1)
    assert got["loss_rel"] < 1e-5 and got["grad_rel_l2_max"] < 1e-3, got
    assert got["leaves"] == 35 and got["leaves_all_zero"] == 10, got


# ------------------------------------------------------ the selection -----

def _by_hand(scores, topk):
    """A loop over rows: ``sorted``, the ``topk``-th largest of the
    finite entries, everything at or above it."""
    scores = np.asarray(scores, np.float64)
    keep = np.zeros(scores.shape, bool)
    for t, row in enumerate(scores):
        finite = [x for x in row if x > -np.inf]
        if not finite:
            continue
        tau = -np.inf if len(finite) <= topk \
            else sorted(finite, reverse=True)[topk - 1]
        keep[t] = (row >= tau) & (row > -np.inf)
    return keep


def _causal_scores(key, s, ties=False):
    scores = jax.random.normal(jax.random.PRNGKey(key), (s, s))
    if ties:      # a grid of values: many equal scores, zeros of both signs
        scores = jnp.round(scores * 2) / 2 * jnp.where(
            jnp.arange(s) % 2 == 0, 1.0, -1.0)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)


@pytest.mark.parametrize("ties", [False, True])
def test_the_selection_against_a_loop_over_rows(ties):
    from horovod_tpu.models import transformer

    s, k = 40, 8
    scores = _causal_scores(1, s, ties)
    want = _by_hand(scores, k)
    got = np.asarray(jax.jit(
        lambda x: transformer.select_rows(x, k))(scores))
    ref = np.asarray(jax.jit(
        lambda x: reference.selection(x[None], k)[0])(scores))
    # At the same scores the program's bisection, the reference's sort
    # and the loop agree on EVERY pair.
    assert (got == want).all() and (ref == want).all()
    kept = want.sum(-1)
    assert (kept[:k] == np.arange(1, k + 1)).all()     # t + 1 <= k: all
    if ties:
        assert kept[k] >= k and (kept[k:] >= k).all() and (kept > k).any()
    else:
        assert kept[k] == k                            # t + 1 = k + 1
        assert (kept[k:] == k).all()
    assert not want[np.triu_indices(s, 1)].any()       # no future key
    # The threshold itself, against ``sorted``, -inf where t + 1 <= k.
    tau = np.asarray(jax.jit(
        lambda x: transformer.kth_largest(x, k))(scores))
    for t in range(s):
        row = sorted(np.asarray(scores[t]), reverse=True)
        assert tau[t] == np.float32(row[k - 1])
    assert (tau[:k - 1] == -np.inf).all() and np.isfinite(tau[k - 1:]).all()


def test_a_selection_of_every_key_is_full_attention():
    """``index_topk`` >= S: every causal pair is kept and the masked
    kernels' results are the static kernels', bit for bit."""
    from horovod_tpu.models import transformer
    from horovod_tpu.ops.pallas_attention import (
        flash_attention,
        pack_selection,
    )

    s = 128       # one tile of 128 either way: the same sums, bit for bit
    keep = transformer.select_rows(_causal_scores(2, s), s + 5)
    assert (np.asarray(keep) == np.tril(np.ones((s, s), bool))).all()
    assert (np.asarray(reference.selection(_causal_scores(2, s)[None], s))
            == np.tril(np.ones((s, s), bool))).all()
    q, k, v, g = (jax.random.normal(jax.random.PRNGKey(i), (1, s, h, 32))
                  for i, h in ((0, 4), (1, 2), (2, 2), (3, 4)))

    def both(select):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, select=select), q, k, v)
        return (out,) + vjp(g)

    for a, b in zip(both(pack_selection(keep[None])), both(None)):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("shape", [
    (2, 200, 4, 2, 32),      # grouped heads, padded S, two sequences
    (1, 256, 4, 4, 16),      # one key/value head a query head
    (1, 72, 8, 1, 32),       # ONE key/value head, a short padded S
])
def test_the_masked_kernels_against_dense_attention(shape):
    """Forward, dK/dV and dQ in interpret mode under a mask that is
    data, against ``_dense_causal_attention`` under the same mask."""
    from horovod_tpu.models.transformer import _dense_causal_attention
    from horovod_tpu.ops.pallas_attention import (
        flash_attention,
        pack_selection,
    )

    b, s, h, h_kv, d = shape
    keys = jax.random.split(jax.random.PRNGKey(s), 5)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k, v = (jax.random.normal(key, (b, s, h_kv, d)) for key in keys[1:3])
    g = jax.random.normal(keys[3], (b, s, h, d))
    keep = jax.random.bernoulli(keys[4], 0.4, (b, s, s)) \
        | jnp.eye(s, dtype=bool)
    select = pack_selection(keep)
    assert select.by_query.shape == select.by_key.shape == (b, 1, s, 128)
    assert select.by_query.dtype == jnp.int32
    # Bit b of word [m, r, j] is column (32 m + b) 128 + j of row r; the
    # second plane packs the transposed mask the same way.
    for plane, mask in ((select.by_query, keep),
                        (select.by_key, jnp.swapaxes(keep, 1, 2))):
        words = np.asarray(plane).astype(np.uint32)[:, 0]      # (B, S, 128)
        bits = (words[:, :, None, :] >> np.arange(32, dtype=np.uint32)[
            None, None, :, None]) & 1                    # (B, S, 32, 128)
        assert (bits.reshape(b, s, 4096)[:, :, :s].astype(bool)
                == np.asarray(mask)).all()
        assert not bits.reshape(b, s, 4096)[:, :, s:].any()

    def both(attend):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(g)

    got = both(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                               select=select))
    want = both(lambda q, k, v: _dense_causal_attention(
        q, k, v, jnp.float32, select=keep))
    for a, w in zip(got, want):
        assert a.shape == w.shape
        assert _rel(a, w) < 1e-5
    # The mask matters: without it the results are others.
    free = both(lambda q, k, v: flash_attention(q, k, v, causal=True))
    assert _rel(free[0], want[0]) > 1e-2
    # What the planes say above the diagonal is not read.
    loud = pack_selection(keep | jnp.triu(jnp.ones((s, s), bool), 1))
    again = both(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 select=loud))
    for a, w in zip(again, got):
        assert (np.asarray(a) == np.asarray(w)).all()


def test_a_selection_needs_its_planes_and_no_window():
    from horovod_tpu.ops.pallas_attention import (
        flash_attention,
        pack_selection,
    )

    q = jnp.zeros((1, 64, 2, 16))
    select = pack_selection(jnp.ones((1, 64, 64), bool))
    for kwargs in (dict(window=8), dict(causal=False)):
        with pytest.raises(ValueError, match="select needs"):
            flash_attention(q, q, q, select=select, **kwargs)
    with pytest.raises(ValueError, match="select needs"):
        flash_attention(q, q, q, select=pack_selection(
            jnp.ones((1, 32, 64), bool)))
    # A plane of 4097 keys takes a second word.
    wide = pack_selection(jnp.zeros((1, 8, 4097), bool).at[0, 3, 4096].set(
        True))
    assert wide.by_query.shape == (1, 2, 8, 128)
    assert int(wide.by_query[0, 1, 3, 0]) == 1
    assert int(jnp.abs(wide.by_query).sum()) == 1
    assert wide.by_key.shape == (1, 1, 4097, 128)
    assert int(wide.by_key[0, 0, 4096, 3]) == 1


def test_sectioned_rotation_with_equal_components_is_rope():
    """``rope_scaling.mrope_section`` [16, 24, 24] over the 64 rotary
    pairs of a 128-wide head: a text token carries one index in all
    three components, and the rotation is the program's ``rope``."""
    from horovod_tpu.models import transformer

    config = cells.load(CELL).config
    sections = config["rope_scaling"]["mrope_section"]
    assert sections == [16, 24, 24] and sum(sections) * 2 \
        == config["head_dim"]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 3, 128))
    text = jnp.broadcast_to(jnp.arange(48), (3, 2, 48))
    got = reference.sectioned_rope(x, text, config["rope_theta"], sections)
    assert _rel(got, transformer.rope(x, 0, float(config["rope_theta"]))) \
        < 1e-6
    assert _rel(got, reference._rope(x, config["rope_theta"])) < 1e-6
    # An image patch's height differs from its time: another rotation,
    # in the second section's pairs and nowhere else.
    image = text.at[1].add(7)
    other = reference.sectioned_rope(x, image, config["rope_theta"],
                                     sections)
    moved = np.asarray(jnp.abs(other - got).max((0, 1, 2))) > 1e-6
    assert moved.reshape(2, 64)[:, 16:40].all()
    assert not moved.reshape(2, 64)[:, :16].any()
    assert not moved.reshape(2, 64)[:, 40:].any()


# ------------------------------------------------------ block by block ----

def _tiny_cfg(**changes):
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    cfg = cell.builder.module_of(cell.config, cell.traffic).cfg
    return cell.config, dataclasses.replace(cfg, **changes)


def _x(key, s=96, m=64):
    return jax.random.normal(jax.random.PRNGKey(key), (1, s, m))


@pytest.mark.parametrize("attention", ["flash", "dense"])
def test_the_attention_block_and_its_indexer(attention):
    from flax.core import meta
    from horovod_tpu.models import transformer

    config, cfg = _tiny_cfg(attention=attention)
    x = _x(3)
    attn = transformer.SelfAttention(cfg, None, True, True)
    p = _seen(meta.unbox(jax.jit(attn.init)(jax.random.PRNGKey(4), x)))[
        "params"]
    assert sorted(p) == sorted(INDEXER + ("wq", "wkv", "wo", "q_norm",
                                          "k_norm"))
    assert p["index_wq"].shape == (64, 4, 16)
    assert p["index_wk"].shape == (64, 16) and p["index_ww"].shape == (64, 4)
    assert sorted(p["index_k_norm"]) == ["bias", "scale"]
    p["index_k_norm"]["bias"] = 0.1 * jnp.cos(jnp.arange(16.0))
    want, select = jax.jit(
        lambda p: reference._attention(x, p, config))(p)
    got, sown = jax.jit(lambda p: attn.apply(
        {"params": p}, x, mutable=["dsa"]))(p)
    assert _rel(got, want) < 1e-5
    kept = int(np.asarray(select).sum())
    assert int(sown["dsa"]["dsa_kept"][0]) == kept
    # Four heads' rectified products tie at zero now and then.
    count = sum(min(t + 1, 32) for t in range(96))
    assert count <= kept < 1.01 * count
    # The scores themselves, pair by pair, and the defects a reading
    # must catch: no ReLU, the weights left out.
    u = x
    scores = reference.index_scores(u, p, config)
    q_i = transformer.rope(jnp.einsum("bsm,mjd->bsjd", u, p["index_wq"]),
                           0, 1e7)
    k_i = reference._layer_norm(u @ p["index_wk"], p["index_k_norm"]["scale"],
                                p["index_k_norm"]["bias"], 1e-6)
    k_i = transformer.rope(k_i[:, :, None], 0, 1e7)[:, :, 0]
    w_i = (u @ p["index_ww"]) * 64 ** -0.5
    mine = transformer.index_scores(q_i, k_i, w_i, 0)
    finite = np.isfinite(np.asarray(scores))
    assert (finite[0] == np.tril(np.ones((96, 96), bool))).all()
    assert (np.isfinite(np.asarray(mine)) == finite).all()
    assert _rel(jnp.where(finite, mine, 0), jnp.where(finite, scores, 0)) \
        < 1e-5
    dots = jnp.einsum("bqjd,bsd->bqjs", q_i, k_i)
    for spoiled in (jnp.sum(w_i[..., None] * dots, 2),
                    jnp.sum(jax.nn.relu(dots), 2)):
        assert _rel(jnp.where(finite, spoiled, 0),
                    jnp.where(finite, scores, 0)) > 0.3
    # A forced selection replaces the indexer's and sows nothing.
    forced = jnp.tril(jnp.ones((1, 96, 96), bool), -0) & ~jnp.tril(
        jnp.ones((1, 96, 96), bool), -5)
    out, sown = jax.jit(lambda p: attn.apply(
        {"params": p}, x, forced, mutable=["dsa"]))(p)
    assert "dsa" not in sown
    assert _rel(out, reference._attention(x, p, config, forced)[0]) < 1e-5
    assert _rel(out, got) > 1e-2


def _expert_layer(cfg):
    from horovod_tpu.parallel.moe import MoeMlp

    return MoeMlp(cfg, None)


def test_the_eight_shares_are_the_whole_layer():
    """What ties the share to the model: chips 0..7 each hold two of the
    16 experts; their routed parts add up to the uncut reference's layer
    (a softmax router, no shared expert, no bias)."""
    from flax.core import meta

    config, cfg = _tiny_cfg()
    x = _x(6)
    y = x[0]
    whole = _expert_layer(dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, experts_held=0)))
    p = meta.unbox(jax.jit(whole.init)(jax.random.PRNGKey(7), x))["params"]
    assert p["wi"].shape == (16, 64, 32) and p["router"].shape == (64, 16)
    want = reference.whole_layer(y, p, config)
    total, rows = jnp.zeros_like(y), 0
    for chip in range(8):
        first = 2 * chip
        layer = _expert_layer(dataclasses.replace(
            cfg, block=dataclasses.replace(
                cfg.block, experts_held=2, first_expert_held=first)))
        mine = dict(p, **{w: p[w][first:first + 2]
                          for w in ("wi", "wg", "wo")})
        out, sown = jax.jit(lambda q, layer=layer: layer.apply(
            {"params": q}, x, mutable=["moe"]))(mine)
        assert int(sown["moe"]["tokens_per_expert"][0].sum()) == 96 * 2
        rows += int(sown["moe"]["rows_held"][0])
        total = total + out[0]
        ref, _, load_balance = reference._experts(
            y, mine, dict(config, first_expert_held=first), None)
        assert _rel(out[0], ref) < 1e-5
        # The load-balancing term is over ALL experts: every chip's.
        assert float(sown["moe"]["load_balance"][0]) == pytest.approx(
            float(load_balance), rel=1e-5)
    assert rows == 96 * 2               # each pair computed exactly once
    assert _rel(total, want) < 1e-5
    out = jax.jit(lambda q: whole.apply({"params": q}, x,
                                        mutable=["moe"])[0])(p)
    assert _rel(out[0], want) < 1e-5
    # Gates renormalised over the chosen: a token's gates over all 16
    # experts add to one.
    gates, chosen, probs = reference.gates_over_all_experts(
        y, p["router"], config)
    assert np.allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray((gates > 0).sum(-1)) == 2).all()


# ------------------------------------------------------ recomputation -----

def test_recomputation_changes_no_gradient():
    cell, model, params, state, tokens = _assembled("float32")
    plain = cells.load(CELL, tiny=True)
    plain.config["compute_dtype"] = "float32"
    plain.traffic["remat"] = False
    assert cell.traffic["remat"] is True
    other = plain.builder.build(plain.config, plain.traffic)
    assert model.module.cfg.remat and not other.module.cfg.remat

    def run(m):
        return jax.jit(jax.value_and_grad(m.loss, has_aux=True))(
            params, state, tokens)

    ((loss, _), grads), ((loss2, _), grads2) = run(model), run(other)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    live = {k: v for k, v in _leaf_distances(grads, grads2).items()
            if "index_" not in k}
    assert max(live.values()) < 1e-5


def _loops(jaxpr, inside=False):
    """(primitive name, inside a ``checkpoint``?) of every ``while`` and
    ``scan`` of ``jaxpr``, a kernel's own left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("while", "scan"):
            yield eqn.primitive.name, inside
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _loops(inner, within)


def test_a_recomputed_sparse_block_neither_scores_nor_selects():
    """The gradient's jaxpr under ``remat``: inside the ``checkpoint``
    equations (a block's recomputed forward and its backward) no
    ``dot_general`` has the operand shapes of one of the indexer's
    projections or of its dot products, and there is no loop (the
    indexer's passes over blocks of queries, the bisection): the two bit
    planes are kept (``_REMAT_KEEPS``) and all three kernels read them.
    What a block multiplies again: its router's logits and the q and k
    projections that stand before the head norms."""
    cell, model, params, state, tokens = _assembled("float32")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, state, tokens)[0]))(params)
    t, m = 2 * 128, 64
    recomputed = [shapes for shapes, inside in _matmuls(jaxpr.jaxpr)
                  if inside]
    forward = {
        "index q": ((2, 128, m), (m, 4, 16)),
        "index k": ((2, 128, m), (m, 16)),
        "index w": ((2, 128, m), (m, 4)),
        "index dots": ((2, 128, 4, 16), (2, 128, 16)),
        "router": ((t, m), (m, 16)),
        "q": ((2, 128, m), (m, 4, 32)),
        "k or v": ((2, 128, m), (m, 2, 32)),
    }
    count = {name: recomputed.count(shapes)
             for name, shapes in forward.items()}
    assert count["index q"] == count["index k"] == count["index w"] == 0
    assert count["index dots"] == 0
    assert count["router"] == 2            # one a layer
    assert count["q"] == 2 and count["k or v"] == 2
    assert not [name for name, inside in _loops(jaxpr.jaxpr) if inside]
    # They do run, once, in the first forward.
    first = [shapes for shapes, inside in _matmuls(jaxpr.jaxpr)
             if not inside]
    assert first.count(forward["index q"]) == 2
    assert sum(not inside for _, inside in _loops(jaxpr.jaxpr)) >= 2
    traced = str(jaxpr)
    assert traced.count("name=hvd_flash_select]") == 2 * 2   # two planes
    # The control: with nothing kept, a block scores and selects again.
    from horovod_tpu.models import transformer

    kept = transformer._REMAT_KEEPS
    transformer._REMAT_KEEPS = ()
    try:
        bare = jax.make_jaxpr(jax.grad(lambda p: cell.builder.build(
            cell.config, cell.traffic).loss(p, state, tokens)[0]))(params)
    finally:
        transformer._REMAT_KEEPS = kept
    again = [shapes for shapes, inside in _matmuls(bare.jaxpr) if inside]
    assert again.count(forward["index q"]) == 2
    assert [name for name, inside in _loops(bare.jaxpr) if inside]


def test_the_counters_of_a_sparse_model():
    """``hvd_attn_layers_total{kind}`` counts the sparse layers,
    ``hvd_dsa_pairs_total{kind}`` a layer's causal and kept pairs,
    ``hvd_flash_tiles_total{kernel,kind}`` the masked kernels' tiles
    under ``learned``, ``hvd_remat_blocks_total`` a block with a
    kernel."""
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import pallas_attention

    cell, model, params, state, tokens = _assembled("float32")
    tiles = {(kernel, kind): pallas_attention._M_TILES.labels(
        kernel=kernel, kind=kind) for kernel in (
            "hvd_dsa_fwd", "hvd_dsa_dkv", "hvd_dsa_dq", "hvd_flash_fwd")
        for kind in ("learned", "skipped", "full", "edge")}

    def read():
        return (
            {k: transformer._M_ATTN_LAYERS.labels(kind=k).get()
             for k in (SPARSE, "full_attention")},
            {k: transformer._M_DSA_PAIRS.labels(kind=k).get()
             for k in ("causal", "kept")},
            {k: v.get() for k, v in tiles.items()},
            transformer._M_REMAT_BLOCKS.labels(keeps="flash+products").get())

    before = read()
    jax.eval_shape(jax.grad(lambda p: model.loss(p, state, tokens)[0]),
                   params)
    after = read()
    layers = {k: after[0][k] - before[0][k] for k in after[0]}
    assert layers[SPARSE] > 0 and layers["full_attention"] == 0
    assert layers[SPARSE] % 2 == 0
    traces = layers[SPARSE] // 2
    pairs = {k: after[1][k] - before[1][k] for k in after[1]}
    assert pairs["causal"] == traces * 2 * 2 * (128 * 129 // 2)
    assert pairs["kept"] == traces * 2 * 2 * sum(
        min(t + 1, 32) for t in range(128))
    moved = {k: after[2][k] - before[2][k] for k in tiles}
    for kernel in ("hvd_dsa_fwd", "hvd_dsa_dkv", "hvd_dsa_dq"):
        assert moved[kernel, "learned"] > 0, kernel      # one 128 x 128 tile
        assert moved[kernel, "full"] == moved[kernel, "edge"] == 0
    assert not any(moved["hvd_flash_fwd", kind] for kind in (
        "learned", "full", "edge"))
    assert after[3] - before[3] == 2 * traces
    assert pallas_attention._Tiles(512, 512, True, 8192, 8192, None,
                                   True).counts() == {
        "learned": 136, "skipped": 120}
    assert pallas_attention._Tiles(512, 512, True, 8192, 8192).counts() == {
        "full": 120, "edge": 16, "skipped": 120}


# ------------------------------------------------- the defaults' case -----

def test_the_older_blocks_are_the_defaults_case():
    """The new fields' defaults are what the older blocks are: no
    indexer, no sparse layer. Their parameter trees hold no ``index_*``
    leaf and their traced losses carry none of the selection's names and
    no masked kernel."""
    from horovod_tpu import models
    from horovod_tpu.jax import introspect
    from horovod_tpu.models import transformer

    spec = models.BlockSpec()
    assert (spec.index_heads, spec.index_head_dim, spec.index_topk) \
        == (0, 0, 0)
    for name in ("gpt2m-s1024-c1", "olmoe-s4096-c1", "glm47f-s8192-ep8-c1",
                 "trinity-s8192-ep8-c1", "lfm2-s16384-ep4-c1"):
        cell = cells.load(name, tiny=True)
        model = cell.builder.build(cell.config, cell.traffic)
        if hasattr(model, "module"):
            assert SPARSE not in transformer._layer_kinds(model.module.cfg)
        params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert not [path for path, _ in
                    jax.tree_util.tree_leaves_with_path(params)
                    if "index_" in jax.tree_util.keystr(path)], name
        tokens = jnp.zeros((1, cell.traffic["seq_len"] + 1), jnp.int32)
        traced = str(jax.make_jaxpr(jax.grad(
            lambda p, s: model.loss(p, s, tokens)[0]))(params, state))
        for new in (introspect.SAVED_FLASH_SELECT, introspect.KERNEL_DSA_FWD,
                    introspect.KERNEL_DSA_DKV, introspect.KERNEL_DSA_DQ,
                    introspect.SCOPE_DSA_INDEX, introspect.SCOPE_DSA_SELECT):
            assert new not in traced, (name, new)
    # This configuration names no ``layer_types``: the indexer's
    # ``index_topk`` makes every layer a sparse one.
    cell = cells.load(CELL, tiny=True)
    cfg = cell.builder.module_of(cell.config, cell.traffic).cfg
    assert cfg.block.layer_types == ()
    assert transformer._layer_kinds(cfg) == (SPARSE, SPARSE)


def test_a_sparse_layer_has_to_fit_the_model():
    from horovod_tpu import models

    def init(block, **cfg):
        model = models.Transformer(models.TransformerConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=8,
            max_seq_len=8, block=block, **cfg))
        return jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    sparse = models.BlockSpec(positions="rope", index_heads=2,
                              index_head_dim=8, index_topk=4)
    assert "index_wq" in init(sparse)["params"]["layer_1"]["attn"]
    mixed = dataclasses.replace(
        sparse, layer_types=("full_attention", SPARSE))
    tree = init(mixed)["params"]
    assert "index_wq" not in tree["layer_0"]["attn"]
    assert "index_wq" in tree["layer_1"]["attn"]
    with pytest.raises(ValueError, match="index_heads"):
        init(models.BlockSpec(layer_types=(SPARSE, SPARSE)))
    with pytest.raises(ValueError, match="learned selection"):
        init(dataclasses.replace(sparse, attention_kind="latent",
                                 q_lora_rank=8, kv_lora_rank=8,
                                 qk_nope_head_dim=4, qk_rope_head_dim=4,
                                 v_head_dim=8))
    with pytest.raises(ValueError, match="seq_axis"):
        init(sparse, seq_axis="seq")
    with pytest.raises(ValueError, match="learned selection"):
        init(sparse, attention="ring")
    with pytest.raises(ValueError, match="Unknown attention layer type"):
        init(models.BlockSpec(layer_types=("sparse", "sparse")))


def test_the_planner_counts_the_held_expert_leaves():
    import horovod_tpu as hvd

    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = hvd.plan(params, batch=1, chips=1, **model.plan_kwargs)
    assert plan.workload.num_experts == 16
    # Five expert layers of three (16, 2048, 768) float32 panels.
    assert plan.workload.expert_param_bytes == 5 * 3 * 16 * 2048 * 768 * 4
    assert plan.workload.param_bytes == 562_290_560 * 4


def test_the_builder_refuses_what_it_has_no_one_answer_to():
    from benchmark.builders import keye_vl2 as builder

    cell = cells.load(CELL)
    builder.block_spec(cell.config)
    for key, value in (("model_type", "qwen3_moe"), ("attention_bias", True),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("norm_topk_prob", False),
                       ("use_sliding_window", True), ("sliding_window", 4096),
                       ("tie_word_embeddings", True),
                       ("first_k_dense_replace", 1), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            builder.block_spec(dict(cell.config, **{key: value}))
    with pytest.raises(ValueError, match="ONE key head"):
        builder.block_spec(dict(cell.config, sa_config=dict(
            cell.config["sa_config"], indexer_num_kv_heads=2)))


# ---------------------------------------------------------- flops_keye ----

def _published():
    with open(os.path.join(CONFIGS, "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "experts_routed_over", "num_local_experts",
        "rope_theta", "rms_norm_eps", "norm_topk_prob",
        "max_position_embeddings", "model_type", "tie_word_embeddings")} == {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "experts_routed_over": 128, "num_local_experts": 128,
        "rope_theta": 10000000, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
        "max_position_embeddings": 262144, "model_type": "KeyeVL2",
        "tie_word_embeddings": False}
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 18992)
    assert config["vocab_size"] * 8 == 151936
    assert config["num_experts"] * 8 == config["experts_routed_over"]
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    assert "562,290,560" in config["reduced_from"]["num_hidden_layers"]
    assert sum("qwen3_moe" in text or "DeepSeek-V3.2-Exp" in text
               for text in config["assumed"].values()) >= 4
    assert config["chunk_sizes_read_as"] == "tiling"
    assert "selection" in config["assumed"]["(e) chunk sizes"]
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    said = " ".join(config["departures"])
    for what in ("text only", "Hadamard", "ties", "KL term", "weight decay",
                 "zero"):
        assert what in said, what
    assert "eight chips" in config["deployment"]
    assert config["optimizer"]["learning_rate"] == 1e-5
    assert config["router_aux_loss_coef"] == 0.001
    check = config["check"]
    assert check["via"] == "sgd_step" and 0 < check["loss_rtol"] <= 2e-4
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s8192-dsa-ep8-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 8192, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}
    held = mix["compiled_bytes"]["keye-vl-2.0-30b-a3b"]["held_bytes_per_chip"]
    assert 0.25 * 16e9 < held < 14.0e9


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every key of the public ``config.json`` (as the ``model-configs``
    catalog carries it, where the catalog is present) stands in the file
    under its own name with its own value, but the three keys of
    ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Keye-VL-2.0-30B-A3B"]
    config = _published()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"])


def test_the_parameters_of_the_share_by_hand():
    """The program's own tree at the published widths (shapes only)."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    p = params["params"]
    attention = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 2 * 128
    assert attention == 8_388_608 * 2 + 2_097_152 + 256
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2 * 64 + 2048 * 16
    assert indexer == 2_097_152 + 131_072 + 128 + 32_768
    assert attention + indexer == count(p["layer_0"]["attn"])
    assert p["layer_0"]["attn"]["wkv"].shape == (2, 2048, 4, 128)
    assert p["layer_0"]["attn"]["index_wq"].shape == (2048, 16, 64)
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 and 16 * expert == 75_497_472
    assert sorted(p["layer_0"]["moe"]) == ["router", "wg", "wi", "wo"]
    assert p["layer_0"]["moe"]["router"].shape == (2048, 128)
    layer = attention + indexer + 2 * 2048 + 2048 * 128 + 16 * expert
    assert layer == 96_899_456
    assert all(count(p["layer_%d" % i]) == layer for i in range(5))
    assert "layer_5" not in p and "mlp" not in p["layer_0"]
    assert count(p["embed"]) == count(p["lm_head"]) == 18992 * 2048
    assert 2 * 18992 * 2048 == 77_791_232 and "pos" not in p
    assert count(params) == 5 * layer + 77_791_232 + 2048 == 562_290_560
    assert 8.99e9 < 16 * count(params) < 9.00e9
    assert 16 * (count(params) + layer) > 10.5e9       # a sixth layer
    assert state == {}


def test_the_step_of_the_share_by_hand():
    from benchmark.builders import keye_vl2 as builder
    from horovod_tpu.parallel.moe import prefix_rows

    config = _published()
    s, d, h, kv, hd, topk = 8192, 2048, 32, 4, 128, 2048
    causal = s * (s + 1) // 2
    kept = sum(min(t + 1, topk) for t in range(s))
    assert flops.causal_pairs(s) == causal == 33_558_528
    assert flops_keye.kept_pairs(s, topk) == kept == 14_681_088
    assert kept == topk * topk // 2 + topk // 2 + (s - topk) * topk
    # EXACTLY what a Trinity sliding layer's window keeps.
    assert kept == flops_afmoe.window_pairs(s, 2048)
    assert flops_keye.kept_pairs(1024, topk) == flops.causal_pairs(1024)
    projections = 2 * s * d * (2 * h * hd + 2 * kv * hd)
    attention = projections + h * 4 * kept * hd
    assert flops_keye.attention_forward_ops(
        s, hidden=d, n_head=h, n_kv=kv, head_dim=hd, topk=topk) == attention
    indexer = 2 * s * d * (16 * 64 + 64 + 16) + causal * 16 * 64 * 2
    assert flops_keye.indexer_forward_ops(
        s, hidden=d, index_heads=16, index_dim=64) == indexer
    router = 2 * s * d * 128
    held = 3 * 2 * (s * 8 * 16 // 128) * d * 768       # 8,192 rows of 65,536
    assert flops_glm.held_rows(s, 8, 16, 128) == 8192
    head = 2 * s * d * 18992
    model = builder.build(config, {"seq_len": s, "remat": True})
    ops = model.step_ops(1)
    # Three times everything that is differentiated; the indexer once.
    assert ops == 3 * (5 * (attention + router + held) + head) + 5 * indexer
    assert ops == 11_911_311_654_912
    assert 3 * 5 * h * 4 * kept * hd == pytest.approx(3.61e12, rel=5e-3)
    assert 5 * indexer == pytest.approx(0.53e12, rel=2e-2)
    # No STATIC flash kernel in the step: the builder states no attention
    # for the ``kernel.flash_*`` readers.
    assert model.attention_work(1) == {}
    # What the masked kernels' layer REQUIRES: a window's count of pairs,
    # two products forward and five backward, plus a plane a direction.
    plane = flops_keye.plane_bytes(1, s)
    assert plane == s * 2 * 128 * 4 == 8_388_608
    work = flops.attention_work(flops_keye.kept_pairs(s, topk), s, n_head=h,
                                n_kv=kv, d=hd, d_v=hd, plane_bytes=plane)
    swa = flops_afmoe.layer_attention_work(
        1, s, flops_afmoe.SLIDING, n_head=h, n_kv=kv, head_dim=hd,
        window=2048)
    assert work == {name: (o, n + plane) for name, (o, n) in swa.items()}
    assert work["fwd"][0] == 2 * h * 2 * kept * hd
    assert 2 * work["bwd"][0] == 5 * work["fwd"][0]
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = sum(flops.roofline_seconds(*w, peak)[0] for w in work.values())
    assert least == pytest.approx(5.49e-3 * 7 / 9, rel=1e-2)
    # The indexer and the selection of one layer.
    index_ops, index_bytes = flops_keye.index_work(
        1, s, index_heads=16, index_dim=64)
    assert index_ops == causal * 16 * 64 * 2
    assert index_bytes == s * (16 * 64 + 64 + 16) * 2 + 2 * causal * 4
    # The two roofs all but tie: 0.3489 ms of dot products, 0.3496 of
    # bytes.
    least, _ = flops.roofline_seconds(index_ops, index_bytes, peak)
    assert least == pytest.approx(0.3496e-3, rel=1e-3)
    assert index_ops / peak["bf16_flops"] == pytest.approx(0.3489e-3,
                                                           rel=1e-3)
    # ``moe.held_roofline`` reads these through the shared reader.
    sizes = builder.sizes_of(config)
    assert {k: sizes[k] for k in ("hidden", "expert_width", "k", "held",
                                  "routed")} == {
        "hidden": 2048, "expert_width": 768, "k": 8, "held": 16,
        "routed": 128}
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 5
    assert prefix_rows(s, 8, 16, 128) == 16384 == 2 * (s * 8 // 8)


# -------------------------------------------------------------- scopes ----

STEP = "jit(hvd_bench_step)/"
FWD = STEP + "jvp(Transformer)/layer_2/"
BWD = STEP + "transpose(jvp(Transformer))/layer_2/"


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "attn/hvd_dsa_index/dot_general", "forward", "attn"),
    (FWD + "attn/hvd_dsa_index/index_k_norm/mul", "forward", "norm"),
    (FWD + "attn/hvd_dsa_select/while/body/ge", "forward", "attn"),
    (FWD + "attn/hvd_flash/hvd_dsa_fwd/pallas_call", "forward",
     "flash_glue"),
    (BWD + "attn/hvd_flash/hvd_dsa_dkv/pallas_call", "backward",
     "flash_glue"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import dsa_view
    from horovod_tpu.jax import introspect

    assert (introspect.SCOPE_DSA_INDEX, introspect.SCOPE_DSA_SELECT) == (
        dsa_view.INDEX, dsa_view.SELECT) == (
        "hvd_dsa_index", "hvd_dsa_select")
    assert sorted(dsa_view.MASKED + k for k in ("fwd", "dkv", "dq")) == sorted((
        introspect.KERNEL_DSA_FWD, introspect.KERNEL_DSA_DKV,
        introspect.KERNEL_DSA_DQ)) == [
        "hvd_dsa_dkv", "hvd_dsa_dq", "hvd_dsa_fwd"]
    assert dsa_view.MASKED + dsa_view.CHOOSE \
        == introspect.KERNEL_DSA_CHOOSE == "hvd_dsa_choose"
    assert introspect.SAVED_FLASH_SELECT == "hvd_flash_select"
    cell, model, params, state, tokens = _assembled("float32")
    grad = jax.grad(lambda p: model.loss(p, state, tokens)[0])
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for name in ("layer_0/attn/hvd_dsa_index", "layer_1/attn/hvd_dsa_index",
                 "layer_0/attn/hvd_dsa_select", "layer_1/attn/q_norm",
                 "hvd_dsa_index/index_k_norm", "layer_1/attn/rope",
                 "layer_0/attn/hvd_flash/hvd_dsa_fwd",
                 "attn/hvd_flash/hvd_dsa_dkv", "attn/hvd_flash/hvd_dsa_dq",
                 "layer_0/moe/hvd_moe_router"):
        assert name in text, name
    for name in ("hvd_flash_fwd", "hvd_flash_dkv", "hvd_flash_dq",
                 "hvd_moe_shared", "hvd_attn_gate", "/mlp/", "/conv/",
                 "rematted_computation/layer_0/attn/hvd_dsa",
                 "rematted_computation/layer_1/attn/hvd_dsa"):
        assert name not in text, name


# The recorded Mosaic calls under the masked kernels' names.
MASKED = {old: new.replace("hvd_flash_", "hvd_dsa_")
          for old, new in NAMED.items()}


def _sparse_step():
    """The recorded step as a sparse model would name it: the three
    kernels under their masked names (and a fourth and a seventh
    operand, which decide nothing), the attention's transpose as the
    indexer's work, the feed-forward's forward matmul as the
    selection's."""
    step = AS_RECORDED
    for kernel in ("fwd", "dkv", "dq"):
        step = step.replace("hvd_flash_" + kernel, "hvd_dsa_" + kernel)
    step = named(step, MASKED).replace(
        "custom-call(%copy.6, %copy.6, %copy.6)",
        "custom-call(%copy.6, %copy.6, %copy.6, %copy.6)").replace(
        "%transpose.10, %copy.4, %copy.4)",
        "%transpose.10, %copy.4, %copy.4, %copy.6)").replace(
        "layer_0/attn/transpose",
        "layer_0/attn/hvd_dsa_index/dot_general").replace(
        "jvp(Transformer)/layer_0/mlp/dot_general",
        "jvp(Transformer)/layer_0/attn/hvd_dsa_select/ge", 1)
    assert step.count("hvd_dsa_index") == step.count("hvd_dsa_select") == 1
    assert step.count("%hvd_dsa_dkv.2 = ") == 1
    return step


def test_no_masked_mosaic_call_reads_as_a_static_flash_kernel():
    """``trace_reduce.flash_kernel`` tells a static flash kernel by its
    NAME: the masked ones are ``hvd_dsa_*``, with 4 and 7 operands or
    with 3 and 6."""
    from benchmark import dsa_view

    for step in (_sparse_step(),
                 _sparse_step().replace(", %copy.6)", ")")):
        calls = [line.strip() for line in step.splitlines()
                 if tr.is_mosaic_call(line)]
        assert len(calls) == 3
        for line in calls:
            assert tr.flash_kernel(line) == "", line
        assert sorted(tr.named_kernel(line, dsa_view.MASKED)
                      for line in calls) == ["dkv", "dq", "fwd"]
    before = [line.strip() for line in RECORDED_STEP.splitlines()
              if tr.is_mosaic_call(line)]
    assert sorted(map(tr.flash_kernel, before)) == ["dkv", "dq", "fwd"]


def test_the_new_readers_on_the_recorded_trace(capsys):
    names = ("dsa.attn_ms", "dsa.index_ms", "dsa.select_ms", "dsa.sparse_ms",
             "dsa.sparse_roofline", "dsa.index_roofline")
    ctx = _ctx(_sparse_step(), MASKED)
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    # The attention module: the three kernels, their glue, the indexer
    # and the selection (scope_view files a masked kernel under the
    # glue: a flash KERNEL to it is a call named ``hvd_flash_*``).
    assert got["dsa.attn_ms"] == pytest.approx(sum(
        scope_view.part_ms(ctx, part) for part in ("attn", "flash_glue")))
    assert got["dsa.index_ms"] + got["dsa.select_ms"] + got["dsa.sparse_ms"] \
        < got["dsa.attn_ms"]
    from benchmark import dsa_view

    times = dsa_view._times(ctx)
    assert sorted(times["kernels"]) == ["dkv", "dq", "fwd"]
    assert got["dsa.sparse_ms"] == pytest.approx(
        1e3 * sum(times["kernels"].values()))
    # Against what the FIVE layers of the configuration require over the
    # kept pairs, forward and backward, whatever calls the trace holds.
    work = flops.attention_work(
        flops_keye.kept_pairs(8192, 2048), 8192, n_head=32, n_kv=4, d=128,
        d_v=128, plane_bytes=flops_keye.plane_bytes(1, 8192))
    least = 5 * sum(flops.roofline_seconds(*w, ctx.peak)[0]
                    for w in work.values())
    assert got["dsa.sparse_roofline"] == pytest.approx(
        100 * 1e3 * least / got["dsa.sparse_ms"])
    # One backward kernel in the place of two, of equal time, and the
    # selection's own call beside them: the same.
    fused = _ctx(_sparse_step().replace("hvd_dsa_dkv", "hvd_dsa_bwd"
                                        ).replace("hvd_dsa_dq", "hvd_dsa_bwd"),
                 {old: new.replace("dkv", "bwd").replace("dq", "bwd")
                  for old, new in MASKED.items()})
    fused.cell = ctx.cell
    assert sorted(dsa_view._times(fused)["kernels"]) == ["bwd", "fwd"]
    assert reader("dsa.sparse_roofline")(fused) == pytest.approx(
        got["dsa.sparse_roofline"])
    chosen = _ctx(_sparse_step().replace(
        "hvd_dsa_dq", "hvd_dsa_choose"),
        dict(MASKED, **{"transpose_jvp___.3": "hvd_dsa_choose.3"}))
    chosen.cell = ctx.cell
    assert sorted(dsa_view._times(chosen)["kernels"]) == ["dkv", "fwd"]
    index = flops.roofline_seconds(*flops_keye.index_work(
        1, 8192, index_heads=16, index_dim=64), ctx.peak)[0]
    assert got["dsa.index_roofline"] == pytest.approx(
        100 * 1e3 * 5 * index / (got["dsa.index_ms"] + got["dsa.select_ms"]))
    logged = capsys.readouterr().err
    assert "masked kernels" in logged and "indexer and selection" in logged
    # The PARENT's program (the static kernels' names, no indexer), every
    # other configuration's cell, a ctx a reader cannot use: nothing, and
    # no exception.
    parent = _ctx(RECORDED_STEP)
    parent.cell = cells.load(CELL)
    trinity = _ctx(RECORDED_STEP)
    trinity.cell = cells.load("trinity-s8192-ep8-c1")
    broken = _ctx("HloModule jit_small_step")
    broken.cell = cells.load(CELL)
    broken.win0 = None
    for name in names:
        assert reader(name)(parent) is None, name
        assert reader(name)(trinity) is None, name
        assert reader(name)(broken) is None, name
    # A step whose compiler left nothing under the selection's scope.
    bare = _ctx(_sparse_step().replace("hvd_dsa_select/", ""), MASKED)
    bare.cell = cells.load(CELL)
    assert reader("dsa.select_ms")(bare) is None
    assert reader("dsa.index_ms")(bare) == pytest.approx(
        got["dsa.index_ms"])
    assert reader("dsa.attn_ms")(bare) == pytest.approx(got["dsa.attn_ms"])


def test_the_metrics_of_the_cell():
    """The cell reports the end-to-end pair, the shared per-layer metrics
    whose readers read it right, and its own; the static kernels', the
    shared expert's, the all-experts roofline and the other
    configurations' attention metrics are not its."""
    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"dsa.attn_ms", "dsa.sparse_roofline", "moe.held_roofline",
            "moe.layer_ms", "moe.experts_ms", "moe.dispatch_ms",
            "model.mfu_pct", "model.step_device_ms", "model.head_ms",
            "model.fwd_ms", "model.bwd_ms", "model.update_ms",
            "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
            "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {
        "kernel.flash_roofline", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_roofline", "kernel.flash_dkv_roofline",
        "kernel.flash_dq_roofline",
        "kernel.flash_share_pct", "kernel.flash_glue_ms", "moe.shared_ms",
        "moe.experts_roofline", "mla.attn_ms", "swa.attn_ms",
        "conv.mixer_ms", "sync.collective_ms", "ssm.mixer_ms",
        "yoco.attn_ms"}
    dsa = [m for m in cell.bench["per_layer"]
           if m["name"].startswith("dsa.")]
    assert len(dsa) == 6 and {m["name"] for m in dsa} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Learned selection"
               and m["source"] == "device_trace"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in dsa)
    # Nine cells or more (later PRs add theirs), one of them or more on
    # four chips; seven configurations or more; this cell the ninth.
    assert len(cell.bench["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) >= 1
    assert len(cell.bench["configs"]) >= 7
    assert cell.bench["workloads"][8]["name"] == CELL


def test_the_defects_own_rehearsal_pieces():
    """``keye_routing``'s spoiled pieces: each defect moves the selection
    (or, for dK/dV's, leaves it) the way its name says."""
    from benchmark import keye_routing
    from horovod_tpu.models import transformer

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q_i = jax.random.normal(keys[0], (1, 24, 4, 8))
    k_i = jax.random.normal(keys[1], (1, 24, 8))
    w_i = jax.random.normal(keys[2], (1, 24, 4))
    sound = transformer.learned_selection(q_i, k_i, w_i, 6)
    assert int(sound.sum()) == sum(min(t + 1, 6) for t in range(24))
    assert sorted(keye_routing.SELECTION_DEFECTS) == [
        "future_key", "no_relu", "no_weights", "one_key_fewer"]
    for defect in keye_routing.SELECTION_DEFECTS:
        with keye_routing.spoiled_selection(defect):
            got = transformer.learned_selection(q_i, k_i, w_i, 6)
        assert (np.asarray(got) != np.asarray(sound)).any(), defect
        kept = np.asarray(got)[0].sum(-1)
        if defect == "one_key_fewer":
            assert (kept[6:] == 5).all()
        elif defect == "future_key":
            assert np.asarray(got)[0][np.triu_indices(24, 1)].any()
            assert not np.asarray(got)[0][np.triu_indices(24, 2)].any()
        else:
            assert (kept[6:] == 6).all()
    again = transformer.learned_selection(q_i, k_i, w_i, 6)
    assert (np.asarray(again) == np.asarray(sound)).all()   # put back


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4100000001", "--seconds", "2", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 35
    assert line["check"]["leaves_all_zero"] == 10
