"""Flash attention (Pallas) vs dense reference attention.

Runs in interpret mode on the CPU test mesh; the same kernels compile
through Mosaic on real TPU hardware.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention
from horovod_tpu.ops.pallas_attention import (_Tiles, _pick_block,
                                              flash_attention)


def dense_reference(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = scale or d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


CASES = [
    # (B, S, H, D, causal, block_q, block_k)
    (2, 64, 2, 32, True, 32, 32),
    (1, 100, 2, 16, False, 32, 32),   # uneven S, non-causal
    (2, 128, 4, 64, True, 128, 128),  # single block
    (1, 96, 1, 8, True, 64, 32),      # block_q != block_k
    (1, 130, 2, 16, True, 64, 64),    # S > block with padding
    (1, 320, 2, 16, True, 64, 128),   # full, edge, skipped AND padded tiles
    (1, 320, 2, 16, True, 128, 64),   # the same, block_q > block_k
    (1, 320, 2, 16, False, 64, 128),  # non-causal: all full but the last
]


@pytest.mark.parametrize("b,s,h,d,causal,bq,bk", CASES)
def test_forward_matches_dense(b, s, h, d, causal, bq, bk):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = dense_reference(q, k, v, causal)
    assert out.shape == ref.shape
    assert _rel(out, ref) < 1e-5


@pytest.mark.parametrize("b,s,h,d,causal,bq,bk", CASES)
def test_gradients_match_dense(b, s, h, d, causal, bq, bk):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        assert _rel(a, b_) < 1e-5


RECT_CASES = [
    # (B, Sq, Skv, H, D, causal, block_q, block_k)
    (1, 1, 64, 2, 16, True, 32, 32),    # single-token decode
    (1, 16, 48, 2, 8, True, 16, 16),    # q shorter than kv
    (1, 30, 70, 1, 8, True, 16, 32),    # uneven rectangular
    (1, 192, 320, 2, 16, True, 64, 128),  # every tile kind, offset 128
]


@pytest.mark.parametrize("b,sq,skv,h,d,causal,bq,bk", RECT_CASES)
def test_rectangular_causal(b, sq, skv, h, d, causal, bq, bk):
    """Causal mask uses the decode convention: end of q aligns with end
    of kv, so a single-token query attends to ALL keys."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, skv, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, skv, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = dense_reference(q, k, v, causal)
    assert _rel(out, ref) < 1e-5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        assert _rel(a, b_) < 1e-5


TILE_CASES = [
    # (q_len, kv_len, block_q, block_k, causal)
    (4096, 4096, 256, 512, True),     # gpt2m-s4096-c1 at the old default
    (1024, 1024, 256, 512, True),
    (1024, 1024, 512, 512, True),
    (320, 320, 64, 128, True),
    (320, 320, 128, 64, True),
    (320, 320, 64, 128, False),
    (256, 256, 64, 64, False),        # non-causal, unpadded: no edge tile
    (192, 320, 64, 128, True),        # decode alignment
    (200, 72, 32, 16, True),          # negative offset
    (1, 64, 8, 32, True),
    (1000, 1000, 112, 112, True),     # ragged
    (130, 130, 64, 32, True),
]


def _brute_force_tiles(q_len, kv_len, bq, bk, causal):
    """Each tile's kind from the mask itself, element by element."""
    num_qb, num_kb = -(-q_len // bq), -(-kv_len // bk)
    row = np.arange(num_qb * bq)[:, None] + (kv_len - q_len)
    col = np.arange(num_kb * bk)[None, :]
    below = (col <= row) if causal else np.ones((row.size, col.size), bool)
    visible = below & (col < kv_len)
    kinds = {}
    for qi in range(num_qb):
        for kj in range(num_kb):
            tile = np.s_[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            kinds[qi, kj] = ("skipped" if not below[tile].any() else
                             "full" if visible[tile].all() else "edge")
    return num_qb, num_kb, kinds


@pytest.mark.parametrize("q_len,kv_len,bq,bk,causal", TILE_CASES)
def test_tile_classes_match_brute_force(q_len, kv_len, bq, bk, causal):
    """The helper gives every tile the kind the mask gives it, and both
    walks (by query block: forward and dQ; by key block: dK/dV) skip
    the same tiles."""
    num_qb, num_kb, kinds = _brute_force_tiles(q_len, kv_len, bq, bk, causal)
    tiles = _Tiles(bq, bk, causal, q_len, kv_len)
    assert (tiles.num_qb, tiles.num_kb) == (num_qb, num_kb)
    by_query, by_key = {}, {}
    for qi in range(num_qb):
        n_full, n_end = tiles.key_full(qi), tiles.key_end(qi)
        assert 0 <= n_full <= n_end <= num_kb
        for kj in range(num_kb):
            by_query[qi, kj] = ("full" if kj < n_full else
                                "edge" if kj < n_end else "skipped")
    for kj in range(num_kb):
        q_from = tiles.query_start(kj)
        assert 0 <= q_from <= num_qb
        for qi in range(num_qb):
            by_key[qi, kj] = qi < q_from
    assert by_query == kinds
    assert by_key == {t: kind == "skipped" for t, kind in kinds.items()}
    want = {kind: sum(1 for k in kinds.values() if k == kind)
            for kind in ("full", "edge", "skipped")}
    assert tiles.counts() == want
    if not causal and not tiles.padded_keys:
        assert want["edge"] == 0 and tiles.visible(1, 0, 0) is None


def _tiles_moved(trace):
    """What ``trace()`` adds to hvd_flash_tiles_total, by kernel and
    kind."""
    from horovod_tpu.jax import introspect

    def read():
        return {(k, kind): pallas_attention._M_TILES.labels(
            kernel=k, kind=kind).get()
            for k in (introspect.KERNEL_FLASH_FWD,
                      introspect.KERNEL_FLASH_BWD,
                      introspect.KERNEL_FLASH_DKV,
                      introspect.KERNEL_FLASH_DQ,
                      introspect.KERNEL_DSA_FWD, introspect.KERNEL_DSA_BWD,
                      introspect.KERNEL_DSA_DKV, introspect.KERNEL_DSA_DQ)
            for kind in ("full", "edge", "skipped", "below", "learned")}

    before = read()
    trace()
    return {key: n - before[key] for key, n in read().items()}


def _calls_moved(trace):
    """What ``trace()`` adds to hvd_flash_calls_total, by kernel and
    widths; labels that did not move are left out."""
    def read():
        return {(e["labels"]["kernel"], e["labels"]["widths"]): e["value"]
                for e in pallas_attention._M_CALLS.snapshot_values()}

    before = read()
    trace()
    return {key: n - before.get(key, 0) for key, n in read().items()
            if n != before.get(key, 0)}


def _trace_gradient(b, s, h, h_kv, d, window=None, selected=False, d_v=None,
                    **tiles):
    """Trace forward and backward at the shape; nothing runs."""
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, h_kv, d_v or d), jnp.bfloat16)
    select = None
    if selected:
        plane = jax.ShapeDtypeStruct((b, -(-s // (128 * 32)), s, 128),
                                     jnp.int32)
        select = pallas_attention.Selection(plane, plane)

    def loss(q, k, v, select):
        return flash_attention(q, k, v, causal=True, window=window,
                               select=select,
                               **tiles).astype(jnp.float32).sum()

    return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, v, select)


@pytest.mark.parametrize("shape,blocks,want", [
    # The benchmark's two one-chip shapes, (B, S, H, D), bf16, causal.
    ((1, 4096, 16, 64), (256, 512), (56, 16, 56)),
    ((4, 1024, 16, 64), (256, 512), (2, 4, 2)),
    ((1, 4096, 16, 64), None, None),     # what the default tiles give
    ((4, 1024, 16, 64), None, None),
])
def test_tile_counter_at_trace_time(shape, blocks, want):
    """hvd_flash_tiles_total{kernel,kind} moves by one plane's tiles
    each time a kernel is traced; nothing runs."""
    from horovod_tpu.jax import introspect

    if blocks is None:
        blocks = pallas_attention._default_blocks(shape[1], shape[1])
        assert blocks == (512, 512)
        n = shape[1]
        _, _, kinds = _brute_force_tiles(n, n, *blocks, True)
        want = tuple(sum(1 for k in kinds.values() if k == kind)
                     for kind in ("full", "edge", "skipped"))
    b, s, h, d = shape
    moved = _tiles_moved(lambda: _trace_gradient(
        b, s, h, h, d, block_q=blocks[0], block_k=blocks[1]))
    for k in (introspect.KERNEL_FLASH_FWD, introspect.KERNEL_FLASH_BWD):
        got = tuple(moved[k, kind] for kind in ("full", "edge", "skipped"))
        assert got == want, (k, got)
    # A static mask's backward is ONE kernel: the two it replaces trace
    # nothing.
    assert not any(n for (k, _), n in moved.items() if k in (
        introspect.KERNEL_FLASH_DKV, introspect.KERNEL_FLASH_DQ))


def _cell_attention_shapes():
    """Every distinct flash call the benchmark's cells trace, as (cell,
    B, S, H, H_kv, D, window, selected): read from ``BENCHMARK.json``,
    each cell's configuration and its traffic, so that a new cell joins
    by itself."""
    from benchmark.cell import HERE, ROOT, read_json

    bench = read_json(ROOT, "BENCHMARK.json")
    files = {c["name"]: c["file"] for c in bench["configs"]}
    seen, shapes = set(), []
    for cell in bench["workloads"]:
        config = read_json(ROOT, files[cell["config"]])
        traffic = read_json(HERE, "workloads", cell["traffic"] + ".json")
        if config.get("attention") != "flash":
            continue
        heads = config.get("n_head") or config["num_attention_heads"]
        kv_heads = config.get("num_key_value_heads") or heads
        if "qk_nope_head_dim" in config:    # latent attention
            dim = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        else:
            dim = config.get("head_dim") or (
                config.get("n_embd") or config["hidden_size"]) // heads
        kinds = set(config.get("layer_types") or ["full_attention"])
        calls = []
        if "sa_config" in config:
            calls.append((None, True))
        elif "full_attention" in kinds:
            calls.append((None, False))
        if "sliding_attention" in kinds:
            calls.append((config.get("sliding_window")
                          or config["sliding_window_size"], False))
        for window, selected in calls:
            shape = (traffic["per_chip_batch"], traffic["seq_len"], heads,
                     kv_heads, dim, window, selected)
            if shape not in seen:
                seen.add(shape)
                shapes.append(pytest.param(
                    *shape, id="%s-%s" % (cell["name"], (
                        "select" if selected else
                        "window" if window else "full"))))
    return shapes


@pytest.mark.parametrize("b,s,h,h_kv,d,window,selected",
                         _cell_attention_shapes())
def test_no_tiles_given_takes_the_rule_at_the_cells_shapes(
        b, s, h, h_kv, d, window, selected):
    """A call that names no tile, as the model's is, traces its kernels
    with ``_default_blocks``' tiles and nothing else's."""
    shape = (b, s, h, h_kv, d, window, selected)
    block_q, block_k = pallas_attention._default_blocks(s, s)
    by_rule = _tiles_moved(lambda: _trace_gradient(
        *shape, block_q=block_q, block_k=block_k))
    assert sum(by_rule.values()) > 0
    assert _tiles_moved(lambda: _trace_gradient(*shape)) == by_rule
    other = _tiles_moved(lambda: _trace_gradient(
        *shape, block_q=block_q // 2, block_k=block_k))
    assert other != by_rule     # the counter tells tiles apart


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, inner jaxprs included,
    as name -> (grid, block shapes of operands then results, result
    shapes, scoped-VMEM limit or None, scratch shapes)."""
    found = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                mapping = eqn.params["grid_mapping"]
                params = eqn.params["compiler_params"].get("mosaic_tpu")
                assert eqn.params["name"] not in found
                found[eqn.params["name"]] = (
                    tuple(mapping.grid),
                    [tuple(getattr(dim, "block_size", None)
                           for dim in spec.block_shape)
                     for spec in mapping.block_mappings],
                    [(aval.shape, aval.dtype.name)
                     for aval in eqn.params["out_avals"]],
                    params and params.vmem_limit_bytes,
                    [(aval.shape, aval.dtype.name)
                     for aval in mapping.scratch_avals])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return found


def _one_width_limit(panel_rows, d, block, out_rows=0, select_rows=0,
                     dq_rows=0):
    """The scoped-VMEM limit as the kernels reckoned it while they had
    ONE width (two bf16 panels and, grouped, two float32 output panels,
    all ``max(d, 128)`` lanes): what ``d_v == d`` must still give; and,
    for the one-pass backward, dQ's float32 scratch and its bf16 output
    panel, double buffered, ``dq_rows`` rows besides."""
    panels = 2 * 2 * panel_rows * max(d, 128) * 2
    panels += 2 * 2 * out_rows * max(d, 128) * 4
    panels += dq_rows * max(d, 128) * (4 + 2 * 2)
    if select_rows:
        panels += 2 * select_rows * 128 * 4 + 2 * 4 * block * block
    need = panels + 6 * 4 * block * block + (2 << 20)
    return None if need <= (16 << 20) else min(need, 100 << 20)


@pytest.mark.parametrize("b,s,h,h_kv,d,window,selected,d_v", [
    pytest.param(*case.values, case.values[4], id=case.id)
    for case in _cell_attention_shapes()] + [
    # The calls phi4flash's differential layers make: a pair's maps.
    pytest.param(1, 8192, 20, 10, 64, 512, False, 128, id="pair-window"),
    pytest.param(1, 8192, 20, 10, 64, None, False, 128, id="pair-full"),
    pytest.param(1, 16384, 8, 2, 64, None, False, 256, id="past-16-MiB"),
    pytest.param(1, 8192, 32, 4, 128, None, True, 64, id="select-narrow"),
    # Panels past the cap under either mask: the two kernels, as they were.
    pytest.param(1, 32768, 2, 2, 256, None, True, 256, id="select-past-cap"),
    pytest.param(1, 32768, 4, 2, 256, None, False, 256, id="full-past-cap"),
])
def test_the_calls_by_their_shapes_and_limits(b, s, h, h_kv, d, window,
                                              selected, d_v):
    """The ``pallas_call``s of a traced gradient at the cells' shapes:
    grid, every block, every result, the scoped-VMEM limit and the
    scratch. The forward and ONE backward, ``hvd_flash_bwd`` (under a
    learned mask ``hvd_dsa_bwd``), on the key-major grid, with dQ its
    first result, the query head's whole panel, beside a float32 scratch
    as large; a learned mask's ``by_key`` rows are its seventh operand
    and it reads no other plane. Where that call's limit would pass the
    cap: dK/dV and dQ exactly as they were, under either mask. With
    ``d_v == d`` the forward (and the pair) are what the kernels gave
    while one ``d`` built every spec (the limit by that rule, written
    out above): with another ``d_v`` the blocks of v, dO, the output and
    dV alone take it, and the limit takes each panel at its own
    width."""
    calls = _pallas_calls(_trace_gradient(b, s, h, h_kv, d, window, selected,
                                          d_v=d_v))
    prefix = "hvd_dsa_" if selected else "hvd_flash_"
    block, group = 512, h // h_kv
    n, words = s // block, -(-s // 4096)
    one_pass = pallas_attention._vmem_need(
        s, d, d_v, jnp.bfloat16, block, block, s if group > 1 else 0,
        words * block if selected else 0, s) <= (100 << 20)
    assert one_pass == ((s, d) != (32768, 256))     # the two past the cap
    assert sorted(calls) == [prefix + k for k in (
        ("bwd", "fwd") if one_pass else ("dkv", "dq", "fwd"))]
    # Block shapes as the specs give them: None where a dimension is
    # squeezed (batch and head; a bit plane has no head).
    plane = [(None, words, block, 128)] if selected else []
    (q_blk, k_blk, v_blk, q_pan, k_pan, v_pan, do_pan, col) = (
        (None, None) + shape for shape in (
            (block, d), (block, d), (block, d_v), (s, d), (s, d), (s, d_v),
            (s, d_v), (block, 1)))
    rows = (None, None, n, 1, block)

    def limit(panel_d, panel_dv, out_rows=0, select_rows=0, dq_rows=0):
        if d_v == d:
            return _one_width_limit(s, d, block, out_rows, select_rows,
                                    dq_rows)
        lanes = max(panel_d, 128) + max(panel_dv, 128)
        need = 2 * s * lanes * 2 + 2 * out_rows * lanes * 4 + (
            2 * select_rows * 128 * 4 + 2 * 4 * block * block
            if select_rows else 0) + dq_rows * max(panel_d, 128) * 8 \
            + 6 * 4 * block * block + (2 << 20)
        return None if need <= (16 << 20) else min(need, 100 << 20)

    sel_rows = words * block if selected else 0
    grid, blocks, results, vmem, scratch = calls[prefix + "fwd"]
    assert grid == (b, h, n)
    assert blocks == [q_blk, k_pan, v_pan] + plane + [v_blk, col]
    assert results == [((b, h, s, d_v), "bfloat16"),
                       ((b, h, s, 1), "float32")]
    assert vmem == limit(d, d_v, select_rows=sel_rows)
    assert scratch == []

    if group == 1:
        key_major = (b, h, n)
        outs, out_rows, kind = [k_blk, v_blk], 0, "bfloat16"
    else:       # the key/value head's float32 panels stay resident
        key_major = (b, h_kv, group, n)
        outs, out_rows, kind = [k_pan, v_pan], s, "float32"
    dkv_results = [((b, h_kv, s, d), kind), ((b, h_kv, s, d_v), kind)]
    if one_pass:
        grid, blocks, results, vmem, scratch = calls[prefix + "bwd"]
        assert grid == key_major
        assert blocks == [q_pan, k_blk, v_blk, do_pan, rows, rows] + plane \
            + [q_pan] + outs
        assert results == [((b, h, s, d), "bfloat16")] + dkv_results
        assert vmem == limit(d, d_v, out_rows, sel_rows, dq_rows=s)
        assert vmem is None or vmem < (100 << 20)    # under the cap
        assert scratch == [((s, d), "float32")]
        return

    grid, blocks, results, vmem, scratch = calls[prefix + "dq"]
    assert grid == (b, h, n)
    assert blocks == [q_blk, k_pan, v_pan, v_blk, col, col] + plane + [q_blk]
    assert results == [((b, h, s, d), "bfloat16")]
    assert vmem == limit(d, d_v, select_rows=sel_rows)
    assert scratch == []

    grid, blocks, results, vmem, scratch = calls[prefix + "dkv"]
    assert grid == key_major
    assert blocks == [q_pan, k_blk, v_blk, do_pan, rows, rows] + plane + outs
    assert results == dkv_results
    assert vmem == limit(d, d_v, out_rows, sel_rows)
    assert scratch == []


def test_the_call_counter_tells_the_widths():
    """hvd_flash_calls_total{kernel,widths} moves by one a traced
    kernel: under ``"64"`` where v is as wide as q.k, under
    ``"64+128"`` where it is not, and under no other label. A static
    mask moves ``hvd_flash_bwd`` and neither ``hvd_flash_dkv`` nor
    ``hvd_flash_dq``; a learned one ``hvd_dsa_fwd`` and ``hvd_dsa_bwd``
    and neither of the pair."""
    names = ("hvd_flash_fwd", "hvd_flash_bwd")
    assert _calls_moved(lambda: _trace_gradient(1, 1024, 4, 2, 64)) \
        == {(name, "64"): 1 for name in names}
    assert _calls_moved(lambda: _trace_gradient(
        1, 1024, 4, 2, 64, window=512, d_v=128)) \
        == {(name, "64+128"): 1 for name in names}
    assert _calls_moved(lambda: _trace_gradient(
        1, 1024, 4, 2, 64, selected=True, d_v=32)) \
        == {(name, "64+32"): 1
            for name in ("hvd_dsa_fwd", "hvd_dsa_bwd")}


@pytest.mark.parametrize("selected,need,one", [
    (False, 16 << 20, True),            # any static mask that fits
    (False, 100 << 20, True),           # up to the cap itself
    (False, (100 << 20) + 1, False),    # panels past the cap: two kernels
    (True, 16 << 20, True),             # a mask that is data: the same rule
    (True, (100 << 20) + 1, False),
])
def test_which_backward_runs_is_a_rule_on_the_input(selected, need, one,
                                                    monkeypatch):
    """``_one_pass`` sees ONE number, the one pass's own VMEM reckoning,
    which ``_flash_bwd`` makes WITH a learned plane's block; the mask
    decides the kernels' names and nothing else."""
    assert pallas_attention._one_pass(need) is one
    reckoned = []

    def told(tiles, group, d, d_v, sq_pad, sk_pad, dtype, words=0,
             with_dq=False):
        reckoned.append((tiles.learned, words, with_dq))
        return need

    monkeypatch.setattr(pallas_attention, "_key_major_need", told)
    moved = _calls_moved(lambda: _trace_gradient(
        1, 4224, 4, 2, 64, selected=selected))
    prefix = "hvd_dsa_" if selected else "hvd_flash_"
    assert sorted(name for name, _ in moved) == [prefix + k for k in (
        ("bwd", "fwd") if one else ("dkv", "dq", "fwd"))]
    # The rule's number first: two words of a 4224-row plane, with dQ.
    assert reckoned[0] == (selected, 2 if selected else 0, True)


@pytest.mark.parametrize("s,h,h_kv,d,d_v,dtype,selected,names", [
    # lfm2-s16384-ep4-c1, the largest panels of any cell: 64 MiB.
    (16384, 32, 8, 64, 64, jnp.bfloat16, False, ("bwd", "fwd")),
    # Twice its rows at 256 wide: q and dO 64 MiB, dQ 64 more.
    (32768, 2, 2, 256, 256, jnp.bfloat16, False, ("dkv", "dq", "fwd")),
    # keye-s8192-dsa-ep8-c1: the plane's block is 3 of its 43 MiB.
    (8192, 32, 4, 128, 128, jnp.bfloat16, True, ("bwd", "fwd")),
    # A learned mask past the cap keeps its pair.
    (32768, 2, 2, 256, 256, jnp.bfloat16, True, ("dkv", "dq", "fwd")),
    # 46 blocks at 256 wide: the cap itself without a plane, 105 MiB with
    # its six words: the plane's block is part of what the rule weighs.
    (23552, 2, 2, 256, 256, jnp.bfloat16, False, ("bwd", "fwd")),
    (23552, 2, 2, 256, 256, jnp.bfloat16, True, ("dkv", "dq", "fwd")),
])
def test_a_shape_past_the_cap_takes_the_two_kernels(s, h, h_kv, d, d_v,
                                                    dtype, selected, names):
    """The rule reads the one pass's own VMEM reckoning: a mask, static
    or learned, whose resident panels pass 100 MiB traces its two
    kernels as it did, so that nothing that compiled stops compiling;
    the cells' largest static call and the one learned call stay one
    pass."""
    words = -(-s // 4096) if selected else 0
    need = pallas_attention._vmem_need(
        s, d, d_v, dtype, 512, 512, s if h != h_kv else 0, words * 512,
        dq_rows=s)
    assert (need <= (100 << 20)) == ("bwd" in names)
    moved = _calls_moved(lambda: _trace_gradient(
        1, s, h, h_kv, d, selected=selected, d_v=d_v))
    assert sorted(name for name, _ in moved) \
        == [("hvd_dsa_" if selected else "hvd_flash_") + n for n in names]


@pytest.mark.parametrize("env,cached", [
    ({"HVD_FLASH_BLOCK_Q": "128", "HVD_FLASH_BLOCK_K": "256"}, False),
    ({"HVD_FLASH_TUNE": "1"}, False),
    ({"HVD_FLASH_TUNE": "cache"}, True),
], ids=["block_q_and_k", "tune", "tune_from_a_cache"])
def test_the_environment_names_no_tile(env, cached, monkeypatch, tmp_path):
    """The options the tile tuner had are dead: set, they change no
    tile, start no sweep, and the cache file they named is neither read
    nor written."""
    import json

    shape = (1, 1024, 2, 2, 64)
    by_rule = _tiles_moved(lambda: _trace_gradient(
        *shape, block_q=512, block_k=512))
    cache = tmp_path / "flash_blocks.jsonl"
    if cached:
        cache.write_text(json.dumps({
            "version": 1, "block_q": 128, "block_k": 128,
            "key": "q1024.kv1024.d64.bfloat16.causal.cpu-cpu"}) + "\n")
    was = cache.read_text() if cached else None
    monkeypatch.setenv("HVD_FLASH_TUNE_CACHE", str(cache))
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert _tiles_moved(lambda: _trace_gradient(*shape)) == by_rule
    assert (cache.read_text() if cache.exists() else None) == was


@pytest.mark.parametrize("s,tile", [
    (4096, 512), (1024, 512), (1000, 512), (1100, 384), (600, 384),
    (513, 384), (512, 512), (100, 128), (1, 128),
    (2048, 512), (8192, 512), (16384, 512), (65536, 512)])
def test_default_blocks_split_the_sequence_evenly(s, tile):
    """At most 512 rows a block, the fewest blocks, whole lane groups:
    the padding stays under a lane group a block."""
    assert pallas_attention._default_blocks(s, s) == (tile, tile)
    assert pallas_attention._default_blocks(s, 4096) == (tile, 512)
    blocks = -(-s // tile)
    assert blocks == -(-s // 512) and blocks * tile - s < 128 * blocks


@pytest.mark.parametrize("s,want,dtype,tile", [
    (100, 256, jnp.float32, 104),    # short ragged: one 8-row-aligned tile
    (100, 256, jnp.bfloat16, 112),   # bf16 packs 16 rows per sublane tile
    (100, 256, jnp.int8, 128),
    (2048, 256, jnp.bfloat16, 256),  # long: the requested tile
    (1000, 100, jnp.bfloat16, 112),  # a misaligned request is rounded up
    (1, 512, jnp.float32, 8),
])
def test_pick_block_is_sublane_aligned(s, want, dtype, tile):
    """Mosaic must be able to prove in-panel slices tile-aligned; the
    raw sequence length as a tile (S=100) does not compile on the chip
    even though interpret mode accepts it."""
    assert _pick_block(s, want, dtype) == tile


@pytest.mark.parametrize("d_v", [64, 128, 32])
def test_short_ragged_bfloat16_matches_dense(d_v):
    """S=100 in bf16: the tile is padded to 112 rows, the padded keys
    masked and the padded query rows sliced off; v as wide as q.k,
    twice as wide and half as wide."""
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 100, 2, d), jnp.bfloat16)
               for d in (64, 64, d_v))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    def dense(q, k, v):
        return dense_reference(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), True)

    out = flash_attention(q, k, v, causal=True)
    assert out.shape == (1, 100, 2, d_v) and out.dtype == jnp.bfloat16
    assert _rel(out.astype(jnp.float32), dense(q, k, v)) < 2e-2
    got = jax.grad(loss(lambda *a: flash_attention(*a, causal=True)),
                   (0, 1, 2))(q, k, v)
    ref = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    for a, b_, width in zip(got, ref, (64, 64, d_v)):
        assert a.shape == (1, 100, 2, width) and a.dtype == jnp.bfloat16
        assert _rel(a.astype(jnp.float32), b_.astype(jnp.float32)) < 3e-2


def test_bfloat16_inputs():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), True)
    assert out.dtype == jnp.bfloat16
    assert _rel(out.astype(jnp.float32), ref) < 5e-2


def test_under_jit():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
    out = jax.jit(lambda x: flash_attention(x, x, x, causal=True))(q)
    ref = dense_reference(q, q, q, True)
    assert _rel(out, ref) < 1e-5


def test_block_q_variation_is_bit_exact():
    """Tuned q-tiles vs default q-tiles: bit-level parity.

    block_q only partitions the query rows; each row's streaming
    (max, sum, acc) walk over kv blocks is row-independent, and a
    causal row-block skip only elides blocks whose contribution is an
    exact no-op (p underflows to exactly 0, alpha = exp(0) = 1). So
    for a FIXED block_k, every block_q must produce identical bits —
    the guarantee that lets the tuner change q-tiles without a
    numerics review.
    """
    rng = np.random.RandomState(7)
    for causal in (True, False):
        q = jnp.asarray(rng.randn(2, 130, 2, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, 130, 2, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, 130, 2, 16), jnp.float32)
        ref = flash_attention(q, k, v, causal=causal, block_q=256,
                              block_k=64)
        for bq in (16, 32, 64):
            out = flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=64)
            assert np.array_equal(np.asarray(out), np.asarray(ref)), \
                "causal=%s bq=%d" % (causal, bq)


def test_block_k_variation_tight_tolerance():
    """block_k changes the fp32 streaming-softmax association order, so
    bit parity is NOT guaranteed across k-tiles — but the drift must
    stay at rounding scale (the tuner may change block_k freely)."""
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 130, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 130, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 130, 2, 16), jnp.float32)
    ref = flash_attention(q, k, v, causal=True, block_q=64, block_k=130)
    for bk in (16, 32, 64):
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=bk)
        assert _rel(out, ref) < 1e-6, bk


def test_transformer_flash_matches_dense():
    import dataclasses

    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.RandomState(4).randint(0, 128, (2, 32)), jnp.int32)
    dense_model = Transformer(cfg)
    params = dense_model.init(jax.random.PRNGKey(0), tokens)
    flash_model = Transformer(
        dataclasses.replace(cfg, attention="flash"))
    out_dense = dense_model.apply(params, tokens)
    out_flash = flash_model.apply(params, tokens)
    assert _rel(out_flash, out_dense) < 1e-4


# ------------------------------------ the caller that has two widths -----

def _four_call_differential(self, q, k, v):
    """``SelfAttention._differential`` as it stood while the kernels had
    one width (PR 45): FOUR runs of ``_attend`` at half the heads, each
    map over v's even and over its odd heads, the halves put side by
    side afterwards."""
    import math

    from flax import linen as nn
    from horovod_tpu.models import transformer

    cfg, d = self.cfg, q.shape[-1]
    lambda_init = 0.8 - 0.6 * math.exp(-0.3 * self.diff_layer)
    diff = self.param(
        "diff", lambda key, shape, dtype: jnp.concatenate([
            nn.initializers.normal(0.1)(key, (4, d), dtype),
            jnp.ones((2, d), dtype)]), (6, d), jnp.float32)
    vectors, scale = diff[:4], diff[4:].reshape(2 * d)
    (q1, q2), (k1, k2), (v1, v2) = (
        (t[:, :, 0::2], t[:, :, 1::2]) for t in (q, k, v))
    maps = [[transformer._attend(cfg, qi, ki, vj, self.window)
             for vj in (v1, v2)] for qi, ki in ((q1, k1), (q2, k2))]
    lq1, lk1, lq2, lk2 = vectors
    lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
           + lambda_init)
    first, second = (jnp.concatenate(pair, -1) for pair in maps)
    out = transformer._differential_output(first, second, lam, lambda_init,
                                           scale)
    return out.astype(cfg.dtype).reshape(q.shape)


def _differential_layer(attention, window):
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.transformer import BlockSpec, SelfAttention

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=8, n_layers=1, d_ff=64,
        max_seq_len=1024, dtype=jnp.float32, attention=attention,
        block=BlockSpec(head_dim=8, n_kv_heads=4, diff_attention=True))
    return SelfAttention(cfg, window=window, diff_layer=3)


@pytest.mark.parametrize("window", [None, 100])
def test_differential_attention_in_two_calls(window, monkeypatch):
    """A differential layer under ``attention="flash"`` (two calls, each
    map over a V of two heads side by side) against the same layer under
    ``"dense"`` and against the four-call form it replaces, written out
    above: the output and every gradient, ``diff`` and the input
    included; four pairs of query heads over two of key/value heads;
    600 positions are two tiles of 384 with padded rows. The traced
    layer counts two calls of each kernel (the forward, the one-pass
    backward), all at 8 + 16."""
    from horovod_tpu.models.transformer import SelfAttention

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 600, 32))
    weight = jax.random.normal(jax.random.PRNGKey(1), (2, 600, 32))
    flash, dense = (_differential_layer(a, window)
                    for a in ("flash", "dense"))
    params = dense.init(jax.random.PRNGKey(2), x)
    assert sorted(params["params"]) == ["diff", "wkv", "wo", "wq"]

    def graded(layer):
        def loss(params, x):
            out = layer.apply(params, x)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            params, x)
        return jax.tree_util.tree_leaves((out, grads))

    two = graded(flash)
    moved = _calls_moved(lambda: jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(flash.apply(p, x))))(params))
    assert moved == {(name, "8+16"): 2 for name in (
        "hvd_flash_fwd", "hvd_flash_bwd")}
    by_dense = graded(dense)
    monkeypatch.setattr(SelfAttention, "_differential",
                        _four_call_differential)
    four = graded(flash)
    assert len(two) == 6        # the output, four leaves, the input
    for got, want, parent in zip(two, by_dense, four):
        assert got.shape == want.shape == parent.shape
        assert _rel(got, want) < 1e-5
        assert _rel(got, parent) < 1e-5


def test_a_differential_pair_is_two_consecutive_heads():
    """Pair i reads key/value heads 2 i and 2 i + 1 and no other: with
    the output projection of the second pair's heads at zero, a change
    to the weights that make v's head 2 or 3 leaves the layer's output
    alone, and those of heads 1 and 2 swapped change it; under 'flash'
    as under 'dense'."""
    import dataclasses

    from flax.core import meta

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    # Eight query heads over eight key/value heads: four pairs of each.
    for attention in ("flash", "dense"):
        layer = _differential_layer(attention, None)
        layer = layer.clone(cfg=dataclasses.replace(
            layer.cfg, block=dataclasses.replace(layer.cfg.block,
                                                 n_kv_heads=0)))
        params = meta.unbox(layer.init(jax.random.PRNGKey(3), x))["params"]
        params = {**params, "wo": params["wo"].at[2:].set(0.0)}

        def first_pair(wv):
            wqkv = params["wqkv"].at[2].set(wv)
            return np.asarray(layer.apply(
                {"params": {**params, "wqkv": wqkv}}, x))

        wv = params["wqkv"][2] * 50.0        # (M, H, D); the init is 0.02
        sound = first_pair(wv)
        assert (first_pair(wv.at[:, 2].add(1.0)) == sound).all()
        assert (first_pair(wv.at[:, 3].add(1.0)) == sound).all()
        swapped = first_pair(wv[:, jnp.array([0, 2, 1, 3, 4, 5, 6, 7])])
        assert np.abs(swapped - sound).max() > 1e-2 * np.abs(sound).max()


# ------------------------------- a window, and grouped key/value heads -----

def masked_reference(q, k, v, window):
    """Dense float32 attention: causal in the decode alignment, a query
    at position p sees the keys j with ``p - window < j <= p``; k and v
    repeated to the query heads (head h reads ``h // group``)."""
    d, group = q.shape[-1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    pos = jnp.arange(q.shape[1])[:, None] + (k.shape[1] - q.shape[1])
    key = jnp.arange(k.shape[1])[None, :]
    visible = key <= pos
    if window is not None:
        visible &= key > pos - window
    s = jnp.where(visible[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _brute_force_window_tiles(q_len, kv_len, bq, bk, window):
    """Each tile's kind from the mask itself, element by element, over
    the padded rows too (the kernels compute them): ``below`` / ``skipped``
    where no element is inside the window / under the diagonal, ``full``
    where every element is visible and no key is padded."""
    num_qb, num_kb = -(-q_len // bq), -(-kv_len // bk)
    row = np.arange(num_qb * bq)[:, None] + (kv_len - q_len)
    col = np.arange(num_kb * bk)[None, :]
    under = col <= row
    inside = np.ones_like(under) if window is None else col > row - window
    visible = under & inside & (col < kv_len)
    kinds = {}
    for qi in range(num_qb):
        for kj in range(num_kb):
            tile = np.s_[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            kinds[qi, kj] = ("skipped" if not under[tile].any() else
                             "below" if not inside[tile].any() else
                             "full" if visible[tile].all() else "edge")
    return num_qb, num_kb, kinds


@pytest.mark.parametrize("window,group,q_len,kv_len,d_v", [
    (window, group, q_len, kv_len, 16)
    for q_len, kv_len in ((200, 200), (100, 200)) for group in (1, 4, 8)
    for window in (None, 1, 3, 64, 500)] + [
    # v twice and half as wide as q.k (16).
    (window, group, 200, 200, d_v)
    for d_v in (32, 8) for group in (1, 4) for window in (None, 1, 64, 500)
] + [(64, 4, 100, 200, 32)])
def test_window_and_grouped_heads_match_dense(window, group, q_len, kv_len,
                                              d_v):
    """Forward and all three gradients against the dense masked
    attention: S no multiple of the 64 x 32 tiles, ``kv_len > q_len``,
    K/V ``H // group`` heads wide going in and coming back, v (and so
    the output, dO and dV) ``d_v`` wide beside q.k's 16; and the tiles'
    classes and counts against the mask itself, by both walks."""
    from horovod_tpu.models.transformer import _dense_causal_attention

    bq, bk, heads, d = 64, 32, 8, 16
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, q_len, heads, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, kv_len, heads // group, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, kv_len, heads // group, d_v), jnp.float32)
    weight = jnp.asarray(rng.randn(1, q_len, heads, d_v), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda q, k, v: masked_reference(q, k, v, window),
                           q, k, v)
    assert out.shape == ref.shape and _rel(out, ref) < 1e-5
    for got, want in zip(vjp(weight), ref_vjp(weight)):
        assert got.shape == want.shape          # dK, dV: H // group heads
        assert float(jnp.max(jnp.abs(got - want))) \
            < 1e-5 * max(float(jnp.max(jnp.abs(want))), 1.0)
    if q_len == kv_len:     # the model's own dense path, as the caller has it
        dense, dense_vjp = jax.vjp(lambda q, k, v: _dense_causal_attention(
            q, k, v, jnp.float32, window), q, k, v)
        assert out.shape == dense.shape == (1, q_len, heads, d_v)
        for got, want in zip((out,) + vjp(weight),
                             (dense,) + dense_vjp(weight)):
            assert float(jnp.max(jnp.abs(got - want))) \
                < 1e-5 * max(float(jnp.max(jnp.abs(want))), 1.0)

    num_qb, num_kb, kinds = _brute_force_window_tiles(q_len, kv_len, bq, bk,
                                                      window)
    tiles = _Tiles(bq, bk, True, q_len, kv_len, window)
    by_query, by_key = {}, {}
    for qi in range(num_qb):
        start, end = tiles.key_start(qi), tiles.key_end(qi)
        first_full = max(start, tiles.key_inside(qi))
        assert 0 <= start <= end <= num_kb
        for kj in range(num_kb):
            by_query[qi, kj] = (
                "below" if kj < start else "skipped" if kj >= end else
                "full" if first_full <= kj < tiles.key_full(qi) else "edge")
    for kj in range(num_kb):
        q_from, q_to = tiles.query_start(kj), tiles.query_end(kj)
        assert 0 <= q_from <= q_to <= num_qb
        for qi in range(num_qb):
            by_key[qi, kj] = q_from <= qi < q_to
    assert by_query == kinds
    assert by_key == {t: kind in ("full", "edge")
                      for t, kind in kinds.items()}
    want = {kind: sum(1 for k_ in kinds.values() if k_ == kind)
            for kind in ("full", "edge", "skipped", "below")}
    if window is None:
        assert want.pop("below") == 0      # the class exists under a window
    assert tiles.counts() == want
    if window in (1, 3, 64):
        assert want["below"] > 0 and want["edge"] > 0


@pytest.mark.parametrize("heads,kv_heads", [(7, 1), (14, 2), (28, 4)])
@pytest.mark.parametrize("window", [None, 40, 300])
def test_a_group_of_seven_matches_dense(heads, kv_heads, window):
    """Grouped key/value heads at a group that is no power of two
    (smallthinker-s8192-ep4-c1's 28 query heads over 4: SEVEN a key/value
    head), with and without a window: the forward, and dQ, dK and dV of
    the ONE-pass backward, against the dense masked attention, in which
    query head h reads key/value head ``h // 7`` and dK/dV are the sums
    over a head's seven readers; 300 rows under 64 x 32 tiles (padded
    rows and keys)."""
    s, d = 300, 16
    assert heads // kv_heads == 7
    rng = np.random.RandomState(heads + (window or 0))
    q, k, v, g = (jnp.asarray(rng.randn(1, s, n, d), jnp.float32)
                  for n in (heads, kv_heads, kv_heads, heads))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=64, block_k=32)

    moved = _calls_moved(lambda: jax.make_jaxpr(
        lambda *a: jax.vjp(flash, *a[:3])[1](a[3]))(q, k, v, g))
    assert sorted(name for name, _ in moved) \
        == ["hvd_flash_bwd", "hvd_flash_fwd"]
    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda q, k, v: masked_reference(q, k, v, window),
                           q, k, v)
    assert out.shape == ref.shape and _rel(out, ref) < 1e-5
    for got, want in zip(vjp(g), ref_vjp(g)):
        assert got.shape == want.shape          # dK, dV: kv_heads wide
        assert _rel(got, want) < 1e-5
    # The dense reference's own dK IS the sum over the seven readers:
    # repeat the key heads, differentiate each copy, add them up.
    if window is None:
        spread = jnp.repeat(k, 7, axis=2)
        each = jax.grad(lambda kk: jnp.sum(masked_reference(
            q, kk, jnp.repeat(v, 7, axis=2), None) * g))(spread)
        assert _rel(vjp(g)[1],
                    each.reshape(1, s, kv_heads, 7, d).sum(3)) < 1e-5
        # ... and head h // 7, not h % kv_heads.
        if kv_heads > 1:
            wrong = each.reshape(1, s, 7, kv_heads, d).sum(2)
            assert _rel(vjp(g)[1], wrong) > 1e-2


def test_window_tile_counts_at_the_benchmarks_shape():
    """trinity-s8192-ep8-c1's planes, 512 x 512 tiles: a sliding layer
    (window 2048) computes 70 of a full layer's 136 tiles."""
    full = _Tiles(512, 512, True, 8192, 8192).counts()
    sliding = _Tiles(512, 512, True, 8192, 8192, 2048).counts()
    assert full == {"full": 120, "edge": 16, "skipped": 120}
    assert sliding == {"full": 42, "edge": 28, "skipped": 120, "below": 66}


def test_a_window_needs_causal_and_the_heads_have_to_divide():
    x = jnp.zeros((1, 16, 4, 8))
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, causal=True, window=0)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(x, x[:, :, :3], x[:, :, :3])


@pytest.mark.parametrize("v_shape", [
    (1, 16, 2, 8), (1, 12, 4, 8), (2, 16, 4, 8), (1, 16, 4, 8, 1)],
    ids=["heads", "length", "batch", "rank"])
def test_v_differs_from_k_in_its_width_alone(v_shape):
    """v may have another LAST dimension than k; before it, any
    difference is refused."""
    x = jnp.zeros((1, 16, 4, 8))
    assert flash_attention(x, x, jnp.zeros((1, 16, 4, 24))).shape \
        == (1, 16, 4, 24)
    with pytest.raises(ValueError, match="up to their widths"):
        flash_attention(x, x, jnp.zeros(v_shape))


def test_tile_counter_and_log_line_carry_window_and_group(caplog):
    """The tile counter's ``below`` kind moves only under a window, and
    the per-shape log line names the window and the group."""
    import logging

    from horovod_tpu.jax import introspect

    below = pallas_attention._M_TILES.labels(
        kernel=introspect.KERNEL_FLASH_FWD, kind="below")
    before = below.get()
    q = jax.ShapeDtypeStruct((1, 1024, 8, 16), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 16), jnp.bfloat16)
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128), q, kv, kv)
        assert below.get() == before
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=256, block_q=128, block_k=128),
            q, kv, kv)
    # Rows 2.. of 8 leave 0, 1, .. 5 key blocks below their window.
    assert below.get() - before == sum(range(6))
    assert "window 256, 4 query head(s) a key/value head" in caplog.text
    assert "window None, 4 query head(s) a key/value head" in caplog.text


# --------------------------------------------------- the one-pass backward ---


def _one_pass_cases():
    """causal / window / non-causal by group 1 / 2 / 8 by float32 /
    bf16, over 1100 rows (padded keys and rows at both tilings); the
    widths (64 + 64, 64 + 128) and the two tilings turn with the case,
    so that each meets every mask, group and dtype. A window of 300
    under 128-row key blocks: query blocks past row 427 never see key
    block 0, their dQ rows' first visit is a later ``kj``."""
    cases = []
    masks = ((True, None), (True, 300), (False, None))
    for i, (causal, window) in enumerate(masks):
        for j, group in enumerate((1, 2, 8)):
            for n, dtype in enumerate((jnp.float32, jnp.bfloat16)):
                d_v = (64, 128)[(i + j + n) % 2]
                tiles = ((256, 128), (128, 384))[(i + j) % 2]
                cases.append(pytest.param(
                    causal, window, group, d_v, dtype, tiles,
                    id="%s-g%d-64+%d-%s-%dx%d" % (
                        "window" if window else
                        "causal" if causal else "all", group, d_v,
                        jnp.dtype(dtype).name, *tiles)))
    return cases


@pytest.mark.parametrize("causal,window,group,d_v,dtype,blocks",
                         _one_pass_cases())
def test_one_pass_backward_matches_dense_and_the_two_kernels(
        causal, window, group, d_v, dtype, blocks):
    """dQ, dK and dV of ``hvd_flash_bwd`` against the dense masked
    attention's AND against ``hvd_flash_dkv`` + ``hvd_flash_dq``, both
    reached through the wrappers ``_flash_bwd`` chooses between, on the
    same operands: the same p, mask and delta, the same float32 sums in
    the same order (dQ's key blocks ascending, each block's ``ds^T . k``
    summed as the dQ kernel sums ``ds . k``: ``_dot_tn``), rounded once:
    in interpret mode the two are equal BIT FOR BIT."""
    s, heads, d = 1100, 8, 64
    block_q, block_k = blocks
    rng = np.random.RandomState(11)
    q, k, v, g = (jnp.asarray(rng.randn(1, s, n, w), dtype)
                  for n, w in ((heads, d), (heads // group, d),
                               (heads // group, d_v), (heads, d_v)))

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        if causal:
            return masked_reference(q, k, v, window)
        return dense_reference(q, jnp.repeat(k, group, axis=2),
                               jnp.repeat(v, group, axis=2), False)

    want = jax.vjp(reference, q, k, v)[1](g.astype(jnp.float32))
    # Through the public call: the rule takes the one pass.
    moved = _calls_moved(lambda: jax.make_jaxpr(
        lambda *a: jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, block_q=block_q,
            block_k=block_k), *a[:3])[1](a[3]))(q, k, v, g))
    assert sorted(name for name, _ in moved) \
        == ["hvd_flash_bwd", "hvd_flash_fwd"]

    scale = d ** -0.5
    heads_first = [jnp.swapaxes(x, 1, 2) for x in (q, k, v, g)]
    _, res = pallas_attention._flash_fwd_impl(
        *heads_first[:3], causal, window, block_q, block_k, scale, True)
    tiles, *operands = pallas_attention._bwd_operands(
        block_q, block_k, causal, window, res, heads_first[3])
    assert tiles.padded_keys and operands[0].shape[2] > s
    if window:      # some query block's first visit is not key block 0
        assert tiles.key_start(tiles.num_qb - 1) > 0
    assert operands[-1] is None        # no learned planes
    one = pallas_attention._bwd_one_pass(tiles, scale, True, *operands[:-1])
    two = pallas_attention._bwd_two_kernels(tiles, scale, True, *operands)
    assert one[0].dtype == two[0].dtype == dtype
    # Padded query rows of dQ are written (zeros), not left as they were.
    assert not np.asarray(one[0][:, :, s:], np.float32).any()
    far = 1e-5 if dtype == jnp.float32 else 3e-2
    for got, same, ref in zip(one, two, want):
        assert got.shape == same.shape and got.dtype == same.dtype
        got, same = (jnp.swapaxes(x[:, :, :s], 1, 2).astype(jnp.float32)
                     for x in (got, same))
        assert got.shape == ref.shape
        assert (np.asarray(got) == np.asarray(same)).all()
        assert _rel(got, ref) < far


def selected_reference(q, k, v, keep):
    """``masked_reference`` under a mask that is data: a query sees the
    keys at or before it that ``keep`` (B, S, S) names."""
    d, group = q.shape[-1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    visible = jnp.tril(jnp.ones(keep.shape[1:], bool)) & keep
    s = jnp.where(visible[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _learned_one_pass_cases():
    """A random selection / one that keeps every key by group 1 / 2 / 8
    by float32 / bf16, over 1100 rows; the widths and the two tilings
    (whole 128-lane groups, as a learned mask's are) turn with the case
    as in ``_one_pass_cases``."""
    cases = []
    for i, every in enumerate((False, True)):
        for j, group in enumerate((1, 2, 8)):
            for n, dtype in enumerate((jnp.float32, jnp.bfloat16)):
                d_v = (64, 128)[(i + j + n) % 2]
                tiles = ((256, 128), (128, 384))[(i + j) % 2]
                cases.append(pytest.param(
                    every, group, d_v, dtype, tiles,
                    id="%s-g%d-64+%d-%s-%dx%d" % (
                        "every" if every else "random", group, d_v,
                        jnp.dtype(dtype).name, *tiles)))
    return cases


@pytest.mark.parametrize("every,group,d_v,dtype,blocks",
                         _learned_one_pass_cases())
def test_one_pass_backward_under_a_learned_mask(every, group, d_v, dtype,
                                                blocks):
    """dQ, dK and dV of ``hvd_dsa_bwd``, which reads ``by_key`` alone,
    against the dense attention under the same mask AND against
    ``hvd_dsa_dkv`` + ``hvd_dsa_dq`` (dQ there masked by ``by_query``),
    both reached through the wrappers ``_flash_bwd`` chooses between on
    the same operands: equal BIT FOR BIT in interpret mode. Under a
    plane that keeps every key the masked one pass is ``hvd_flash_bwd``
    itself, bit for bit."""
    s, heads, d = 1100, 8, 64
    block_q, block_k = blocks
    rng = np.random.RandomState(13)
    q, k, v, g = (jnp.asarray(rng.randn(1, s, n, w), dtype)
                  for n, w in ((heads, d), (heads // group, d),
                               (heads // group, d_v), (heads, d_v)))
    keep = jnp.asarray(np.ones((1, s, s), bool) if every else
                       (rng.rand(1, s, s) < 0.3) | np.eye(s, dtype=bool))
    select = pallas_attention.pack_selection(keep)
    want = jax.vjp(lambda q, k, v: selected_reference(
        *(x.astype(jnp.float32) for x in (q, k, v)), keep), q, k, v)[1](
            g.astype(jnp.float32))
    # Through the public call: the rule takes the one pass.
    moved = _calls_moved(lambda: jax.make_jaxpr(
        lambda *a: jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, select=select, block_q=block_q, block_k=block_k),
            *a[:3])[1](a[3]))(q, k, v, g))
    assert sorted(name for name, _ in moved) == ["hvd_dsa_bwd", "hvd_dsa_fwd"]

    scale = d ** -0.5
    heads_first = [jnp.swapaxes(x, 1, 2) for x in (q, k, v, g)]
    _, res = pallas_attention._flash_fwd_impl(
        *heads_first[:3], True, None, block_q, block_k, scale, True, select)
    tiles, *operands = pallas_attention._bwd_operands(
        block_q, block_k, True, None, res, heads_first[3])
    assert tiles.learned and tiles.padded_keys and operands[0].shape[2] > s
    assert operands[-1] is select
    one = pallas_attention._bwd_one_pass(tiles, scale, True, *operands)
    two = pallas_attention._bwd_two_kernels(tiles, scale, True, *operands)
    # The one pass has no use for ``by_query``: spoiled, nothing moves.
    blind = pallas_attention._bwd_one_pass(
        tiles, scale, True, *operands[:-1],
        select._replace(by_query=jnp.zeros_like(select.by_query)))
    static = None
    if every:
        static = pallas_attention._bwd_one_pass(
            tiles._replace(learned=False), scale, True, *operands[:-1])
    assert one[0].dtype == two[0].dtype == dtype
    assert not np.asarray(one[0][:, :, s:], np.float32).any()
    far = 1e-5 if dtype == jnp.float32 else 3e-2
    for i, (got, same, ref) in enumerate(zip(one, two, want)):
        assert got.shape == same.shape and got.dtype == same.dtype
        assert (np.asarray(got) == np.asarray(blind[i])).all()
        if static is not None:
            assert (np.asarray(got) == np.asarray(static[i])).all()
        got, same = (jnp.swapaxes(x[:, :, :s], 1, 2).astype(jnp.float32)
                     for x in (got, same))
        assert got.shape == ref.shape
        assert (np.asarray(got) == np.asarray(same)).all()
        assert _rel(got, ref) < far


@pytest.mark.parametrize("rows,width", [(128, 32), (128, 64), (256, 128)])
def test_the_transposed_product_has_one_meaning_in_both_modes(rows, width):
    """``_dot_tn`` is ``a^T . b`` either way. A SQUARE ``a`` (a score
    tile's block_k == block_q) would take the wrong contraction without
    a shape error, so the form Mosaic compiles (``interp`` False: the
    transposed dimension numbers) is held here to the interpret-mode
    form within float32's rounding, and that one to the plain product of
    the transposed tile BIT FOR BIT, also under ``jit``, where XLA:CPU
    would fold a bare ``.T`` into the product and add in another
    order at some of these widths."""
    rng = np.random.RandomState(5)
    a = jnp.asarray(rng.randn(rows, rows), jnp.float32)
    b = jnp.asarray(rng.randn(rows, width), jnp.float32)
    plain = np.asarray(pallas_attention._dot(
        jnp.asarray(np.ascontiguousarray(np.asarray(a).T)), b,
        pallas_attention._NN))
    assert not np.allclose(plain, np.asarray(a @ b), atol=1.0)
    on_chip, interpreted = (
        np.asarray(jax.jit(lambda a, b, m=m: pallas_attention._dot_tn(
            a, b, m))(a, b)) for m in (False, True))
    assert (interpreted == plain).all()
    assert np.abs(on_chip - plain).max() < 1e-5 * np.abs(plain).max()
