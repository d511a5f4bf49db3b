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
                      introspect.KERNEL_FLASH_DKV,
                      introspect.KERNEL_FLASH_DQ,
                      introspect.KERNEL_DSA_FWD, introspect.KERNEL_DSA_DKV,
                      introspect.KERNEL_DSA_DQ)
            for kind in ("full", "edge", "skipped", "below")}

    before = read()
    trace()
    return {key: n - before[key] for key, n in read().items()}


def _trace_gradient(b, s, h, h_kv, d, window=None, selected=False, **tiles):
    """Trace forward, dK/dV and dQ at the shape; nothing runs."""
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16)
    select = None
    if selected:
        plane = jax.ShapeDtypeStruct((b, -(-s // (128 * 32)), s, 128),
                                     jnp.int32)
        select = pallas_attention.Selection(plane, plane)

    def loss(q, k, v, select):
        return flash_attention(q, k, v, causal=True, window=window,
                               select=select,
                               **tiles).astype(jnp.float32).sum()

    jax.eval_shape(jax.grad(loss, (0, 1, 2)), q, kv, kv, select)


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
    for k in (introspect.KERNEL_FLASH_FWD, introspect.KERNEL_FLASH_DKV,
              introspect.KERNEL_FLASH_DQ):
        got = tuple(moved[k, kind] for kind in ("full", "edge", "skipped"))
        assert got == want, (k, got)


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
            calls.append((config["sliding_window"], False))
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
    """A call that names no tile, as the model's is, traces the three
    kernels with ``_default_blocks``' tiles and nothing else's."""
    shape = (b, s, h, h_kv, d, window, selected)
    block_q, block_k = pallas_attention._default_blocks(s, s)
    by_rule = _tiles_moved(lambda: _trace_gradient(
        *shape, block_q=block_q, block_k=block_k))
    assert sum(by_rule.values()) > 0
    assert _tiles_moved(lambda: _trace_gradient(*shape)) == by_rule
    other = _tiles_moved(lambda: _trace_gradient(
        *shape, block_q=block_q // 2, block_k=block_k))
    assert other != by_rule     # the counter tells tiles apart


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


def test_short_ragged_bfloat16_matches_dense():
    """S=100 in bf16: the tile is padded to 112 rows, the padded keys
    masked and the padded query rows sliced off."""
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 100, 2, 64), jnp.bfloat16)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    def dense(q, k, v):
        return dense_reference(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), True)

    out = flash_attention(q, k, v, causal=True)
    assert out.shape == (1, 100, 2, 64) and out.dtype == jnp.bfloat16
    assert _rel(out.astype(jnp.float32), dense(q, k, v)) < 2e-2
    got = jax.grad(loss(lambda *a: flash_attention(*a, causal=True)),
                   (0, 1, 2))(q, k, v)
    ref = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    for a, b_ in zip(got, ref):
        assert a.shape == (1, 100, 2, 64)
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


@pytest.mark.parametrize("q_len,kv_len", [(200, 200), (100, 200)])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("window", [None, 1, 3, 64, 500])
def test_window_and_grouped_heads_match_dense(window, group, q_len, kv_len):
    """Forward and all three gradients against the dense masked
    attention: S no multiple of the 64 x 32 tiles, ``kv_len > q_len``,
    K/V ``H // group`` heads wide going in and coming back; and the
    tiles' classes and counts against the mask itself, by both walks."""
    bq, bk, heads, d = 64, 32, 8, 16
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, q_len, heads, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, kv_len, heads // group, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, kv_len, heads // group, d), jnp.float32)
    weight = jnp.asarray(rng.randn(1, q_len, heads, d), jnp.float32)

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
