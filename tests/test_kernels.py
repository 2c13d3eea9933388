"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

F32, BF16 = jnp.float32, jnp.bfloat16


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == BF16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# tiled_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8), (64, 96, 80), (128, 128, 128), (200, 300, 100), (1, 7, 5),
    (256, 512, 128),
])
@pytest.mark.parametrize("dt", [F32, BF16])
def test_tiled_matmul(rng, m, k, n, dt):
    x = jnp.asarray(rng.normal(size=(m, k)), dt)
    y = jnp.asarray(rng.normal(size=(k, n)), dt)
    got = ops.tiled_matmul(x, y)
    want = ref.tiled_matmul(x, y)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dt)
    )


# ---------------------------------------------------------------------------
# stream_mac_conv  (the paper's core op)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,hw,ci,co,k,s,p", [
    (1, 16, 8, 16, 3, 1, 1),
    (2, 12, 3, 8, 5, 2, 2),
    (1, 9, 4, 4, 1, 1, 0),
    (1, 11, 3, 96, 11, 4, 0),       # AlexNet conv1 shape family
    (1, 8, 130, 8, 3, 1, 1),        # ci > lane width: multi-pass T_Ci
    (2, 7, 5, 6, 7, 1, 3),
])
@pytest.mark.parametrize("dt", [F32, BF16])
def test_stream_mac_conv(rng, n, hw, ci, co, k, s, p, dt):
    x = jnp.asarray(rng.normal(size=(n, hw, hw, ci)), dt)
    w = jnp.asarray(rng.normal(size=(k, k, ci, co)) / np.sqrt(k * k * ci), dt)
    got = ops.stream_mac_conv(x, w, stride=(s, s), padding=(p, p))
    want = ref.stream_mac_conv(x, w, stride=(s, s), padding=(p, p))
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dt)
    )


def test_stream_mac_conv_asymmetric_stride(rng):
    x = jnp.asarray(rng.normal(size=(1, 12, 10, 4)), F32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), F32)
    got = ops.stream_mac_conv(x, w, stride=(2, 1), padding=(1, 1))
    want = ref.stream_mac_conv(x, w, stride=(2, 1), padding=(1, 1))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# stream_maxpool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hw,c,k,s", [(8, 5, 2, 2), (13, 16, 3, 2), (7, 130, 3, 1)])
def test_stream_maxpool(rng, hw, c, k, s):
    x = jnp.asarray(rng.normal(size=(2, hw, hw, c)), F32)
    got = ops.stream_maxpool(x, (k, k), (s, s))
    want = ref.stream_maxpool(x, (k, k), (s, s))
    np.testing.assert_allclose(got, want)


# ---------------------------------------------------------------------------
# stream_gd  (Eq. 1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("j,shape", [(2, (7, 11)), (3, (64,)), (4, (5, 3, 2))])
def test_stream_gd(rng, j, shape):
    d = jnp.asarray(rng.normal(size=(j, *shape)), F32)
    c = jnp.asarray(rng.normal(size=(j,)), F32)
    got = ops.stream_gd(d, c)
    want = ref.stream_gd(d, c)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_stream_gd_is_sgd_update(rng):
    """W' = C0·W + C1·dW with C0=1-lr·wd, C1=-lr reproduces SGD (paper §V-B)."""
    w = jnp.asarray(rng.normal(size=(32,)), F32)
    g = jnp.asarray(rng.normal(size=(32,)), F32)
    lr, wd = 0.1, 0.01
    got = ops.stream_gd(jnp.stack([w, g]), jnp.asarray([1 - lr * wd, -lr]))
    want = (1 - lr * wd) * w - lr * g
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,hkv,sq,sk,d,causal,win,off", [
    (1, 4, 2, 64, 64, 32, True, None, 0),
    (2, 2, 1, 32, 128, 16, True, None, 96),     # decode-ish with offset
    (1, 2, 2, 128, 128, 64, True, 64, 0),       # sliding window
    (1, 4, 4, 64, 64, 32, False, None, 0),      # bidirectional (whisper enc)
    (1, 8, 2, 100, 70, 24, True, None, 0),      # ragged, padded dims
])
@pytest.mark.parametrize("dt", [F32, BF16])
def test_flash_attention(rng, b, h, hkv, sq, sk, d, causal, win, off, dt):
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)), dt)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, d)), dt)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, d)), dt)
    got = ops.flash_attention(q, k, v, causal=causal, window=win, q_offset=off)
    want = ref.flash_attention(q, k, v, causal=causal, window=win, q_offset=off)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dt)
    )


def test_flash_attention_blocks_sweep(rng):
    """Block-size invariance: different (bq, bk) tilings agree exactly-ish."""
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), F32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), F32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), F32)
    outs = [
        np.asarray(ops.flash_attention(q, k, v, block_q=bq, block_k=bk))
        for bq, bk in [(128, 128), (64, 128), (128, 64), (32, 32)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged_decode_attention — kernel vs oracle over ragged block tables
# ---------------------------------------------------------------------------

# lanes covering the ragged-table envelope: a partial last page, a single
# page, the full pages_per_lane width, and an empty (inactive) lane
_RAGGED_CASES = [
    # (block-table rows, lengths); pool is 12 pages of 16 tokens, P=4
    pytest.param([[0, 3, -1, -1], [5, -1, -1, -1], [1, 2, 7, 9], [4, 6, -1, -1]],
                 [20, 9, 64, 32], id="mixed-partial-single-max"),
    pytest.param([[2, -1, -1, -1]], [1], id="single-token-single-page"),
    pytest.param([[0, 1, 2, 3]], [63], id="max-pages-partial-last"),
    pytest.param([[0, 1, 2, 3]], [64], id="max-pages-exact"),
    pytest.param([[10, -1, -1, -1], [-1, -1, -1, -1]], [16, 0],
                 id="exact-page-plus-empty-lane"),
]


@pytest.mark.parametrize("table,lengths", _RAGGED_CASES)
@pytest.mark.parametrize("dt", [F32, BF16])
def test_paged_decode_attention_ragged(rng, table, lengths, dt):
    """Fused kernel == gather-then-attend oracle on ragged block tables
    (partial last page, single page, max pages, empty lanes)."""
    n, ps, g, d, h = 12, 16, 2, 32, 4
    kpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), dt)
    vpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), dt)
    bt = jnp.asarray(table, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    b = bt.shape[0]
    q = jnp.asarray(rng.normal(size=(b, h, d)), dt)
    got = ops.paged_attention(q, kpool, vpool, bt, ln)
    want = ref.paged_decode_attention(
        q.reshape(b, g, h // g, d), kpool.transpose(2, 0, 1, 3),
        vpool.transpose(2, 0, 1, 3), bt, ln,
    ).reshape(b, h, d)
    # fully-masked lanes (length 0) are don't-care outputs: the engine only
    # reads active lanes — compare where at least one key is visible
    visible = np.asarray(ln) > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[visible],
        np.asarray(want, np.float32)[visible], **_tol(dt)
    )


# lanes spanning several blocks: 24 pages of 16 tokens walk as three blocks
# of 8 pages (128 tokens); lengths at and around the block boundaries
_BLOCK_PS, _BLOCK_P = 16, 24


def _block_lanes(rng, lengths, h, g, d, dt, n_pool):
    """Pools, query and a block table over the pool's first lanes x P pages
    whose entries past the pages a lane's length needs are -1 (as the
    serving cache leaves them)."""
    b = len(lengths)
    kpool = jnp.asarray(rng.normal(size=(n_pool, _BLOCK_PS, g, d)), dt)
    vpool = jnp.asarray(rng.normal(size=(n_pool, _BLOCK_PS, g, d)), dt)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dt)
    need = -(-np.asarray(lengths) // _BLOCK_PS)
    pages = rng.permutation(b * _BLOCK_P).reshape(b, _BLOCK_P)
    table = np.where(np.arange(_BLOCK_P)[None] < need[:, None], pages, -1)
    return q, kpool, vpool, table.astype(np.int32)


def _paged_oracle(q, kpool, vpool, table, lengths):
    b, h, d = q.shape
    g = kpool.shape[2]
    return ref.paged_decode_attention(
        q.reshape(b, g, h // g, d), kpool.transpose(2, 0, 1, 3),
        vpool.transpose(2, 0, 1, 3), jnp.asarray(table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
    ).reshape(b, h, d)


@pytest.mark.parametrize("lengths", [
    pytest.param([0, 1, 127, 128, 129, _BLOCK_P * _BLOCK_PS],
                 id="empty-one-block-edges-full"),
    pytest.param([255, 256, 257, 383, 16], id="second-third-block-edges"),
])
@pytest.mark.parametrize("h,g,d", [
    pytest.param(16, 2, 128, id="qwen-h16-hkv2-d128"),
    pytest.param(4, 2, 32, id="h4-hkv2-d32"),
])
@pytest.mark.parametrize("dt", [F32, BF16])
def test_paged_decode_attention_multi_block(rng, lengths, h, g, d, dt):
    """Lanes spanning several blocks of pages == the oracle: every valid
    token attended, lanes of length 0 output zeros."""
    assert ops.paged_block_pages(_BLOCK_PS, _BLOCK_P) == 8
    q, kpool, vpool, table = _block_lanes(rng, lengths, h, g, d, dt,
                                          n_pool=len(lengths) * _BLOCK_P)
    got = ops.paged_attention(q, kpool, vpool, jnp.asarray(table),
                              jnp.asarray(lengths, jnp.int32))
    want = _paged_oracle(q, kpool, vpool, table, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("dt", [F32, BF16])
def test_paged_decode_attention_never_reads_past_the_length(rng, dt):
    """Table entries past the pages a lane's length needs point at a pool
    page of NaN; none of it reaches the output, which is finite and equal
    to the oracle over the valid prefix (where those entries are -1)."""
    lengths = [0, 1, 20, 127, 129, 300]
    h, g, d = 16, 2, 128
    b = len(lengths)
    q, kpool, vpool, table = _block_lanes(rng, lengths, h, g, d, dt,
                                          n_pool=b * _BLOCK_P + 1)
    nan_page = b * _BLOCK_P
    kpool = kpool.at[nan_page].set(jnp.nan)
    vpool = vpool.at[nan_page].set(jnp.nan)
    poisoned = np.where(table >= 0, table, nan_page).astype(np.int32)
    got = np.asarray(ops.paged_attention(
        q, kpool, vpool, jnp.asarray(poisoned),
        jnp.asarray(lengths, jnp.int32)), np.float32)
    want = _paged_oracle(q, kpool, vpool, table, lengths)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("page_size,pages,want", [
    (16, 128, 8), (16, 24, 8), (16, 4, 4), (8, 128, 16), (64, 6, 2),
    (16, 7, 7), (128, 3, 1),
])
def test_paged_block_pages(page_size, pages, want):
    """The fewest pages that hold 128 tokens and divide the table, else all."""
    assert ops.paged_block_pages(page_size, pages) == want


def test_paged_decode_attention_matches_model_xla_path(rng):
    """The fused kernel and the model layer's XLA paged path agree on the
    same pools/table/positions (positions = lengths - 1)."""
    from repro.models.attention import paged_decode_attention_xla

    n, ps, g, d, h = 12, 16, 2, 32, 4
    kpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), F32)
    vpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), F32)
    bt = jnp.asarray([[0, 3, -1, -1], [5, 2, 7, -1], [1, -1, -1, -1]],
                     jnp.int32)
    lengths = jnp.asarray([20, 45, 9], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, h, d)), F32)
    got = ops.paged_attention(q[:, 0], kpool, vpool, bt, lengths)
    # model-layer pools are (n_pages, PS, Hkv, D) — same layout
    want = paged_decode_attention_xla(q, kpool, vpool, bt, lengths - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_scan  (VMEM-resident Mamba-2 chunk kernel — §Perf cell 3's TPU answer)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 32, 2, 8, 8, 8),
    (2, 64, 4, 8, 16, 16),
    (2, 64, 4, 8, 16, 64),       # single chunk
    (1, 128, 3, 16, 8, 32),
])
@pytest.mark.parametrize("dt_", [F32, BF16])
def test_ssd_scan(rng, b, s, h, p, n, chunk, dt_):
    xh = jnp.asarray(rng.normal(size=(b, s, h, p)), dt_) * 0.5
    bb = jnp.asarray(rng.normal(size=(b, s, n)), dt_) * 0.5
    cc = jnp.asarray(rng.normal(size=(b, s, n)), dt_) * 0.5
    dts = jnp.asarray(rng.uniform(0.01, 0.5, size=(b, s, h)), F32)
    a = -jnp.asarray(rng.uniform(0.5, 1.5, size=(h,)), F32)
    got = ops.ssd_scan(xh, bb, cc, dts, a, chunk=chunk)
    want = ref.ssd_scan(xh, bb, cc, dts, a)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **(_tol(dt_) if dt_ == BF16 else dict(rtol=5e-4, atol=5e-4)),
    )
