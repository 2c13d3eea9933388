"""Public jit'd wrappers around the Pallas kernels.

Each wrapper pads inputs to kernel block granularity, selects block shapes
through the 4D-tile optimizer (``core.tiling``), runs the kernel
(``interpret=True`` automatically off-TPU), and unpads.  These are the ops the
framework calls; ``ref.py`` holds the oracles tests compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.tiling import choose_matmul_blocks
from repro.tune.registry import dtype_code, tunable
from . import flash_attention as _fa
from . import paged_attn as _pa
from . import ssd_scan as _ssd
from . import stream_gd as _gd
from . import stream_mac_conv as _conv
from . import stream_maxpool as _mp
from . import tiled_matmul as _mm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(flag: bool | None) -> bool:
    return (not _on_tpu()) if flag is None else flag


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block(size: int, pref: int, align: int = 8) -> int:
    """Block size: ``pref`` when the dim is large, else the padded dim."""
    if size >= pref:
        return pref
    return size + ((-size) % align)


# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def tiled_matmul(x: jax.Array, y: jax.Array, interpret: bool | None = None):
    m, k = x.shape
    _, n = y.shape
    bm, bn, bk = choose_matmul_blocks(m, n, k, dtype_bytes=x.dtype.itemsize)
    bm, bn, bk = _block(m, bm), _block(n, bn, 128), _block(k, bk, 128)
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    yp = _pad_to(_pad_to(y, 0, bk), 1, bn)
    out = _mm.tiled_matmul(xp, yp, bm, bn, bk, interpret=_interpret(interpret))
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("stride", "padding", "block_yo", "interpret")
)
def stream_mac_conv(
    x: jax.Array,
    w: jax.Array,
    stride: tuple[int, int] = (1, 1),
    padding: tuple[int, int] = (0, 0),
    block_yo: int = 8,
    interpret: bool | None = None,
):
    """NHWC conv with HWIO weights (the paper's CONV layer).

    Pads for the TPU's tiling: Ci and Co to the 128-lane tile, the output
    width to the sublane tile (8 rows of 32-bit, 16 of bf16), and W to the
    columns that width reads, rounded to the sublane tile too."""
    interp = _interpret(interpret)
    if stride != (1, 1) and x.dtype.itemsize != 4 and not interp:
        # a strided tap is a strided VMEM load, which Mosaic implements for
        # 32-bit data only ("Strided load with non 32-bit data")
        raise NotImplementedError(
            f"stream_mac_conv: stride {stride} compiles for TPU only with "
            f"32-bit data, got {x.dtype}"
        )
    n, h, wd, ci = x.shape
    kh, kw, _, co = w.shape
    sy, sx = stride
    py, px = padding
    yo = (h + 2 * py - kh) // sy + 1
    wo = (wd + 2 * px - kw) // sx + 1

    sub = 8 * max(1, 4 // x.dtype.itemsize)
    bci = 128
    bco = 128
    byo = min(block_yo, yo)
    yo_p = yo + ((-yo) % byo)
    wo_p = wo + ((-wo) % sub)
    h_need = (yo_p - 1) * sy + kh
    w_need = (wo_p - 1) * sx + kw
    w_need += (-w_need) % sub

    xp = jnp.pad(x, ((0, 0), (py, max(0, h_need - h - py)),
                     (px, max(0, w_need - wd - px)), (0, 0)))
    xp = _pad_to(xp[:, :h_need, :w_need], 3, bci)
    wp = _pad_to(_pad_to(w, 2, bci), 3, bco)
    out = _conv.stream_mac_conv(
        xp, wp, stride=stride, block_yo=byo, block_co=bco, block_ci=bci,
        wo=wo_p, interpret=interp,
    )
    return out[:, :yo, :wo, :co]


@functools.partial(jax.jit, static_argnames=("window", "stride", "interpret"))
def stream_maxpool(
    x: jax.Array,
    window: tuple[int, int],
    stride: tuple[int, int],
    interpret: bool | None = None,
):
    n, h, w, c = x.shape
    bc = _block(c, 128)
    xp = _pad_to(x, 3, bc)
    out = _mp.stream_maxpool(
        xp, window, stride, block_c=bc, interpret=_interpret(interpret)
    )
    return out[..., :c]


@functools.partial(jax.jit, static_argnames=("interpret",))
def stream_gd(derivs: jax.Array, coeffs: jax.Array, interpret: bool | None = None):
    """Eq. (1) update over arbitrary-shaped weights: derivs (J, *shape)."""
    j = derivs.shape[0]
    shape = derivs.shape[1:]
    flat = derivs.reshape(j, -1)
    m = flat.shape[1]
    bm = _block(m, 1024, 128)
    flat = _pad_to(flat, 1, bm)
    out = _gd.stream_gd(flat, coeffs, block_m=bm, interpret=_interpret(interpret))
    return out[:m].reshape(shape)


def _flash_pallas_shape_class(q, k, *_a) -> str:
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    return (f"b{b}.sq{sq}.sk{sk}.h{h}.hkv{hkv}.d{d}"
            f".{dtype_code(q.dtype)}")


def _flash_pallas_cost(params, q, k, *_a):
    """(flops, HBM bytes) vs (block_q, block_k): k/v stream through VMEM
    once per q-block grid step, so HBM read traffic scales with
    ceil(Sq/block_q); block_k only repartitions the inner loop (VMEM
    resident, no HBM multiplier)."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    nq = -(-sq // min(params["block_q"], sq))
    itemsize = jnp.dtype(q.dtype).itemsize
    flops = 4.0 * b * h * sq * sk * d
    bytes_ = float(itemsize) * (2 * b * sq * h * d
                                + nq * 2 * b * sk * hkv * d)
    return flops, bytes_


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "q_offset", "block_q", "block_k", "interpret",
    ),
)
@tunable(
    "attn.flash_pallas",
    space={"block_q": (64, 128, 256), "block_k": (128, 256, 512)},
    defaults={"block_q": 128, "block_k": 128},
    shape_class=_flash_pallas_shape_class,
    cost_model=_flash_pallas_cost,
    # interpret mode is not a timing proxy (kernel_bench's standing rule),
    # so this space is only tunable where the kernel actually compiles —
    # registered anyway: the registry is how a kernel joins for free, and
    # off-TPU lookups fall back to the 128/128 defaults
    backends=("tpu",),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    scale = float(scale) if scale is not None else float(d) ** -0.5
    dp = d + ((-d) % 128)
    bq = min(block_q or 128, sq + ((-sq) % 8))
    bk = min(block_k or 128, sk + ((-sk) % 128))
    qp = _pad_to(_pad_to(q, 2, bq), 3, dp)
    kp = _pad_to(_pad_to(k, 2, bk), 3, dp)
    vp = _pad_to(_pad_to(v, 2, bk), 3, dp)
    out = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window, scale=scale,
        q_offset=q_offset, kv_len=sk, block_q=bq, block_k=bk,
        interpret=_interpret(interpret),
    )
    return out[:, :, :sq, :d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pool: jax.Array, block_table: jax.Array,
                 interpret: bool | None = None):
    """Block-table gather of a page pool: pool (n_pages, *page) + table
    (B, P) → (B, P, *page); -1 entries read as zeros.  Arbitrary page tails
    are flattened to one row per page (a page is one DMA burst)."""
    n = pool.shape[0]
    page_shape = pool.shape[1:]
    flat = pool.reshape(n, -1)
    f = flat.shape[1]
    flat = _pad_to(flat, 1, 128)
    out = _pa.paged_gather(flat, block_table.astype(jnp.int32),
                           interpret=_interpret(interpret))
    b, p = block_table.shape
    return out[..., :f].reshape((b, p) + page_shape)


def paged_block_pages(page_size: int, pages_per_lane: int) -> int:
    """Pages ``paged_attention`` streams per loop step: the fewest that hold
    at least 128 tokens (one lane-width score tile) and divide the block
    table's width, else the whole width."""
    for n in range(1, pages_per_lane + 1):
        if pages_per_lane % n == 0 and n * page_size >= 128:
            return n
    return pages_per_lane


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(
    q: jax.Array,             # (B, H, D) one decode token per lane
    k_pool: jax.Array,        # (n_pages, PS, Hkv, D) — pool layout of the
    v_pool: jax.Array,        #   paged serving cache
    block_table: jax.Array,   # (B, P) int32, -1 = unallocated
    lengths: jax.Array,       # (B,) int32 valid tokens per lane
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Fused paged decode-attention read (GQA grouped, online softmax):
    each lane reads only the pages its length reaches, a block of
    ``paged_block_pages`` pages at a time."""
    b, h, d = q.shape
    n, ps, hkv, _ = k_pool.shape
    rep = h // hkv
    rep_p = rep + ((-rep) % 8)
    dp = d + ((-d) % 128)
    qg = _pad_to(_pad_to(q.reshape(b, hkv, rep, d), 2, rep_p), 3, dp)
    kp = _pad_to(k_pool.transpose(2, 0, 1, 3), 3, dp)
    vp = _pad_to(v_pool.transpose(2, 0, 1, 3), 3, dp)
    out = _pa.paged_decode_attention(
        qg, kp, vp, block_table.astype(jnp.int32), lengths.astype(jnp.int32),
        pages_per_block=paged_block_pages(ps, block_table.shape[1]),
        scale=float(scale) if scale is not None else float(d) ** -0.5,
        interpret=_interpret(interpret),
    )
    return out[:, :, :rep, :d].reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, b, c, dt, a, chunk: int = 128, interpret: bool | None = None):
    """Mamba-2 SSD sequence mix (VMEM-resident chunk kernel)."""
    return _ssd.ssd_scan(xh, b, c, dt, a, chunk=chunk,
                         interpret=_interpret(interpret))
