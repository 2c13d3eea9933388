"""Pure-jnp oracles for every Pallas kernel (used by tests + interpret sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tiled_matmul(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.dot(
        x.astype(jnp.float32), y.astype(jnp.float32)
    ).astype(x.dtype)


def stream_mac_conv(
    x: jax.Array,           # (N, H, W, Ci)
    w: jax.Array,           # (KH, KW, Ci, Co)
    stride: tuple[int, int] = (1, 1),
    padding: tuple[int, int] = (0, 0),
) -> jax.Array:
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        window_strides=stride,
        padding=((padding[0], padding[0]), (padding[1], padding[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out.astype(x.dtype)


def stream_maxpool(
    x: jax.Array,           # (N, H, W, C)
    window: tuple[int, int],
    stride: tuple[int, int],
) -> jax.Array:
    return jax.lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        jax.lax.max,
        (1, window[0], window[1], 1),
        (1, stride[0], stride[1], 1),
        "VALID",
    )


def stream_gd(derivs: jax.Array, coeffs: jax.Array) -> jax.Array:
    """Paper Eq. (1): W_i = sum_j C_j * W_i^{(j)}.  derivs: (J, ...), coeffs: (J,)."""
    c = coeffs.reshape((-1,) + (1,) * (derivs.ndim - 1)).astype(jnp.float32)
    return jnp.sum(c * derivs.astype(jnp.float32), axis=0).astype(derivs.dtype)


def flash_attention(
    q: jax.Array,           # (B, H, Sq, D)
    k: jax.Array,           # (B, Hkv, Sk, D)
    v: jax.Array,           # (B, Hkv, Sk, D)
    causal: bool = True,
    window: int | None = None,   # sliding window (RecurrentGemma local attn)
    scale: float | None = None,
    q_offset: int = 0,      # absolute position of q[0] (decode: cache length)
) -> jax.Array:
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)   # fully-masked rows
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_gather(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Block-table gather oracle.  pool (n_pages, *page), block_table (B, P)
    int32 with -1 = unallocated → (B, P, *page); -1 pages read as zeros."""
    view = jnp.take(pool, jnp.clip(block_table, 0, pool.shape[0] - 1), axis=0)
    mask = (block_table >= 0).reshape(block_table.shape + (1,) * (pool.ndim - 1))
    return jnp.where(mask, view, jnp.zeros((), pool.dtype))


def paged_decode_attention(
    q: jax.Array,             # (B, Hkv, rep, D) one decode token per lane
    k_pool: jax.Array,        # (L, n_pages, Hkv, PS, Dp) stacked pools,
    v_pool: jax.Array,        #   head dim padded to Dp >= D
    block_table: jax.Array,   # (B, P) int32, -1 = unallocated
    lengths: jax.Array,       # (B,) int32 valid tokens per lane
    layer: int,               # the layer of the pools to read
    scale: float | None = None,
) -> jax.Array:
    """Gather-then-attend oracle for the fused paged decode read."""
    b, hkv, rep, d = q.shape
    ps = k_pool.shape[3]
    p = block_table.shape[1]
    scale = scale if scale is not None else float(d) ** -0.5
    clipped = jnp.clip(block_table, 0, k_pool.shape[1] - 1)

    def lane_view(pool):                           # → (B, Hkv, P*PS, D)
        pages = jnp.take(pool[layer, ..., :d], clipped, axis=0)
        return pages.transpose(0, 2, 1, 3, 4).reshape(b, hkv, p * ps, d)

    k, v = lane_view(k_pool), lane_view(v_pool)
    s = jnp.einsum("bgrd,bgkd->bgrk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    kpos = jnp.arange(p * ps)
    valid = (kpos[None] < lengths[:, None]) & jnp.repeat(
        block_table >= 0, ps, axis=1
    )
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    a = jnp.where(jnp.isnan(a), 0.0, a)
    out = jnp.einsum("bgrk,bgkd->bgrd", a, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_scan(xh, b, c, dt, a):
    """Exact sequential SSD recurrence (oracle for kernels/ssd_scan)."""
    bsz, sl, h, p = xh.shape
    n = b.shape[-1]
    xh32 = xh.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)

    def step(state, xs):
        x_t, b_t, c_t, dt_t = xs                    # (B,H,P),(B,N),(B,N),(B,H)
        da = jnp.exp(dt_t * a[None])                # (B,H)
        state = state * da[..., None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt_t, b_t, x_t)
        y = jnp.einsum("bn,bhpn->bhp", c_t, state)
        return state, y

    init = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, ys = jax.lax.scan(
        step, init,
        (xh32.swapaxes(0, 1), b32.swapaxes(0, 1), c32.swapaxes(0, 1),
         dt32.swapaxes(0, 1)),
    )
    return ys.swapaxes(0, 1).astype(xh.dtype)
