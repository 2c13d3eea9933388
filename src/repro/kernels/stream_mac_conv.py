"""STREAM_MAC 4D-tiled convolution (Pallas, TPU target) — the paper's core op.

Faithful structure (§IV-A / Fig 5):
  * the Pallas grid walks (batch, T_Y row-stripes, T_Co blocks) — the tile
    work-list each NeuroCluster pulls from;
  * the kernel body DMAs one *augmented* input tile (rows including the halo)
    from HBM ("DRAM vault") into a VMEM scratch ("cluster SPM") with an
    explicit async copy — the cluster DMA engine;
  * it then loops over T_Ci blocks performing partial-sum accumulation into a
    resident f32 output tile (Fig 3d: D += A · K_AD), with the (ky, kx)
    hardware-loops unrolled around an MXU contraction over T_Ci;
  * the output tile is written back once — DRAM write bandwidth off the
    critical path (<4 % of reads in the paper, exactly 1/n_ci of reads here).

Hardware adaptation: the NST scalar MAC stream becomes a (rows×width, T_Ci)
× (T_Ci, T_Co) MXU contraction per filter tap; the zig-zag layout becomes
channels-minor NHWC so each tile's HBM window is contiguous per row.  Every
tap's patch and weight slice is read from a VMEM *ref* (a shifted load, not a
slice of a loaded value), and the patch is flattened to a 2-D matmul operand;
``ops.stream_mac_conv`` pads W to the sublane tile and Ci/Co to the lane tile
so those reads and the HBM window stay aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(
    x_hbm,            # (N, H_pad, W_pad, Ci)   in ANY/HBM — DMA'd manually
    w_ref,            # (KH, KW, Ci, bco)       VMEM block (full Ci)
    o_ref,            # (1, byo, WO, bco)       VMEM output block
    x_spm,            # scratch: (bh_in, W_pad, bci)  — the "SPM" tile
    acc_ref,          # scratch: (byo * WO, bco) f32  — resident partial sums
    dma_sem,
    *,
    kh: int,
    kw: int,
    sy: int,
    sx: int,
    byo: int,
    wo: int,
    bci: int,
    n_ci: int,
):
    n = pl.program_id(0)
    yb = pl.program_id(1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    y0 = yb * byo * sy

    def ci_pass(ci, _):
        c0 = pl.multiple_of(ci * bci, bci)
        # --- cluster DMA: fetch one augmented input tile (with halo rows) ---
        copy = pltpu.make_async_copy(
            x_hbm.at[n, pl.ds(y0, x_spm.shape[0]), :, pl.ds(c0, bci)],
            x_spm,
            dma_sem,
        )
        copy.start()
        copy.wait()
        # --- NST streams: (ky, kx) hardware loops around a T_Ci contraction -
        for dy in range(kh):
            for dx in range(kw):
                # (byo, WO, bci); a unit stride is a plain shifted load
                patch = x_spm[pl.ds(dy, byo, sy), pl.ds(dx, wo, sx), :]
                wt = w_ref[dy, dx, pl.ds(c0, bci), :]         # (bci, bco)
                acc_ref[...] += jnp.dot(
                    patch.reshape(byo * wo, bci), wt,
                    preferred_element_type=jnp.float32,
                )
        return 0

    jax.lax.fori_loop(0, n_ci, ci_pass, 0)
    o_ref[...] = acc_ref[...].reshape(o_ref.shape).astype(o_ref.dtype)


def stream_mac_conv(
    x: jax.Array,                      # (N, H, W, Ci) — already zero-padded
    w: jax.Array,                      # (KH, KW, Ci, Co)
    stride: tuple[int, int] = (1, 1),
    block_yo: int = 8,
    block_co: int = 128,
    block_ci: int = 128,
    wo: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Valid conv over a pre-padded input.  Ci % block_ci == 0, Co % block_co
    == 0, and YO % block_yo == 0 are required (``ops.stream_mac_conv`` pads).
    ``wo`` (default: every valid column) lets the wrapper compute a
    sublane-aligned output width from a W padded to match."""
    n, h, wdt, ci = x.shape
    kh, kw, ci2, co = w.shape
    assert ci == ci2
    sy, sx = stride
    yo = (h - kh) // sy + 1
    wo = (wdt - kw) // sx + 1 if wo is None else wo
    assert (wo - 1) * sx + kw <= wdt
    assert yo % block_yo == 0 and co % block_co == 0 and ci % block_ci == 0
    n_ci = ci // block_ci
    bh_in = (block_yo - 1) * sy + kh
    grid = (n, yo // block_yo, co // block_co)
    kern = functools.partial(
        _conv_kernel,
        kh=kh, kw=kw, sy=sy, sx=sx, byo=block_yo, wo=wo, bci=block_ci, n_ci=n_ci,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),               # x stays in HBM
            pl.BlockSpec((kh, kw, ci, block_co), lambda n_, y_, c_: (0, 0, 0, c_)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_yo, wo, block_co), lambda n_, y_, c_: (n_, y_, 0, c_)
        ),
        out_shape=jax.ShapeDtypeStruct((n, yo, wo, co), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bh_in, wdt, block_ci), x.dtype),
            pltpu.VMEM((block_yo * wo, block_co), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(x, w)
