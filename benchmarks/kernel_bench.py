"""Kernel microbenchmarks: wall-time of the jitted XLA reference paths on
CPU (the Pallas kernels are TPU-targeted; interpret mode is not a timing
proxy) + derived roofline positioning for the TPU target."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.roofline import V5E
from repro.core.tiling import choose_matmul_blocks
from repro.kernels import ref


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


def _med_time(fn, *args, iters=20):
    """Median per-call wall time in us — robust to scheduler noise (the
    bench-gate ratios are built from these, so one descheduled call must
    not swing a gated metric)."""
    jax.block_until_ready(fn(*args))       # compile + warm caches
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


def bench():
    rows = []
    rng = np.random.default_rng(0)

    # matmul: measure CPU ref; derive TPU roofline position for chosen blocks
    for m, k, n in ((512, 512, 512), (1024, 1024, 1024)):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        f = jax.jit(ref.tiled_matmul)
        us = _time(f, x, y)
        flops = 2 * m * n * k
        bm, bn, bk = choose_matmul_blocks(m, n, k)
        oi = flops / (2 * (m * k + k * n + m * n))
        tpu_roof = min(V5E.peak_flops, oi * V5E.hbm_bw)
        rows.append((f"kernel.matmul.{m}", round(us, 1),
                     f"blocks=({bm},{bn},{bk}),tpu_roof={tpu_roof/1e12:.0f}TF"))

    # conv (the paper's op): CPU ref timing + OI
    for hw, ci, co, kk in ((56, 64, 64, 3), (14, 256, 256, 3)):
        x = jnp.asarray(rng.normal(size=(1, hw, hw, ci)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(kk, kk, ci, co)), jnp.float32)
        f = jax.jit(lambda a, b: ref.stream_mac_conv(a, b, (1, 1), (1, 1)))
        us = _time(f, x, w)
        flops = 2 * hw * hw * co * kk * kk * ci
        rows.append((f"kernel.conv.{hw}x{hw}x{ci}", round(us, 1),
                     f"gflop={flops/1e9:.2f}"))

    # attention
    q = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.float32)
    us = _time(jax.jit(lambda a: ref.flash_attention(a, a, a)), q)
    rows.append(("kernel.attention.512", round(us, 1), "b1h8d64"))
    return rows


def bench_json() -> dict:
    """Kernel-vs-oracle timing metrics for the CI bench gate.

    The ``*.oracle_ratio`` keys are **impl_us / oracle_us** on the SAME
    machine — lower is better, < 1.0 means the production path beats its
    naive oracle (flipped from the pre-tuner oracle/impl spelling; see
    MIGRATION.md).  Same-machine ratios are machine-independent enough to
    gate with a tolerance; the ``*_us`` keys are advisory absolutes.

    The impl legs run with the tuned table active (tunable params passed
    as ``None`` resolve from ``TUNED_kernels.json`` at trace time — the
    bench inputs come from ``repro.tune.cutouts``, the same builders
    ``python -m repro.tune --update`` tuned, so the shape-class keys match
    by construction).  The ``*.tuned_ratio`` keys are tuned_us /
    default_us (default = ``no_tuning()``, the declared defaults); also
    lower-is-better and gated for the SSD and paged-decode kernels.
    """
    from repro.models.attention import (
        decode_attention,
        flash_attention_xla,
        paged_decode_attention_xla,
    )
    from repro.tune import cutouts, no_tuning

    rng = np.random.default_rng(0)
    out = {}

    # streaming chunked attention (the production XLA path) vs the
    # materialized-logits oracle, prefill shape
    q, _, _ = cutouts.build("attn.flash_xla")
    impl = jax.jit(lambda a: flash_attention_xla(a, a, a, chunk=None))
    oracle = jax.jit(
        lambda a: ref.flash_attention(
            a.transpose(0, 2, 1, 3), a.transpose(0, 2, 1, 3),
            a.transpose(0, 2, 1, 3),
        )
    )
    impl_us = _med_time(impl, q)
    oracle_us = _med_time(oracle, q)
    out["attn.flash_xla.us"] = round(impl_us, 1)
    out["attn.flash_xla.oracle_ratio"] = impl_us / oracle_us

    # paged decode attention (XLA paged path: transient per-layer gather)
    # vs the gather-whole-view-then-attend oracle
    qd, kpool, vpool, bt, pos, layer = cutouts.build("attn.paged_decode")
    lanes, p = bt.shape
    hkv, ps = kpool.shape[2:4]
    d = qd.shape[-1]
    impl = jax.jit(lambda *a: paged_decode_attention_xla(*a, layer))

    def _oracle(qq, kp, vp, table, position):
        def view(pool):          # (lanes, P, Hkv, PS, Dp) → (lanes, S, Hkv, D)
            pages = ref.paged_gather(pool[layer], table)[..., :d]
            return pages.transpose(0, 1, 3, 2, 4).reshape(lanes, p * ps,
                                                          hkv, d)
        return decode_attention(qq, view(kp), view(vp), position=position)

    oracle = jax.jit(_oracle)
    impl_us = _med_time(impl, qd, kpool, vpool, bt, pos)
    with no_tuning():
        dflt = jax.jit(lambda *a: paged_decode_attention_xla(*a, layer))
        default_us = _med_time(dflt, qd, kpool, vpool, bt, pos)
    oracle_us = _med_time(oracle, qd, kpool, vpool, bt, pos)
    out["attn.paged_decode.us"] = round(impl_us, 1)
    out["attn.paged_decode.oracle_ratio"] = impl_us / oracle_us
    out["attn.paged_decode.tuned_ratio"] = impl_us / default_us

    # SSD chunk scan (the production XLA dual form with the exact masked
    # decay — models/ssm.ssd_chunked) vs the exact sequential recurrence
    # oracle (ref.ssd_scan); Mamba-2 decode/prefill hot path
    from repro.models.ssm import ssd_chunked

    cfg, xh, bbn, ccn, dtn, a_log, d_skip, dt_bias = cutouts.build(
        "ssd.chunked")
    impl = jax.jit(lambda *a: ssd_chunked(cfg, *a)[0])
    oracle = jax.jit(
        lambda xx, bb_, cc_, dd: ref.ssd_scan(
            xx, bb_, cc_, jax.nn.softplus(dd + dt_bias), -jnp.exp(a_log)
        )
    )
    impl_us = _med_time(impl, xh, bbn, ccn, dtn, a_log, d_skip, dt_bias)
    with no_tuning():
        dflt = jax.jit(lambda *a: ssd_chunked(cfg, *a)[0])
        default_us = _med_time(dflt, xh, bbn, ccn, dtn, a_log, d_skip,
                               dt_bias)
    oracle_us = _med_time(oracle, xh, bbn, ccn, dtn)
    out["ssd.chunked.us"] = round(impl_us, 1)
    out["ssd.chunked.oracle_ratio"] = impl_us / oracle_us
    out["ssd.chunked.tuned_ratio"] = impl_us / default_us

    # MoE grouped-einsum capacity dispatch (the GSPMD production form in
    # models/moe: router + dispatch + the tunable expert_ffn) vs the dense
    # every-token-through-every-expert oracle
    from repro.models.moe import _dispatch_masks, expert_ffn

    g_, t_, e_, c_, d_, f_ = 1, 512, 8, 128, 128, 256
    k_ = 2
    xt = jnp.asarray(rng.normal(size=(g_, t_, d_)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d_, e_)) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e_, d_, f_)) * d_ ** -0.5, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e_, f_, d_)) * f_ ** -0.5, jnp.float32)

    def moe_impl(x, r, w1, w2):
        gates = jax.nn.softmax(jnp.einsum("gtd,de->gte", x, r), axis=-1)
        disp, comb = _dispatch_masks(gates, k_, c_)
        xe = jnp.einsum("gtec,gtd->gecd", disp.astype(x.dtype), x)
        ye = expert_ffn(xe, w1, None, w2)
        return jnp.einsum("gtec,gecd->gtd", comb.astype(x.dtype), ye)

    def moe_oracle(x, r, w1, w2):
        gates = jax.nn.softmax(jnp.einsum("gtd,de->gte", x, r), axis=-1)
        topw, topi = jax.lax.top_k(gates, k_)
        topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)
        w = jnp.sum(jax.nn.one_hot(topi, e_) * topw[..., None], axis=2)
        h = jax.nn.gelu(jnp.einsum("gtd,edf->gtef", x, w1))
        ye = jnp.einsum("gtef,efd->gted", h, w2)
        return jnp.einsum("gte,gted->gtd", w, ye)

    impl = jax.jit(moe_impl)
    oracle = jax.jit(moe_oracle)
    impl_us = _med_time(impl, xt, router, wg, wd)
    oracle_us = _med_time(oracle, xt, router, wg, wd)
    out["moe.dispatch.us"] = round(impl_us, 1)
    out["moe.dispatch.oracle_ratio"] = impl_us / oracle_us

    # matmul advisory absolute
    x = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    out["matmul.512.us"] = round(_med_time(jax.jit(ref.tiled_matmul), x, x), 1)
    return out
