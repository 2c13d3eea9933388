#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU chip.

    python chip_smoke.py               # one chip: serve phase, then ConvNet phase
    python chip_smoke.py --four-chips  # four chips: router replicas only

Serve phase: qwen2.5-3b at its published widths (36 layers, d_model 2048,
GQA 16/2, vocab 151936, bf16; random weights from ``--seed``) served by
``ServeEngine`` with the paged decode path and the fused Pallas
decode-attention kernel: 8 lanes, ``max_len`` 1024, page size 16, 8 greedy
requests of three prompt lengths, 16 new tokens each.  Checks every request's
tokens, that the decode program holds the compiled kernel (``tpu_custom_call``)
and that one decode step's logits match the XLA attention form on the same
pools.

ConvNet phase: VGG16 at 224 px, batch 8, bf16, through
``ConvNetExecutor(impl="pallas")`` — every conv layer on the compiled
``stream_mac_conv`` kernel — against ``impl="xla"`` on the same params and
input; plus one strided f32 layer against XLA's convolution.

``--four-chips``: four qwen2.5-3b replicas behind ``CubeRouter``
(``least_loaded``, one engine per device) against one engine, 16 greedy
requests; tokens must match by uid, each engine's pools on its own device.

Every check raises; nothing falls back to the CPU.  Without a TPU the script
exits non-zero before any phase runs.  The timings it prints are a one-off
smoke, not benchmark numbers.  The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "qwen2.5-3b"
SERVE_LANES, SERVE_MAX_LEN, PAGE_SIZE = 8, 1024, 16
PROMPT_LENGTHS = (64, 128, 256)
MAX_NEW = 16
VGG_BATCH, VGG_PX = 8, 224

# Pallas vs XLA decode attention, relative L2 error of one decode step's
# logits per lane.  Both read the same bf16 pages; the XLA form rounds q and
# the softmax weights to bf16 before its two contractions while the kernel
# keeps them in f32, so each layer's attention output differs by about one
# bf16 rounding (2^-8 relative), and 36 bf16 layers compound it: a v5e run
# at seed 0 measured 1.9e-2.  5e-2 is that with 2.5x headroom for other
# seeds and positions; a wrong page, mask or head mapping moves the logits
# by O(1), which the wrong-page control in ``serve_phase`` checks.
SERVE_LOGITS_RTOL = 5e-2
# stream_mac_conv vs XLA convolution on VGG16, relative L2 error of the
# final logits.  Both take bf16 operands, accumulate in f32 and round every
# layer's output to bf16; they differ in accumulation order, so each layer
# can flip the last bf16 bit (2^-8 relative) of some outputs.  Over 16
# layers (13 conv + 3 fc) such flips add up like a random walk to ~1e-2;
# 3e-2 leaves headroom and still fails any wrong tap, halo row or channel
# block, which moves the logits by O(1).
VGG_LOGITS_RTOL = 3e-2
# One strided f32 layer: the kernel's f32 contraction against XLA's f32
# convolution at HIGHEST precision; the MXU may take f32 operands in bf16
# passes, so allow bf16-level error.
STRIDED_RTOL = 1e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Seconds spent compiling (cache reads included) and persistent-cache
    hits and misses, from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses}


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "note": "one-off smoke, not a benchmark",
                      **fields}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_requests(n: int, lengths, vocab: int, max_new: int, seed: int):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=(lengths[i % len(lengths)],))
               .astype(np.int32) for i in range(n)]
    return lambda: [Request(uid=i, prompt=prompts[i], max_new_tokens=max_new)
                    for i in range(n)]


def serve_config():
    from repro.serve import CacheConfig, EngineConfig

    return EngineConfig(
        batch_slots=SERVE_LANES, max_len=SERVE_MAX_LEN,
        cache=CacheConfig(page_size=PAGE_SIZE, decode_path="paged",
                          attn_impl="pallas"),
    )


def check_tokens(done, n: int, max_new: int, vocab: int) -> dict:
    check(len(done) == n, f"{len(done)} of {n} requests completed")
    out = {}
    for r in done:
        check(len(r.out_tokens) == max_new,
              f"request {r.uid}: {len(r.out_tokens)} tokens, want {max_new}")
        check(all(0 <= t < vocab for t in r.out_tokens),
              f"request {r.uid}: token id outside [0, {vocab})")
        out[r.uid] = list(r.out_tokens)
    return out


def serve_phase(jax, meter, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serve import ServeEngine

    dev = jax.devices()[0]
    cfg = get_arch(SERVE_ARCH)
    model = build_model(cfg)
    c0 = meter.seconds
    params = model.init(jax.random.key(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    eng = ServeEngine(model, params, serve_config())
    n, max_new = SERVE_LANES, MAX_NEW
    reqs = make_requests(n, PROMPT_LENGTHS, cfg.vocab_size, max_new, seed)()
    for r in reqs:
        eng.submit(r)
    snap, most = None, 0
    t0 = time.perf_counter()
    while eng.load:
        eng.step()
        # at the fullest batch seen so far, keep the next step's inputs and
        # a copy of the pools for the Pallas-vs-XLA replay after the run
        if len(eng.sched.running) > most:
            most = len(eng.sched.running)
            snap = (jax.tree.map(jnp.copy, eng.cache.pools),
                    eng.decode_inputs())
    eng.pipeline.shutdown()
    wall = time.perf_counter() - t0
    toks = check_tokens(eng.completed, n, max_new, cfg.vocab_size)
    check(most >= 2, f"at most {most} lanes ever decoded together")

    pools, (bt, tokens, positions, active) = snap
    args = (eng.params, pools, jnp.asarray(bt), jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(active))
    hlo = eng._decode.lower(*args).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the engine's decode program holds no compiled Pallas kernel")

    steps = {impl: jax.jit(functools.partial(eng.model.decode_step_paged,
                                             attn_impl=impl))
             for impl in ("pallas", "xla")}

    def replay(impl, table):
        out = steps[impl](args[0], args[1], jnp.asarray(table), *args[3:])
        return np.asarray(jax.device_get(out[0][:, 0]), np.float32)

    lanes = [i for i in range(len(active)) if active[i]]
    want = replay("xla", bt)
    got = replay("pallas", bt)
    check(len(lanes) == most, f"{len(lanes)} active lanes in the replay")
    check(bool(np.isfinite(got).all()), "non-finite logits")
    errs = [rel_err(got[i], want[i]) for i in lanes]
    check(max(errs) <= SERVE_LOGITS_RTOL,
          f"Pallas vs XLA decode logits: max rel err {max(errs)} "
          f"> {SERVE_LOGITS_RTOL}")
    # negative control: with every lane reading its neighbour's pages the
    # kernel must miss the tolerance on every lane, or this check could not
    # tell a wrong page from rounding
    wrong = replay("pallas", np.roll(bt, 1, axis=0))
    wrong_err = min(rel_err(wrong[i], want[i]) for i in lanes)
    check(wrong_err > SERVE_LOGITS_RTOL,
          f"wrong pages moved the logits by only {wrong_err}")
    report("serve", arch=cfg.name, params=int(n_params), requests=n,
           tokens=sum(len(t) for t in toks.values()), wall_s=wall,
           compile_s_phase=meter.seconds - c0,
           replay_positions=[int(p) for p in positions[active]],
           pallas_vs_xla_max_rel_err=max(errs),
           wrong_pages_min_rel_err=wrong_err,
           peak_bytes_in_use=peak_bytes(dev), **meter.snapshot())


# ---------------------------------------------------------------------------
# the paper's ConvNet
# ---------------------------------------------------------------------------


def convnet_phase(jax, meter, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.convnet import ConvNetExecutor
    from repro.core.zoo import vgg16
    from repro.kernels import ops

    dev = jax.devices()[0]
    layers = vgg16()
    n_conv = sum(1 for l in layers if l.kind == "conv")
    c0 = meter.seconds
    pallas = ConvNetExecutor(layers, impl="pallas")
    xla = ConvNetExecutor(layers, impl="xla")
    kp, kx = jax.random.split(jax.random.key(seed + 1))
    params = pallas.init(kp, dtype=jnp.bfloat16)
    x = jax.random.normal(kx, (VGG_BATCH, VGG_PX, VGG_PX, 3), jnp.bfloat16)
    f_pallas = jax.jit(pallas.apply).lower(params, x).compile()
    n_kernels = f_pallas.as_text().count("tpu_custom_call")
    check(n_kernels >= n_conv,
          f"{n_kernels} compiled kernels for {n_conv} conv layers")
    f_xla = jax.jit(xla.apply)
    want = jax.device_get(f_xla(params, x))
    t0 = time.perf_counter()
    got = jax.block_until_ready(f_pallas(params, x))
    wall = time.perf_counter() - t0
    got = jax.device_get(got)
    check(got.shape == (VGG_BATCH, 1000), f"VGG16 output shape {got.shape}")
    check(bool(np.isfinite(np.asarray(got, np.float32)).all()),
          "non-finite VGG16 logits")
    err = rel_err(got, want)
    check(err <= VGG_LOGITS_RTOL,
          f"VGG16 stream_mac_conv vs XLA: rel err {err} > {VGG_LOGITS_RTOL}")

    # a strided layer (ResNet's 7x7/2 stem) compiles for 32-bit data ...
    ks = jax.random.split(jax.random.key(seed + 2), 2)
    xs = jax.random.normal(ks[0], (VGG_BATCH, VGG_PX, VGG_PX, 3), jnp.float32)
    ws = jax.random.normal(ks[1], (7, 7, 3, 64), jnp.float32) * 0.08
    ys = ops.stream_mac_conv(xs, ws, stride=(2, 2), padding=(3, 3))
    ref = jax.lax.conv_general_dilated(
        xs, ws, (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    s_err = rel_err(jax.device_get(ys), jax.device_get(ref))
    check(s_err <= STRIDED_RTOL,
          f"strided stream_mac_conv vs XLA: rel err {s_err} > {STRIDED_RTOL}")
    # ... and refuses 16-bit data instead of quietly leaving the kernel
    refused = False
    try:
        ops.stream_mac_conv(xs.astype(jnp.bfloat16), ws.astype(jnp.bfloat16),
                            stride=(2, 2), padding=(3, 3))
    except NotImplementedError:
        refused = True
    check(refused, "strided bf16 stream_mac_conv did not refuse")
    report("vgg16", batch=VGG_BATCH, px=VGG_PX, conv_layers=n_conv,
           compiled_kernels=n_kernels, pallas_batch_wall_s=wall,
           pallas_vs_xla_rel_err=err, strided_f32_rel_err=s_err,
           compile_s_phase=meter.seconds - c0,
           peak_bytes_in_use=peak_bytes(dev), **meter.snapshot())


# ---------------------------------------------------------------------------
# four chips: replicas behind the router
# ---------------------------------------------------------------------------


def four_chip_phase(jax, meter, seed: int) -> None:
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serve import CubeRouter, ServeEngine

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, JAX has {len(devs)}")
    devs = devs[:4]
    cfg = get_arch(SERVE_ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    n, max_new = 2 * SERVE_LANES, MAX_NEW
    reqs = make_requests(n, PROMPT_LENGTHS, cfg.vocab_size, max_new, seed)

    one = ServeEngine(model, params, serve_config(), device=devs[0])
    for r in reqs():
        one.submit(r)
    t0 = time.perf_counter()
    want = check_tokens(one.run(), n, max_new, cfg.vocab_size)
    one_wall = time.perf_counter() - t0

    router = CubeRouter(model, params, serve_config(), n_cubes=4,
                        policy="least_loaded", devices=devs)
    for r in reqs():
        router.submit(r)
    t0 = time.perf_counter()
    got = check_tokens(router.run(), n, max_new, cfg.vocab_size)
    four_wall = time.perf_counter() - t0
    mismatched = sorted(u for u in want if got[u] != want[u])
    check(not mismatched, f"router tokens differ from one engine's for uids "
                          f"{mismatched}")

    placed = []
    for e in router.engines:
        on = {d for leaf in jax.tree.leaves(e.cache.pools)
              for d in leaf.devices()}
        on |= {d for leaf in jax.tree.leaves(e.params) for d in leaf.devices()}
        check(len(on) == 1, f"an engine spans devices {on}")
        placed.append(on.pop())
    check(len(set(placed)) == 4, f"engines share devices: {placed}")
    report("four_chips", arch=cfg.name, requests=n,
           tokens=sum(len(t) for t in got.values()),
           identical_by_uid=True, routed=router.routed,
           engine_devices=[str(d) for d in placed],
           one_engine_wall_s=one_wall, four_engine_wall_s=four_wall,
           peak_bytes_in_use=[peak_bytes(d) for d in devs],
           **meter.snapshot())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase "
                         "(needs a host with four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}; "
              "no phase was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    print(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, compile cache "
          f"{cache_dir}", flush=True)
    if args.four_chips:
        four_chip_phase(jax, meter, args.seed)
    else:
        serve_phase(jax, meter, args.seed)
        convnet_phase(jax, meter, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
