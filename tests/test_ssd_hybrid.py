"""The Mamba-2 / attention hybrid (granite-4.0-h-micro) against the plain
float32 reference in ``chipbench/reference/ssd_hybrid.py``.

* ``ssd_chunked`` equals the token-by-token recurrence with a non-zero
  ``dt_bias`` and with chunks whose decay spans far more than 60;
* ``ssm_extend`` across chunk boundaries and ``ssm_decode`` after it equal
  the reference mixer;
* the reduced granite configuration served by ``ServeEngine`` (chunked
  prefill, then decode through the page pools) gives the reference's
  logits;
* a lane's state is written in place (the donated old leaf is deleted), and
  the write has its span and histogram.
"""
import copy
import dataclasses
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.harness import registry, weights  # noqa: E402
from chipbench.program import ssd_hybrid as prog  # noqa: E402
from chipbench.reference import ssd_hybrid as ref  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.configs.base import SSMConfig  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import DEFAULT_RULES, AxisRules  # noqa: E402
from repro.models.ssm import (  # noqa: E402
    ssd_chunked,
    ssm_block,
    ssm_cache_spec,
    ssm_decode,
    ssm_extend,
)
from repro.obs.trace import PH_BEGIN, PH_END  # noqa: E402
from repro.serve import (  # noqa: E402
    AdmissionConfig,
    CacheConfig,
    EngineConfig,
    ObsConfig,
    PagedKVCache,
    Request,
    ServeEngine,
)

RULES = AxisRules(DEFAULT_RULES)


def small_cfg(periods: int = 2, dtype: str = "float32") -> dict:
    """granite4_h_micro.json at the ``.reduced()`` widths: hidden 128, 4
    heads of 32 over 2 KV heads, MLP 256, vocab 512, 8 SSM heads of 32 with
    state 16 and chunk 16; ``periods`` periods of the published pattern."""
    cfg = copy.deepcopy(registry.load_json(
        registry.BENCH / "configs" / "granite4_h_micro.json"))
    per = cfg["layer_types"][:10]
    cfg.update(hidden_size=128, intermediate_size=256,
               shared_intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512, mamba_n_heads=8,
               mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=16,
               num_hidden_layers=10 * periods, layer_types=per * periods,
               dtype=dtype)
    return cfg


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _identity(a):
    return a


# ---------------------------------------------------------------------------
# The SSD against the recurrence
# ---------------------------------------------------------------------------


class _Cfg:
    ssm = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=8, chunk=16)


@pytest.mark.parametrize("decay", ["far", "slow"])
@pytest.mark.parametrize("chunk", [8, 32])
def test_ssd_chunked_matches_recurrence(decay, chunk):
    """Exact for any decay: with A up to -16 and dt near 1 a chunk's decay
    spans hundreds (the old +-60 clip of the factorized form failed here);
    with slow decays the state carries across chunks.  ``dt_bias`` is
    non-zero in both."""
    b, s, h, p, n = 2, 64, 4, 8, 16
    k = jax.random.split(jax.random.key(0), 7)
    xh = jax.random.normal(k[0], (b, s, h, p))
    bb = jax.random.normal(k[1], (b, s, n))
    cc = jax.random.normal(k[2], (b, s, n))
    dt = jax.random.normal(k[3], (b, s, h))
    if decay == "far":
        a_log = jnp.log(jax.random.uniform(k[4], (h,), minval=8.0, maxval=16.0))
        dt_bias = jnp.full((h,), 1.0)
    else:
        a_log = jnp.log(jax.random.uniform(k[4], (h,), minval=0.5, maxval=1.0))
        dt_bias = jnp.full((h,), -4.0)
    d_skip = jax.random.normal(k[5], (h,))
    h0 = jax.random.normal(k[6], (b, h, p, n))

    dts = jax.nn.softplus(dt + dt_bias)
    span = float(jnp.min(jnp.sum(dts[:, :chunk] * jnp.exp(a_log), axis=1)))
    if decay == "far":
        assert span > 60.0                 # a chunk decays by more than e^60
    else:
        assert span < 2.0                  # state survives whole chunks

    y, final = ssd_chunked(_Cfg, xh, bb, cc, dt, a_log, d_skip, dt_bias, h0,
                           chunk=chunk)
    for i in range(b):
        y_r, h_r = ref.ssd_recurrence(xh[i], dts[i], -jnp.exp(a_log), bb[i],
                                      cc[i], d_skip, h0[i])
        scale = float(jnp.max(jnp.abs(y_r)))
        # float32 both sides: the chunked form takes each decay as a
        # difference of cumulative sums, the recurrence multiplies per-token
        # factors; they part by float32 rounding over <= 64 tokens
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-5 * scale)
        np.testing.assert_allclose(np.asarray(final[i]), np.asarray(h_r),
                                   rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(h_r))))


# ---------------------------------------------------------------------------
# The mixer's prefill and decode paths against the reference mixer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stated", ["float32", "bfloat16"])
def test_program_holds_stated_state_dtype(stated):
    """The adapter refuses a program whose lane state is not the dtype the
    configuration states: a bf16 state reads a logit gap like the float32
    state's on the chip, so the check alone would not see it."""
    cfg = small_cfg(periods=1, dtype="bfloat16")
    cfg["ssm_state_dtype"] = stated
    if stated == "float32":
        arch = prog.arch(cfg)
        assert ssm_cache_spec(arch, 2)["state"].dtype == jnp.float32
    else:
        with pytest.raises(ValueError, match="states bfloat16"):
            prog.arch(cfg)


@pytest.fixture(scope="module")
def one_mixer():
    """A reduced granite arch in float32, one period's reference weights,
    and the program's parameters of its first mamba layer."""
    cfg = small_cfg(periods=1)
    arch = prog.arch(cfg)
    w = _f32(weights.layer_weights(ref, cfg, weights.seed_bits(11), 0))
    p = {"in_proj": w["m_in_proj"][0], "conv_w": w["m_conv_w"][0],
         "conv_b": w["m_conv_b"][0], "a_log": w["m_a_log"][0],
         "d_skip": w["m_d"][0], "dt_bias": w["m_dt_bias"][0],
         "norm": w["m_norm"][0], "out_proj": w["m_out_proj"][0]}
    x = jax.random.normal(jax.random.key(5), (1, 64, arch.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(cfg, w, 0, x[0], _identity)
    return arch, p, x, np.asarray(want)


def _close(got, want):
    # float32 program and reference: the chunked SSD against the recurrence
    # and the same projections, rounding only
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3,
                               atol=1e-4 * float(np.max(np.abs(want))))


def test_ssm_block_matches_reference_mixer(one_mixer):
    arch, p, x, want = one_mixer
    y, _ = ssm_block(arch, p, x, RULES)
    _close(y[0], want)


def test_ssm_extend_across_chunk_boundaries(one_mixer):
    """Extends of 24 and 40 tokens at chunk 16: each slice threads the state
    and the conv ring into the next, through chunk boundaries inside and
    between extends."""
    arch, p, x, want = one_mixer
    assert arch.ssm.chunk == 16
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         ssm_cache_spec(arch, 1))
    y1, cache = ssm_extend(arch, p, x[:, :24], cache, RULES)
    y2, cache = ssm_extend(arch, p, x[:, 24:], cache, RULES)
    _close(jnp.concatenate([y1, y2], axis=1)[0], want)


def test_ssm_decode_after_extend_matches_reference_mixer(one_mixer):
    arch, p, x, want = one_mixer
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         ssm_cache_spec(arch, 1))
    _, cache = ssm_extend(arch, p, x[:, :40], cache, RULES)
    ys = []
    for t in range(40, 64):
        y, cache = ssm_decode(arch, p, x[:, t: t + 1], cache, RULES)
        ys.append(y[0, 0])
    _close(jnp.stack(ys), want[40:])


# ---------------------------------------------------------------------------
# The whole model through ServeEngine against the reference's logits
# ---------------------------------------------------------------------------


def _program(cfg: dict, seed: int):
    arch = prog.arch(cfg)
    model = build_model(arch)
    bits = weights.seed_bits(seed)
    params = prog.params(cfg, arch, weights.global_weights(ref, cfg, bits),
                         weights.stacked_weights(ref, cfg, bits))
    params = jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), params,
                          model.abstract())
    return model, params


def _reference_logits(cfg: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    bits = weights.seed_bits(seed)
    with jax.default_matmul_precision("highest"):
        g = _f32(weights.global_weights(ref, cfg, bits))
        x = ref.embed(cfg, g, jnp.asarray(tokens))
        for layer in range(ref.n_layers(cfg)):
            w = _f32(weights.layer_weights(ref, cfg, bits, layer))
            x = ref.layer(cfg, w, x, _identity)
        return np.asarray(ref.logits(cfg, g, x, _identity))


def _serve_and_capture(model, params, prompts, max_new, prefill_chunk):
    """Serve greedy requests; capture the logits each extend and decode
    step produced, keyed by (request uid, position)."""
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=2, max_len=96,
        cache=CacheConfig(page_size=8, attn_impl="pallas"),
        admission=AdmissionConfig(prefill_chunk=prefill_chunk,
                                  async_prefill=False)))
    seen = {}
    extend, decode = eng._extend, eng._decode

    def spy_extend(params, tree, toks, start):
        logits, tree = extend(params, tree, toks, start)
        # the prompts differ in length, so a last position names a request
        seen[("extend", int(start) + toks.shape[1] - 1)] = np.asarray(
            logits[0, -1], np.float32)
        return logits, tree

    def spy_decode(params, pools, bt, toks, positions, active):
        choice, logits, pools = decode(params, pools, bt, toks, positions,
                                       active)
        for lane, st in eng.sched.running.items():
            seen[("decode", st.req.uid, int(positions[lane]))] = np.asarray(
                logits[lane, 0], np.float32)
        return choice, logits, pools

    eng._extend, eng._decode = spy_extend, spy_decode
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs, seen


@pytest.mark.parametrize("dtype,tol", [
    # float32 program: the chunked SSD, paged attention and the recurrence
    # agree to float32 rounding through 20 layers (read 1.1e-5 on logits of
    # unit spread)
    ("float32", 1e-4),
    # bf16 as served: weights, activations and the residual stream of 8
    # significant bits, compounded through 20 layers of two branches each
    # (read 0.22; leaving out dt_bias reads 4.9)
    ("bfloat16", 0.5),
])
def test_served_logits_match_reference(dtype, tol):
    """Two ragged requests, chunked prefill at 16 (chunks of 16, 16 and 8
    tokens), then 18 decode steps each through the page pools and the
    Pallas paged kernel (interpret mode here): every logit row the engine
    computed equals the reference's at its position."""
    cfg = small_cfg(periods=2, dtype=dtype)
    seed = 2**33 + 16
    model, params = _program(cfg, seed)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (40, 23)]
    reqs, seen = _serve_and_capture(model, params, prompts, max_new=19,
                                    prefill_chunk=16)
    n_decode = 0
    for r in reqs:
        assert len(r.out_tokens) == 19
        full = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1])])
        want = _reference_logits(cfg, seed, full)
        p = len(r.prompt)
        got = [(seen[("extend", p - 1)], p - 1)]
        for pos in range(p, p + 18):
            got.append((seen[("decode", r.uid, pos)], pos))
            n_decode += 1
        for row, pos in got:
            np.testing.assert_allclose(row[:512], want[pos], rtol=0, atol=tol)
    assert n_decode == 36


# ---------------------------------------------------------------------------
# The lane state write: in place, spanned and timed
# ---------------------------------------------------------------------------


def test_state_write_is_in_place():
    cfg = dataclasses.replace(get_arch("granite-4.0-h-micro").reduced(),
                              decode_unroll_layers=False)
    model = build_model(cfg)
    cache = PagedKVCache(model, lanes=3, n_pages=8, page_size=8, max_len=32)
    tree = jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype),
                        model.cache_specs(1, 32))
    old = cache.pools[0]["s0_ssm"]["state"]
    cache.write_state(1, tree)
    assert old.is_deleted()                      # donated, not copied
    st = np.asarray(cache.pools[0]["s0_ssm"]["state"])
    assert (st[:, 1] == 1).all() and (st[:, 0] == 0).all() and (st[:, 2] == 0).all()
    # write_prefill's state branch takes the same in-place path
    old = cache.pools[0]["s1_ssm"]["conv"]
    cache.write_prefill(cache.acquire(8), tree, lane=2)
    assert old.is_deleted()
    assert (np.asarray(cache.pools[0]["s1_ssm"]["conv"])[:, 2] == 1).all()


def _profiled_names(trace_dir) -> set:
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in pd.planes for line in plane.lines
            for e in line.events}


@pytest.mark.parametrize("arch,has_state", [("granite-4.0-h-micro", True),
                                            ("qwen2.5-3b", False)])
def test_state_write_span_and_histogram(arch, has_state, tmp_path):
    """Each admission of a state family writes its lane under
    ``lanes.state`` (inside ``lanes.fill``) and observes
    ``lanes.state_write_s`` once; a stateless family observes nothing."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=2, max_len=48, admission=AdmissionConfig(prefill_chunk=8),
        obs=ObsConfig(trace=True, trace_annotations=True)))
    rng = np.random.default_rng(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            eng.submit(Request(uid=i, max_new_tokens=4,
                               prompt=rng.integers(0, 512, 10 + i)))
        eng.run()
    finally:
        jax.profiler.stop_trace()
    h = eng.telemetry()["histograms"]["lanes.state_write_s"]
    ring = {e["name"] for e in eng.tracer.events()}
    profiled = _profiled_names(tmp_path)
    if has_state:
        assert h["count"] == 3 and h["sum"] > 0
        assert {"lanes.fill", "lanes.state"} <= ring
        assert {"repro.lanes.fill", "repro.lanes.state"} <= profiled
        # every lanes.state span opens and closes inside a lanes.fill span
        open_, inside = [], 0
        for e in eng.tracer.events():
            if e["name"] not in ("lanes.fill", "lanes.state"):
                continue
            if e["ph"] == PH_BEGIN:
                if e["name"] == "lanes.state":
                    assert open_ == ["lanes.fill"]
                    inside += 1
                open_.append(e["name"])
            elif e["ph"] == PH_END:
                assert open_.pop() == e["name"]
        assert inside == 3
    else:
        assert h["count"] == 0
        assert "lanes.state" not in ring
        assert "repro.lanes.state" not in profiled
