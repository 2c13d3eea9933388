"""The comparison that decides ``correct``: served tokens against the plain
reference.

After the window, a sample of the requests the window finished (the one
with the most served tokens first, then others in an order the seed sets)
is run through the family's float32 reference, prompt and served tokens
together, layer by layer with the weights drawn again from the seed.  At
every position that produced a served token the reference's best logit is
compared with its logit of that token; the widest such gap is the number
compared.  Greedy decoding serves the argmax, so a sound program reads only
the rounding of its own precision.

The control puts the reference, computed with fp8 (e4m3) operands in every
projection, in the program's place: at the same positions it reads the gap,
under the float32 reference, of the token that fp8 puts first.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.harness import weights

ROW_BLOCK = 256
PAD = 512          # sequence rows padded to a multiple of this, and the
                   # sample to a power of two, so few shapes ever compile


def sample(done: list, seed: int, min_tokens: int, max_requests: int) -> list:
    """(prompt, served tokens) pairs: the longest finished request, then
    others in a seeded order, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.uid)
    first = max(range(len(done)), key=lambda i: len(done[i].out_tokens))
    rng = np.random.default_rng([int(seed), 7])
    order = [first] + [int(i) for i in rng.permutation(len(done)) if i != first]
    out, n = [], 0
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        r = done[i]
        out.append((np.asarray(r.prompt, np.int32), list(r.out_tokens)))
        n += len(r.out_tokens)
    return out


def identity(a):
    return a


def fp8(a):
    """Round trip through float8 e4m3 with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _hidden(ref, cfg, bits, tokens, casts):
    """Last-layer hidden states (B, S, d) of the padded token rows, one
    stream per entry of ``casts``."""
    g = _f32(jax.jit(lambda b: weights.global_weights(ref, cfg, b))(bits))
    draw = jax.jit(lambda b, l: weights.layer_weights(ref, cfg, b, l))
    emb = jax.jit(lambda g, t: jax.vmap(lambda r: ref.embed(cfg, g, r))(t))
    x0 = emb(g, tokens)
    steps = [jax.jit(lambda w, x, c=c: jax.lax.map(
        lambda r: ref.layer(cfg, w, r, c), x)) for c in casts]
    xs = [x0 for _ in casts]
    for layer in range(ref.n_layers(cfg)):
        w = _f32(draw(bits, layer))
        xs = [step(w, x) for step, x in zip(steps, xs)]
    return g, xs


def gaps(ref, cfg: dict, seed: int, seqs: list, control: bool = False) -> dict:
    """Widest gap of the served tokens (and, with ``control``, of fp8's
    first choices) below the reference's best logit."""
    bits = weights.seed_bits(seed)
    rows_of = []
    s_max = 0
    for prompt, out in seqs:
        full = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        rows_of.append(full)
        s_max = max(s_max, len(full))
    s_pad = -(-s_max // PAD) * PAD
    b_pad = 1 << (len(seqs) - 1).bit_length()
    tokens = np.zeros((b_pad, s_pad), np.int32)
    for i, full in enumerate(rows_of):
        tokens[i, :len(full)] = full
    casts = [identity, fp8] if control else [identity]
    with jax.default_matmul_precision("highest"):
        g, xs = _hidden(ref, cfg, bits, jnp.asarray(tokens), casts)
        # rows that produced a served token, and that token
        idx, tgt = [], []
        for i, (prompt, out) in enumerate(seqs):
            p = len(prompt)
            idx += [(i, p - 1 + j) for j in range(len(out))]
            tgt += list(out)
        n = len(idx)
        n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
        bi = np.zeros(n_pad, np.int32)
        si = np.zeros(n_pad, np.int32)
        bi[:n], si[:n] = zip(*idx)
        tg = np.zeros(n_pad, np.int32)
        tg[:n] = tgt

        @jax.jit
        def block(g, x_ref, x_ctl, t):
            ref_logits = ref.logits(cfg, g, x_ref, identity)
            best = jnp.max(ref_logits, axis=-1)
            served = jnp.take_along_axis(ref_logits, t[:, None], -1)[:, 0]
            out = [best - served]
            if x_ctl is not None:
                first = jnp.argmax(ref.logits(cfg, g, x_ctl, fp8), axis=-1)
                out.append(best - jnp.take_along_axis(
                    ref_logits, first[:, None], -1)[:, 0])
            return out

        got = [[] for _ in casts]
        for b0 in range(0, n_pad, ROW_BLOCK):
            sl = slice(b0, b0 + ROW_BLOCK)
            rows = [x[bi[sl], si[sl]] for x in xs]
            res = block(g, rows[0], rows[1] if control else None,
                        jnp.asarray(tg[sl]))
            for k, r in enumerate(res):
                got[k].append(np.asarray(r))
    vals = [np.concatenate(v)[:n].astype(np.float64) for v in got]
    res = {"logit_gap": float(np.max(vals[0])), "tokens": n,
           "requests": len(seqs)}
    if control:
        res["control_logit_gap"] = float(np.max(vals[1]))
    return res
