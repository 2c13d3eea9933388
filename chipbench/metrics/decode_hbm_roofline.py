"""Model step: the traced decode steps' share of their roofline.  The least
time is the larger of their FLOPs over peak and their HBM bytes over
bandwidth (``work/``: the weights once a step, and every decoded token's
KV over its context and its lane state read and written); the time is the
summed device time of the decode program (the jitted ``_decode_impl``)
in the traced window."""

PROGRAM = "_decode_impl"


def read(rec):
    t = rec.trace
    steps = getattr(rec.work, "decode_steps", None)
    if t is None or not rec.decode_ctx or steps is None:
        return None
    n, ns = t.module(PROGRAM)
    if not n or ns <= 0:
        return None
    flops, nbytes = steps(rec.cfg, n // t.n_planes, rec.decode_ctx)
    least = max(flops / rec.peaks["bf16_flops_per_s"],
                nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / t.n_planes)
