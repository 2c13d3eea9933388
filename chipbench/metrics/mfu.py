"""Model step: model FLOPs of the traced window over the window's length
times the chips' bf16 peak.  Decode tokens count their projections, head
and attention over their context (``work/``); prompt tokens count their
projections and MLP only, so the share is a lower bound."""


def read(rec):
    t = rec.trace
    if t is None or t.window_ns <= 0 or not rec.decode_ctx:
        return None
    w = rec.work
    flops = sum(w.decode_token_flops(rec.cfg, c) for c in rec.decode_ctx)
    flops += rec.prefill_tokens * w.prefill_token_flops(rec.cfg)
    peak = rec.peaks["bf16_flops_per_s"] * rec.chips
    return 100.0 * flops / (t.window_ns * 1e-9 * peak)
