"""Kernels: the Pallas paged decode-attention kernel's share of its
roofline in a model whose attention layers are only some of its layers
(the work module's ``attn_layers``).  The least time is the larger of the
kernel's FLOPs over peak and its bytes over HBM bandwidth, from the context
of every token the traced steps decoded, in every attention layer; the time
is the summed device time of the kernel's events in the traced window."""

KERNEL = "paged_attention"  # the custom call's HLO name


def read(rec):
    t = rec.trace
    layers = getattr(rec.work, "attn_layers", None)
    if t is None or not rec.decode_ctx or layers is None:
        return None
    kernel_s = t.op_ns(KERNEL) * 1e-9 / t.n_planes
    if kernel_s <= 0:
        return None
    flops, nbytes = rec.work.paged_attn_call(rec.cfg, rec.decode_ctx)
    least = max(flops / rec.peaks["bf16_flops_per_s"],
                nbytes / rec.peaks["hbm_bytes_per_s"]) * layers(rec.cfg)
    return 100.0 * least / kernel_s
