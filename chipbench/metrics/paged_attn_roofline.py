"""Kernels: the Pallas paged decode-attention kernel's share of its
roofline.  The least time is the larger of its FLOPs over peak and its
bytes over HBM bandwidth (``work/``, from the context of every token the
traced steps decoded, in every layer); the time is the summed device time
of the kernel's events in the traced window."""

KERNEL = "paged_attention"  # the custom call's HLO name


def read(rec):
    t = rec.trace
    if t is None or not rec.decode_ctx:
        return None
    kernel_s = t.op_ns(KERNEL) * 1e-9 / t.n_planes
    if kernel_s <= 0:
        return None
    layers = rec.work.dims(rec.cfg)["layers"]
    flops, nbytes = rec.work.paged_attn_call(rec.cfg, rec.decode_ctx)
    least = max(flops / rec.peaks["bf16_flops_per_s"],
                nbytes / rec.peaks["hbm_bytes_per_s"]) * layers
    return 100.0 * least / kernel_s
