"""Per-layer metric ``decode_attn_roofline`` (kernels): the least time the
decoded tokens' paged attention needs at the chip's peaks (the larger of
operations / FLOP/s and bytes / HBM bytes/s, counted from shapes) over the
summed device time of the kernel's events, in %."""

from readers import DECODE_ATTN_KERNEL, share


def read(m):
    t = m.trace.ops_matching(DECODE_ATTN_KERNEL)
    if not m.window.decode_ctxs or not t:
        return None
    ops = nbytes = 0.0
    for ctx in m.window.decode_ctxs:
        o, b = m.flops.decode_attn_work(m.dims, ctx, m.page_size)
        ops += o
        nbytes += b
    least = max(ops / m.peak["bf16_flops_per_s"],
                nbytes / m.peak["hbm_bytes_per_s"])
    return share(least, t)
