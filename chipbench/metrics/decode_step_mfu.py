"""Per-layer metric ``decode_step_mfu`` (model step): operations the decoded
tokens need over what the chip could do in the decode programs' device time,
in %."""

from readers import decode_time_s, share


def read(m):
    t = decode_time_s(m)
    if not m.window.decode_ctxs or not t:
        return None
    need = m.flops.decode_flops(m.dims, m.window.decode_ctxs)
    return share(need, t * m.peak["bf16_flops_per_s"])
