"""Per-layer metric ``prefill_mfu`` (model step): operations the admitted
prompts need, counting neither padding nor cached tokens, over what the chip
could do in the admission programs' device time, in %."""

from readers import ADMIT_PROGRAM, share


def read(m):
    t = m.trace.modules_matching(ADMIT_PROGRAM)
    if not m.window.prefills or not t:
        return None
    need = sum(m.flops.prefill_flops(m.dims, cached, ell)
               for cached, ell in m.window.prefills)
    return share(need, t * m.peak["bf16_flops_per_s"])
