"""Per-layer metric ``moe_gmm_roofline`` (kernels): the least time the held
experts' grouped products need at the chip's peaks (the larger of
operations / FLOP/s and bytes / HBM bytes/s; the family's ``gmm_work`` from
the batcher's expert counters: 6 d d_expert operations per assignment routed
to a held expert, each hit (layer, expert)'s weights read once, plus the
rows in and out) over the summed device time of the grouped kernel's events,
in %.

The kernel is megablox's grouped matmul (``models/moe.grouped_matmul``),
whose events on a TPU are ``gmm``, ``gmm.1``, ...  The trace keeps operation time by name, not by
program, so admissions' grouped products are in the time, and their
counters (``admit_moe_*``) in the work, beside the decode chunks'.  A window
with no expert counters (a model without experts, or a program that does
not count them) reads nothing."""

from readers import share

GMM_KERNEL = r"^gmm(\.\d+)?$"


def read(m):
    c = m.counters
    assignments = (c.get("moe_held_assignments", 0)
                   + c.get("admit_moe_held_assignments", 0))
    hit = (c.get("moe_held_experts_hit", 0)
           + c.get("admit_moe_held_experts_hit", 0))
    t = m.trace.ops_matching(GMM_KERNEL)
    if not assignments or not t:
        return None
    ops, nbytes = m.flops.gmm_work(m.dims, assignments, hit)
    least = max(ops / m.peak["bf16_flops_per_s"],
                nbytes / m.peak["hbm_bytes_per_s"])
    return share(least, t)
