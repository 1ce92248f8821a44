"""Per-layer metric ``expert_batch_tokens`` (model step): the tokens each
held expert computes per decode step, Δ assignments routed to held experts
over Δ held (layer, expert) pairs that received at least one, summed over
the decode chunks' steps and layers (the batcher's expert counters).  Read
beside ``batch_occupancy``: it grows with the batch each expert sees."""


def read(m):
    c = m.counters
    hit = c.get("moe_held_experts_hit", 0)
    if not hit:
        return None
    return c["moe_held_assignments"] / hit
