"""Per-layer metric ``admit_plan_ms`` (batcher): host time the batcher spent
planning admissions (its ``admit.plan`` phase: deadline shedding, the
queue's prefix witness, prefix-cache plans, the page ledger and eviction)
per request admitted in the window, from the batcher's counters
``admit_plan_us`` and ``admitted``.  Nothing to read on a program without
them."""


def read(m):
    c = m.counters
    n, t = c.get("admitted"), c.get("admit_plan_us")
    return None if not n or t is None else t / 1e3 / n
