"""Per-layer metric ``batch_occupancy`` (batcher): slot-steps that decoded a
token over slot-steps run in the window, in %, from the batcher's
counters."""

from readers import share


def read(m):
    c = m.counters
    return share(c["slot_busy_steps"], c["slot_total_steps"])
