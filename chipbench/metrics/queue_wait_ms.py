"""Per-layer metric ``queue_wait_ms`` (batcher): mean time a request waited
in the batcher's queue, from ``submit`` (or a requeue) to the admission
that took it, over the joins in the window, from the batcher's counters
``queue_wait_us`` and ``admitted``.  Nothing to read on a program without
them."""


def read(m):
    c = m.counters
    n, t = c.get("admitted"), c.get("queue_wait_us")
    return None if not n or t is None else t / 1e3 / n
