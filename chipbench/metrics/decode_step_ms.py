"""Per-layer metric ``decode_step_ms`` (engine): device time of the decode
chunk programs per decode step."""

from readers import decode_time_s


def read(m):
    steps, t = m.counters["steps"], decode_time_s(m)
    return None if not steps or not t else 1e3 * t / steps
