"""Per-layer metric ``prefix_hit_share`` (batcher): prompt tokens served
from cached pages over prompt tokens admitted in the window, in %."""

from readers import share


def read(m):
    admitted = sum(ell for _, ell in m.window.prefills)
    return share(m.counters["prefill_tokens_skipped"], admitted)
