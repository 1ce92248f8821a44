"""Per-layer metric ``traced_ttft_p90_ms`` (batcher): p90 (nearest rank) of
arrival to first token on the host over the requests that arrived in the
traced window; a request with no first token at the close counts with its
age then.  The end-to-end TTFT tail of a full window, which the seed moves
too far to hold to a bound, read here beside the traced window's
admissions."""

from clock import percentile


def read(m):
    return None if not m.ttft_s else 1e3 * percentile(m.ttft_s, 0.9)
