"""What the per-layer readers share.

A per-layer metric ``<name>`` is read by ``chipbench/metrics/<name>.py``,
whose ``read(m)`` takes a ``run.Measured`` and returns the metric's value,
or ``None`` when its window holds nothing to read (no decode step, no
admission, no kernel event); the harness then leaves the metric out.  A
share of a peak or of a roofline is never clamped: one above 100% means an
operation or byte count is too high or a time leaves out part of the work.
"""

from __future__ import annotations

#: program names (``jit_<function>``) in the device trace
DECODE_PROGRAM = r"jit_decode_chunk$"
ADMIT_PROGRAM = r"jit_admit_step$"
#: the paged attention kernel's custom calls (``kernels/paged_attention``,
#: named by its jitted wrapper) in the device trace
DECODE_ATTN_KERNEL = r"^paged_(verify|decode)_attention"


def share(num, den):
    """``num / den`` in %, or ``None`` when there is nothing to divide by."""
    return None if not den else 100.0 * num / den


def decode_time_s(m):
    """Device time of the decode chunk programs in the traced window."""
    return m.trace.modules_matching(DECODE_PROGRAM)
